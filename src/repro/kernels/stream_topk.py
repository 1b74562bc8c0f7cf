"""Pallas TPU kernel: streaming k-smallest over column tiles (paper Sect. 6).

The paper's phase 2 gives each row to a thread block; threads stride the row
with coalesced reads, filter candidates against the heap top into thread-local
buffers, and push under a block lock.  The TPU mapping (DESIGN.md):

  per-thread heap      -> per-row ascending sorted K-buffer in VMEM scratch
  coalesced strided    -> (bm, bn) VMEM tile DMA of the distance matrix
  heap-top filter      -> whole-tile `pl.when(any(tile < kth_best))` skip
  buffered heap push   -> bitonic tile-reduce + O(log K) bitonic top-k merge

The selection network is static dataflow (lane rotations, selects and
min/max), so it vectorizes across the 8x128 VPU lanes with no
synchronization at all — the paper's lock disappears instead of being
emulated.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import topk as T
from repro.kernels._backend import resolve_interpret


def fold_tile(run_v, run_i, tile, K, col_offset, threshold_skip):
    """Fold a (bm, bn) distance tile into the running top-K VMEM buffers.

    The buffers are ``Wr`` lanes wide, the K kept values
    ascending in lanes [0, K); the tile's K smallest come out of the network
    descending, so one bitonic merge keeps the K smallest of both.  With
    ``threshold_skip`` a tile none of whose values beats a row's current
    k-th best is skipped whole.
    """
    def merge():
        idx = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 1) + col_offset
        tv, ti = T.reduce_topk(tile, idx, K, descending=True)
        mv, mi = T.merge_topk_bitonic(run_v[...], run_i[...], tv, ti, K)
        run_v[...] = mv
        run_i[...] = mi

    if threshold_skip:
        pl.when(jnp.any(tile < run_v[:, K - 1 : K]))(merge)
    else:
        merge()


def init_running(run_v, run_i):
    run_v[...] = jnp.full_like(run_v, T.POS_INF)
    run_i[...] = jnp.full_like(run_i, -1)


def emit_running(out_v_ref, out_i_ref, run_v, run_i):
    # The whole buffer: storing an int32 slice narrower than a lane tile
    # aborts the TPU compiler (libtpu 0.0.34), so callers slice [:, :K].
    out_v_ref[...] = run_v[...]
    out_i_ref[...] = run_i[...]


def _kernel(K, n_col_tiles, bn, threshold_skip):
    def kernel(x_ref, out_v_ref, out_i_ref, run_v, run_i):
        j = pl.program_id(1)
        pl.when(j == 0)(lambda: init_running(run_v, run_i))
        fold_tile(run_v, run_i, x_ref[...], K, j * bn, threshold_skip)
        pl.when(j == n_col_tiles - 1)(
            lambda: emit_running(out_v_ref, out_i_ref, run_v, run_i))

    return kernel


@functools.partial(
    jax.jit, static_argnames=("k", "bm", "bn", "threshold_skip", "interpret")
)
def stream_topk_pallas(
    x: jnp.ndarray,
    k: int,
    *,
    bm: int = 256,
    bn: int = 512,
    threshold_skip: bool | None = None,
    interpret: bool | None = None,
):
    """Ascending k smallest of each row of ``x`` [m, n] + int32 indices.

    Requires m % bm == 0, n % bn == 0, bn = next_pow2(k) * 2^t.
    Returns (values [m, K], indices [m, K]) with K = next_pow2(k); callers
    slice [:, :k].  ``interpret=None`` resolves backend-aware (Mosaic on a
    real TPU, the interpreter elsewhere); ``threshold_skip=None`` resolves to
    the Pallas policy (on) — see ``topk.resolve_threshold_skip``.
    """
    interpret = resolve_interpret(interpret)
    threshold_skip = T.resolve_threshold_skip(threshold_skip, pallas=True)
    m, n = x.shape
    K = T.kernel_k(k)
    Wr = T.reduce_width(bn, K)
    assert m % bm == 0 and n % bn == 0, (x.shape, bm, bn)
    assert bn % K == 0 and (bn // K) & (bn // K - 1) == 0, (bn, K)
    n_col_tiles = n // bn
    grid = (m // bm, n_col_tiles)
    vals, idx = pl.pallas_call(
        _kernel(K, n_col_tiles, bn, threshold_skip),
        grid=grid,
        in_specs=[pl.BlockSpec((bm, bn), lambda i, j: (i, j))],
        out_specs=[
            pl.BlockSpec((bm, Wr), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, Wr), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, Wr), jnp.float32),
            jax.ShapeDtypeStruct((m, Wr), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bm, Wr), jnp.float32),
            pltpu.VMEM((bm, Wr), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="stream_topk",
    )(x)
    return vals[:, :T.next_pow2(k)], idx[:, :T.next_pow2(k)]
