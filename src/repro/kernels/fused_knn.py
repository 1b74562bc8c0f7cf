"""Pallas TPU kernel: FUSED distance + k-smallest selection (beyond-paper).

The paper stores each grid's distance tile to global memory (phase 1) and
re-reads it for selection (phase 2): 2 x O(GSIZE^2) HBM traffic per tile.  On
TPU the distance tile can stay in VMEM and be folded straight into the running
top-k buffer — the [n, n] intermediate never exists in HBM, so the kNN problem
moves from memory-bound to compute(MXU)-bound.  This is the same insight as
FlashAttention's online-softmax fusion, applied to selection instead of
softmax (DESIGN.md, "beyond paper").

Grid: (m/bm, n/bn, d/bd); the d-axis accumulates the MXU-form distance into a
VMEM accumulator; at the last d-chunk the finished tile is masked (column
padding + self-exclusion) and bitonic-merged into the per-row top-K scratch;
at the last column tile the K-buffer is emitted.

Quantized scan (DESIGN.md §Quantized): ``gy`` may be stored bf16 or int8 —
the DMA from HBM moves 2x/4x fewer database bytes, and the operand is
upcast to fp32 in VMEM right before the MXU dot.  int8 rows carry a per-row
symmetric scale folded into the same rank-1 epilogue as ``hy``:

    tile = finalize(alpha * (fx @ gy^T) * gy_scale + hx + hy)

so dequantization costs one extra [1, bn] VMEM multiply, never a second pass
over the database.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import topk as T
from repro.kernels._backend import resolve_interpret
from repro.core.distances import EXACT, get_distance, matmul_finalize
from repro.kernels.stream_topk import emit_running, fold_tile, init_running


def _kernel(K, nj, nk, bm, bn, alpha, finalize, n_real, exclude_self,
            threshold_skip, scaled, masked):
    def kernel(fx_ref, gy_ref, *refs):
        pos = 0
        gs_ref = qm_ref = None
        if scaled:
            gs_ref = refs[pos]
            pos += 1
        if masked:
            qm_ref = refs[pos]
            pos += 1
        hx_ref, hy_ref = refs[pos], refs[pos + 1]
        out_v_ref, out_i_ref, acc, run_v, run_i = refs[-5:]
        i, j, kd = pl.program_id(0), pl.program_id(1), pl.program_id(2)

        pl.when(jnp.logical_and(j == 0, kd == 0))(
            lambda: init_running(run_v, run_i))

        @pl.when(kd == 0)
        def _init_acc():
            acc[...] = jnp.zeros_like(acc)

        # bf16/int8 gy upcasts in VMEM, AFTER the (compressed) HBM->VMEM DMA.
        acc[...] += jax.lax.dot_general(
            fx_ref[...],
            gy_ref[...].astype(jnp.float32),
            (((1,), (1,)), ((), ())),
            precision=EXACT,
            preferred_element_type=jnp.float32,
        )

        @pl.when(kd == nk - 1)
        def _select():
            t = alpha * acc[...]
            if scaled:
                t = t * gs_ref[...]  # per-row int8 scale, rank-1 epilogue
            tile = finalize(t + hx_ref[...] + hy_ref[...])
            col = jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 1) + j * bn
            tile = jnp.where(col >= n_real, T.POS_INF, tile)
            if exclude_self:
                row = jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 0) + i * bm
                tile = jnp.where(row == col, T.POS_INF, tile)
            if masked:
                # Per-query filter bitmap (DESIGN.md §17) — a full [bm, bn]
                # VMEM block, because the rank-1 hy epilogue can only carry
                # per-ROW masks.  fp32 {0, 1} rather than i1: the mask block
                # then shares the fp32 tiling of every other operand.
                tile = jnp.where(qm_ref[...] != 0, tile, T.POS_INF)
            fold_tile(run_v, run_i, tile, K, j * bn, threshold_skip)
            pl.when(j == nj - 1)(
                lambda: emit_running(out_v_ref, out_i_ref, run_v, run_i))

    return kernel


@functools.partial(
    jax.jit,
    static_argnames=(
        "k",
        "distance",
        "bm",
        "bn",
        "bd",
        "n_real",
        "exclude_self",
        "threshold_skip",
        "interpret",
    ),
)
def fused_knn_pallas(
    fx: jnp.ndarray,
    gy: jnp.ndarray,
    hx: jnp.ndarray,
    hy: jnp.ndarray,
    k: int,
    *,
    gy_scale: jnp.ndarray | None = None,
    q_mask: jnp.ndarray | None = None,
    distance: str = "sqeuclidean",
    bm: int = 256,
    bn: int = 512,
    bd: int = 128,
    n_real: int,
    exclude_self: bool = False,
    threshold_skip: bool | None = None,
    interpret: bool | None = None,
):
    """Fused kNN over pre-mapped MXU-form operands (see ops.fused_knn).

    ``gy`` may be fp32, bf16, or int8 (then pass ``gy_scale`` [1, n] fp32 —
    the per-row symmetric scales, see module docstring).  ``threshold_skip``
    and ``interpret`` default to the backend policy (``None`` → skip on, and
    interpret off exactly on real TPUs) — see ``topk.resolve_threshold_skip``.

    ``q_mask``: optional [m, n] fp32 per-query filter bitmap (0 = masked,
    nonzero = allowed; DESIGN.md §17) blocked [bm, bn] alongside the
    distance tile — disallowed entries finalize to +inf exactly like column
    padding, so they can never enter the running top-K.

    Returns (values [m, K], indices [m, K]) ascending, K = next_pow2(k).
    """
    interpret = resolve_interpret(interpret)
    threshold_skip = T.resolve_threshold_skip(threshold_skip, pallas=True)
    dist = get_distance(distance)
    assert dist.matmul_form is not None, f"{distance} has no MXU form"
    assert gy.dtype in (jnp.float32, jnp.bfloat16, jnp.int8), gy.dtype
    m, d = fx.shape
    n = gy.shape[0]
    K = T.kernel_k(k)
    Wr = T.reduce_width(bn, K)
    assert m % bm == 0 and n % bn == 0 and d % bd == 0
    assert bn % K == 0 and (bn // K) & (bn // K - 1) == 0, (bn, K)
    nj, nk = n // bn, d // bd
    grid = (m // bm, nj, nk)
    scaled = gy_scale is not None
    masked = q_mask is not None
    in_specs = [
        pl.BlockSpec((bm, bd), lambda i, j, kd: (i, kd)),
        pl.BlockSpec((bn, bd), lambda i, j, kd: (j, kd)),
    ]
    operands = [fx, gy]
    if scaled:
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, kd: (0, j)))
        operands.append(gy_scale)
    if masked:
        assert q_mask.shape == (m, n), (q_mask.shape, m, n)
        in_specs.append(pl.BlockSpec((bm, bn), lambda i, j, kd: (i, j)))
        operands.append(q_mask)
    in_specs += [
        pl.BlockSpec((bm, 1), lambda i, j, kd: (i, 0)),
        pl.BlockSpec((1, bn), lambda i, j, kd: (0, j)),
    ]
    operands += [hx, hy]
    vals, idx = pl.pallas_call(
        _kernel(
            K,
            nj,
            nk,
            bm,
            bn,
            dist.matmul_form.alpha,
            matmul_finalize(dist),
            n_real,
            exclude_self,
            threshold_skip,
            scaled,
            masked,
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((bm, Wr), lambda i, j, kd: (i, 0)),
            pl.BlockSpec((bm, Wr), lambda i, j, kd: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, Wr), jnp.float32),
            jax.ShapeDtypeStruct((m, Wr), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.float32),
            pltpu.VMEM((bm, Wr), jnp.float32),
            pltpu.VMEM((bm, Wr), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
        name="fused_knn",
    )(*operands)
    return vals[:, :T.next_pow2(k)], idx[:, :T.next_pow2(k)]
