"""Pallas TPU kernel: IVF cell-probed scan via scalar prefetch (DESIGN.md §IVF).

The fused flat-scan kernel (``fused_knn.py``) walks every database block; a
probe mask could zero the COMPUTE for unprobed cells but the blocks would
still stream through VMEM — on a bandwidth-bound scan that saves nothing.
This kernel prunes the *DMA* instead: the per-query-tile probe list rides in
as a scalar-prefetch operand (``pltpu.PrefetchScalarGridSpec``), available
before the kernel body runs, and the database BlockSpec's index map reads it
to choose which cell block each grid step fetches:

    gy block for (i, j, kd)  =  (probes[i, j], kd)

A cell whose id never appears in a tile's probe list is never named by the
index map, so its rows are never DMA'd — unprobed cells cost zero HBM
traffic, not just predicated compute.  The corpus must be in the cell-packed
layout (``core.ivf.pack_cells``): one cell == one contiguous ``cell_cap``-row
block, pad slots dead via a +inf ``hy``.

Probe lists are fixed-width unions padded with adjacent REPEATS of the last
real cell (``core.ivf.tile_probe_lists``).  A slot equal to its predecessor
is skipped entirely (``pl.when``) — and because consecutive grid steps with
an unchanged block index re-use the resident block, duplicate padding costs
neither compute nor a second DMA of that cell.

Everything else — fp32/bf16/int8 ``gy`` operand upcast in VMEM after the
(compressed) DMA, the per-row int8 scale folded into the rank-1 epilogue,
the bitonic K-buffer merge, the heap-top threshold skip — is inherited
unchanged from the flat fused kernel; candidate indices are emitted in
PACKED slot space (``slot = cell * cell_cap + lane``) and the caller maps
them back to corpus rows through ``row_of_slot``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import topk as T
from repro.core.distances import EXACT, get_distance, matmul_finalize
from repro.kernels._backend import VMEM_LIMIT, resolve_interpret
from repro.kernels.stream_topk import emit_running, fold_tile, init_running


def _kernel(K, W, nk, cell_cap, alpha, finalize, threshold_skip, scaled):
    def kernel(probe_ref, fx_ref, gy_ref, *refs):
        if scaled:
            gs_ref, hx_ref, hy_ref = refs[:3]
        else:
            gs_ref = None
            hx_ref, hy_ref = refs[:2]
        out_v_ref, out_i_ref, acc, run_v, run_i = refs[-5:]
        i, j, kd = pl.program_id(0), pl.program_id(1), pl.program_id(2)
        cell = probe_ref[i, j]
        # Padding repeats the previous slot's cell.  Its block DMA is elided
        # by the unchanged index map; its CANDIDATES are neutralized
        # arithmetically (tile -> +inf below) rather than by a pl.when skip:
        # a duplicate re-merge would push the same (value, slot) pairs into
        # the K-buffer twice, and a control-flow skip keyed on the scalar
        # operand miscompiles under an outer jit around shard_map on the
        # pinned toolchain (the select is data-flow, so it cannot).
        dup = jnp.logical_and(j > 0, cell == probe_ref[i, jnp.maximum(j - 1, 0)])

        pl.when(jnp.logical_and(j == 0, kd == 0))(
            lambda: init_running(run_v, run_i))

        @pl.when(kd == 0)
        def _init_acc():
            acc[...] = jnp.zeros_like(acc)

        # bf16/int8 gy upcasts in VMEM, AFTER the compressed DMA.
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
            # Pad slots arrive with hy == +inf; duplicate probe slots are
            # neutralized here (merging +inf is a no-op for the K-buffer).
            tile = jnp.where(dup, T.POS_INF, tile)
            # Global PACKED slot ids: the probed cell's block offset.
            fold_tile(run_v, run_i, tile, K, cell * cell_cap, threshold_skip)

        pl.when(jnp.logical_and(j == W - 1, kd == nk - 1))(
            lambda: emit_running(out_v_ref, out_i_ref, run_v, run_i))

    return kernel


@functools.partial(
    jax.jit,
    static_argnames=(
        "k",
        "distance",
        "cell_cap",
        "bm",
        "bd",
        "threshold_skip",
        "interpret",
    ),
)
def ivf_scan_pallas(
    probes: jnp.ndarray,
    fx: jnp.ndarray,
    gy: jnp.ndarray,
    hx: jnp.ndarray,
    hy: jnp.ndarray,
    k: int,
    *,
    cell_cap: int,
    gy_scale: jnp.ndarray | None = None,
    distance: str = "sqeuclidean",
    bm: int = 256,
    bd: int = 128,
    threshold_skip: bool | None = None,
    interpret: bool | None = None,
):
    """Cell-probed kNN scan over pre-mapped MXU-form operands.

    ``probes`` [m/bm, W] int32 per-query-tile cell lists (ascending unions,
    duplicate-padded — ``core.ivf.tile_probe_lists``); ``gy`` [S, d] the
    cell-packed corpus (S = ncells · cell_cap) in fp32/bf16/int8 (int8 passes
    ``gy_scale`` [1, S]); ``hx`` [m, 1] / ``hy`` [1, S] rank-1 terms, ``hy``
    pre-set to +inf on dead (pad/tombstoned) slots.

    Returns (values [m, K], indices [m, K]) ascending, K = next_pow2(k),
    indices in PACKED slot space (−1 = empty).
    """
    interpret = resolve_interpret(interpret)
    threshold_skip = T.resolve_threshold_skip(threshold_skip, pallas=True)
    dist = get_distance(distance)
    assert dist.matmul_form is not None, f"{distance} has no MXU form"
    assert gy.dtype in (jnp.float32, jnp.bfloat16, jnp.int8), gy.dtype
    m, d = fx.shape
    S = gy.shape[0]
    nt, W = probes.shape
    K = T.kernel_k(k)
    Wr = T.reduce_width(cell_cap, K)
    assert m % bm == 0 and nt == m // bm, (m, bm, nt)
    assert S % cell_cap == 0 and d % bd == 0, (S, cell_cap, d, bd)
    assert cell_cap % K == 0 and (cell_cap // K) & (cell_cap // K - 1) == 0, (
        cell_cap, K)
    nk = d // bd
    grid = (m // bm, W, nk)
    scaled = gy_scale is not None
    in_specs = [
        pl.BlockSpec((bm, bd), lambda i, j, kd, pr: (i, kd)),
        pl.BlockSpec((cell_cap, bd), lambda i, j, kd, pr: (pr[i, j], kd)),
    ]
    operands = [fx, gy]
    if scaled:
        in_specs.append(pl.BlockSpec((1, cell_cap),
                                     lambda i, j, kd, pr: (0, pr[i, j])))
        operands.append(gy_scale)
    in_specs += [
        pl.BlockSpec((bm, 1), lambda i, j, kd, pr: (i, 0)),
        pl.BlockSpec((1, cell_cap), lambda i, j, kd, pr: (0, pr[i, j])),
    ]
    operands += [hx, hy]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((bm, Wr), lambda i, j, kd, pr: (i, 0)),
            pl.BlockSpec((bm, Wr), lambda i, j, kd, pr: (i, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((bm, cell_cap), jnp.float32),
            pltpu.VMEM((bm, Wr), jnp.float32),
            pltpu.VMEM((bm, Wr), jnp.int32),
        ],
    )
    vals, idx = pl.pallas_call(
        _kernel(
            K,
            W,
            nk,
            cell_cap,
            dist.matmul_form.alpha,
            matmul_finalize(dist),
            threshold_skip,
            scaled,
        ),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((m, Wr), jnp.float32),
            jax.ShapeDtypeStruct((m, Wr), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            # Cell-wide blocks: past the 16 MiB default at cell_cap >= 1024.
            vmem_limit_bytes=VMEM_LIMIT,
        ),
        interpret=interpret,
        name="ivf_scan",
    )(probes, *operands)
    return vals[:, :T.next_pow2(k)], idx[:, :T.next_pow2(k)]
