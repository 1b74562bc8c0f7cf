"""Pallas TPU kernel: IVF-PQ ADC scan — LUT accumulation over uint8 codes
(DESIGN.md §PQ).

The cell-probed scalar-quantized scan (``ivf_scan.py``) still streams d bytes
per probed row (int8) and scores with an MXU matmul.  This kernel streams
``m`` bytes per row — the PQ codes — and scores by asymmetric distance
computation: per query tile a ``(bm, m, 2^nbits)`` lookup table of subspace
partial dots (``core.pq.build_pq_luts``) is resident in VMEM, and a row's
score is the sum of its m table entries plus the rank-1 epilogue:

    tile[q, s] = finalize(Σ_j lut[q, j, codes[s, j]]  (+ qc[q, cell])
                          + hx[q] + hy[s])

TPU has no per-lane gather, so the LUT lookup is expressed as a one-hot
contraction on the MXU: the code block [m, cell_cap] expands to a one-hot
[m·2^nbits, cell_cap] operand and one ``dot_general`` against the flattened
[bm, m·2^nbits] LUT computes all m lookups and their sum at once.  That
trades MXU FLOPs (which the bandwidth-bound scan has to burn) for HBM bytes
(which it does not have): the database stream drops from d to m bytes/row.

VMEM budget (DESIGN.md §PQ): the LUT block is bm·m·2^nbits·4 B — 4 MiB at
the defaults (bm=256, m=16, nbits=8) — plus a transient one-hot
[m·2^nbits, min(cell_cap, ADC_CHUNK)] fp32 (at most 4 MiB: a wider cell is
scored in ADC_CHUNK-column pieces) and the K-buffers; inside the ~16 MiB
VMEM, and the LUT block is revisited (not re-DMA'd) across the probe axis
since its index map ignores j.

Probe-list machinery is inherited verbatim from ``ivf_scan.py``: the
per-query-tile union list rides in as a scalar-prefetch operand
(``pltpu.PrefetchScalarGridSpec``) and the code/hy/qc BlockSpecs' index maps
read it, so a cell absent from the list is never DMA'd — unprobed cells cost
zero HBM traffic.  Padding repeats the previous slot's cell; its candidates
are neutralized arithmetically (tile → +inf — same pinned-toolchain
rationale as ivf_scan).  ``qc`` is the residual-PQ cross term
``alpha · fx · centroid[cell]`` (``core.pq.pq_cell_bias``).  A [bm, 1] block
of it is not a legal TPU block, so the kernel fetches the lane-aligned
[bm, 128] block holding the probed cell's column and selects that lane.
Candidate indices are emitted in PACKED slot space.
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


ADC_CHUNK = 256  # code columns per one-hot contraction in the kernel


def adc_tile(lut_flat, codes_t, ncodes):
    """ADC scores [bm, cap] of one code block: one-hot MXU contraction.

    ``lut_flat`` [bm, m·ncodes] fp32 (the flattened per-query LUTs);
    ``codes_t`` [m, cap] uint8.  Shared verbatim by the Pallas kernel and the
    jnp reference path (``core.knn.quantized_scan``) so the two scores are
    bit-identical under the interpreter: same one-hot construction, same
    ``dot_general`` contraction, same operand shapes when the reference is
    tiled at tile_n = cell_cap.
    """
    m, cap = codes_t.shape
    iot = jax.lax.broadcasted_iota(jnp.int32, (m, ncodes, cap), 1)
    oh = (codes_t.astype(jnp.int32)[:, None, :] == iot).astype(jnp.float32)
    return jax.lax.dot_general(
        lut_flat,
        oh.reshape(m * ncodes, cap),
        (((1,), (0,)), ((), ())),
        precision=EXACT,
        preferred_element_type=jnp.float32,
    )


def _kernel(K, W, m, ncodes, cell_cap, finalize, threshold_skip, residual):
    def kernel(probe_ref, lut_ref, codes_ref, *refs):
        if residual:
            qc_ref, own_ref, hx_ref, hy_ref = refs[:4]
        else:
            qc_ref = None
            own_ref, hx_ref, hy_ref = refs[:3]
        out_v_ref, out_i_ref, run_v, run_i = refs[-4:]
        i, j = pl.program_id(0), pl.program_id(1)
        cell = probe_ref[i, j]
        # Padding repeats the previous slot's cell: block DMA elided by the
        # unchanged index map, candidates neutralized arithmetically below
        # (same pinned-toolchain rationale as ivf_scan: data-flow select,
        # never control flow keyed on the scalar operand).
        dup = jnp.logical_and(j > 0, cell == probe_ref[i, jnp.maximum(j - 1, 0)])

        pl.when(j == 0)(lambda: init_running(run_v, run_i))

        # The one-hot operand is m·2^nbits x chunk fp32 (4 MiB at m = 16,
        # nbits = 8): wider cells score chunk by chunk to stay in VMEM.
        chunk = min(cell_cap, ADC_CHUNK)
        t = jnp.concatenate(
            [adc_tile(lut_ref[...], codes_ref[:, c:c + chunk], ncodes)
             for c in range(0, cell_cap, chunk)], axis=1)
        if residual:
            # alpha·fx·centroid[cell]: the cell's lane of its 128-lane block;
            # a sum of one value and zeros, so the column is exact.
            lane = jax.lax.broadcasted_iota(jnp.int32, qc_ref.shape, 1)
            t = t + jnp.sum(jnp.where(lane == cell % T.LANES, qc_ref[...], 0.0),
                            axis=1, keepdims=True)
        tile = finalize(t + hx_ref[...] + hy_ref[...])
        # Pad slots arrive with hy == +inf; duplicate probe slots die here,
        # and so does the cell for every query of the tile that did not
        # probe it: the tile scans the union of its queries' cells, but an
        # approximate shortlist drawn from the union holds more impostors
        # than one drawn from the query's own nprobe cells.
        own = jnp.max((own_ref[...] == cell).astype(jnp.int32), axis=1,
                      keepdims=True) > 0
        tile = jnp.where(jnp.logical_and(own, jnp.logical_not(dup)), tile,
                         T.POS_INF)
        # Global PACKED slot ids: the probed cell's block offset.
        fold_tile(run_v, run_i, tile, K, cell * cell_cap, threshold_skip)
        pl.when(j == W - 1)(
            lambda: emit_running(out_v_ref, out_i_ref, run_v, run_i))

    return kernel


@functools.partial(
    jax.jit,
    static_argnames=(
        "k",
        "distance",
        "cell_cap",
        "ncodes",
        "bm",
        "threshold_skip",
        "interpret",
    ),
)
def pq_scan_pallas(
    probes: jnp.ndarray,
    luts: jnp.ndarray,
    codes_t: jnp.ndarray,
    hx: jnp.ndarray,
    hy: jnp.ndarray,
    k: int,
    *,
    cells: jnp.ndarray,
    cell_cap: int,
    ncodes: int,
    qc: jnp.ndarray | None = None,
    distance: str = "sqeuclidean",
    bm: int = 256,
    threshold_skip: bool | None = None,
    interpret: bool | None = None,
):
    """Cell-probed ADC scan over prebuilt LUT operands.

    ``probes`` [m/bm, W] int32 per-query-tile cell lists
    (``core.ivf.tile_probe_lists``); ``luts`` [m, mj·ncodes] fp32 flattened
    per-query tables (``core.pq.build_pq_luts`` reshaped); ``codes_t``
    [mj, S] uint8 TRANSPOSED cell-packed codes (S = ncells · cell_cap on the
    lane axis — the streamed operand wants the long axis last); ``hx`` [m, 1]
    / ``hy`` [1, S] rank-1 terms, ``hy`` pre-set to +inf on dead slots;
    ``qc`` [m, ncells] fp32 residual cross term (None = non-residual codes);
    ``cells`` [m, nprobe] int32 each query's own probed cells — a query
    keeps candidates from those cells only.

    Returns (values [m, K], indices [m, K]) ascending, K = next_pow2(k),
    indices in PACKED slot space (−1 = empty).
    """
    interpret = resolve_interpret(interpret)
    threshold_skip = T.resolve_threshold_skip(threshold_skip, pallas=True)
    dist = get_distance(distance)
    assert dist.matmul_form is not None, f"{distance} has no MXU form"
    assert codes_t.dtype == jnp.uint8, codes_t.dtype
    m = luts.shape[0]
    mj, S = codes_t.shape
    assert luts.shape[1] == mj * ncodes, (luts.shape, mj, ncodes)
    nt, W = probes.shape
    K = T.kernel_k(k)
    Wr = T.reduce_width(cell_cap, K)
    assert m % bm == 0 and nt == m // bm, (m, bm, nt)
    assert S % cell_cap == 0, (S, cell_cap)
    assert cell_cap % K == 0 and (cell_cap // K) & (cell_cap // K - 1) == 0, (
        cell_cap, K)
    grid = (m // bm, W)
    residual = qc is not None
    in_specs = [
        pl.BlockSpec((bm, mj * ncodes), lambda i, j, pr: (i, 0)),
        pl.BlockSpec((mj, cell_cap), lambda i, j, pr: (0, pr[i, j])),
    ]
    operands = [luts, codes_t]
    if residual:
        in_specs.append(pl.BlockSpec(
            (bm, T.LANES), lambda i, j, pr: (i, pr[i, j] // T.LANES)))
        operands.append(jnp.pad(qc, ((0, 0), (0, (-qc.shape[1]) % T.LANES))))
    in_specs.append(pl.BlockSpec((bm, cells.shape[1]),
                                 lambda i, j, pr: (i, 0)))
    operands.append(cells)
    in_specs += [
        pl.BlockSpec((bm, 1), lambda i, j, pr: (i, 0)),
        pl.BlockSpec((1, cell_cap), lambda i, j, pr: (0, pr[i, j])),
    ]
    operands += [hx, hy]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((bm, Wr), lambda i, j, pr: (i, 0)),
            pl.BlockSpec((bm, Wr), lambda i, j, pr: (i, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((bm, Wr), jnp.float32),
            pltpu.VMEM((bm, Wr), jnp.int32),
        ],
    )
    vals, idx = pl.pallas_call(
        _kernel(
            K,
            W,
            mj,
            ncodes,
            cell_cap,
            matmul_finalize(dist),
            threshold_skip,
            residual,
        ),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((m, Wr), jnp.float32),
            jax.ShapeDtypeStruct((m, Wr), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            # Cell-wide blocks: past the 16 MiB default at cell_cap >= 1024.
            vmem_limit_bytes=VMEM_LIMIT,
        ),
        interpret=interpret,
        name="pq_scan",
    )(probes, *operands)
    return vals[:, :T.next_pow2(k)], idx[:, :T.next_pow2(k)]
