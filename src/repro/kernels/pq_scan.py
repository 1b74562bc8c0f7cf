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

The MXU multiplies bf16.  The one-hot's 0s and 1s are exact in bf16, and the
float32 LUT splits exactly into three bf16 pieces (``split_lut``: hi, mid
and lo hold 3 x 8 significand bits, so hi + mid + lo == lut bit for bit).
The pieces stack into one [3, rows, m·2^nbits] bf16 operand, split once a
batch outside the kernel; a query tile's [3, bm, ·] block merges to
[3·bm, ·] and one default-precision pass with float32 accumulation scores
all three: the products are exact, so the tile keeps float32 accuracy
without a multi-pass float32 contraction.

VMEM budget (DESIGN.md §PQ): the LUT block is 3·bm·m·2^nbits·2 B — 6 MiB
at the defaults (bm=256, m=16, nbits=8), double-buffered — plus a transient
one-hot [m·2^nbits, min(cell_cap, ADC_CHUNK)] bf16 (at most 2 MiB: a wider
cell is scored in ADC_CHUNK-column pieces), its [3·bm, ADC_CHUNK] float32
product and the K-buffers; inside the 64 MiB scoped limit, and the LUT block
is revisited (not re-DMA'd) across the probe axis since its index map
ignores j.

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
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import topk as T
from repro.core.distances import get_distance, matmul_finalize
from repro.kernels._backend import VMEM_LIMIT, resolve_interpret
from repro.kernels.stream_topk import emit_running, fold_tile, init_running


ADC_CHUNK = 256  # code columns per one-hot contraction
LUT_PIECES = 3  # bf16 pieces that hold a float32 LUT entry exactly

# Bits of a float32 that a bf16 keeps: sign, exponent, 7 stored significand.
_BF16_BITS = np.uint32(0xFFFF0000)


def _bf16_head(x):
    """The leading bf16 of float32 ``x``, as a float32: ``x`` truncated to 8
    significand bits.  A bit mask, so no compiler may fold it away."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32) & _BF16_BITS
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def split_lut(lut_flat):
    """Exact three-way bf16 split of the float32 LUTs.

    ``lut_flat`` [rows, L] float32.  Returns [3, rows, L] bf16: the hi, mid
    and lo pieces.  Each piece takes the next 8 significand bits of what the
    earlier ones left, and float32 has 24, so hi + mid + lo == lut bit for
    bit for zero and every finite entry of magnitude 2^-103 or more (below
    that the pieces leave float32's normal range).  ``adc_tile`` contracts
    a tile's three pieces in one MXU pass.
    """
    hi = _bf16_head(lut_flat)
    rest = lut_flat - hi  # exact: the low 16 significand bits
    mid = _bf16_head(rest)
    lo = rest - mid  # exact, at most 8 significand bits
    return jnp.stack([p.astype(jnp.bfloat16) for p in (hi, mid, lo)])


def adc_tile(lut_split, codes_t, ncodes):
    """ADC scores [bm, cap] of one code block: one-hot MXU contraction.

    ``lut_split`` [3, bm, m·ncodes] bf16: one query tile's LUT pieces
    (``split_lut``); ``codes_t`` [m, cap] uint8.  Either may be a Pallas
    ref.  The block is scored ADC_CHUNK columns at a time: a bf16 one-hot
    [m·ncodes, chunk] (0 and 1 are exact) against all three pieces in one
    default-precision ``dot_general`` with float32 accumulation, then
    hi + (mid + lo).  Shared verbatim by the Pallas kernel and the jnp
    reference path (``core.knn.quantized_scan``) so the two scores are
    bit-identical under the interpreter: same split, same one-hot, same
    chunks, same contraction when the reference is tiled at tile_n =
    cell_cap.
    """
    _, bm, L = lut_split.shape
    lut = lut_split[...].reshape(LUT_PIECES * bm, L)
    m, cap = codes_t.shape
    parts = []
    for c in range(0, cap, ADC_CHUNK):
        w = min(ADC_CHUNK, cap - c)
        ct = codes_t[:, c:c + w].astype(jnp.int32)
        iot = jax.lax.broadcasted_iota(jnp.int32, (m, ncodes, w), 1)
        oh = (ct[:, None, :] == iot).astype(jnp.bfloat16)
        p = jax.lax.dot_general(
            lut,
            oh.reshape(m * ncodes, w),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        parts.append(p[:bm] + (p[bm:2 * bm] + p[2 * bm:]))
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


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

        t = adc_tile(lut_ref, codes_ref, ncodes)
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
    (``core.ivf.tile_probe_lists``); ``luts`` [3, m, mj·ncodes] bf16: the
    flattened per-query tables (``core.pq.build_pq_luts`` reshaped), split
    by ``split_lut``; ``codes_t``
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
    assert luts.dtype == jnp.bfloat16, luts.dtype
    _, m, L = luts.shape
    mj, S = codes_t.shape
    assert luts.shape[0] == LUT_PIECES and L == mj * ncodes, (
        luts.shape, mj, ncodes)
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
        pl.BlockSpec((LUT_PIECES, bm, L), lambda i, j, pr: (0, i, 0)),
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
