"""Public jit'd wrappers around the Pallas kernels.

Handles operand padding to block multiples, MXU-form pre-mapping (f/g/h), and
the interpret-mode switch: on the CPU container every kernel runs with
``interpret=True`` (the Pallas interpreter executes the kernel body exactly);
on a real TPU backend the same calls lower to Mosaic.  The kernel entry
points themselves (``fused_knn_pallas`` & co.) resolve ``interpret=None`` the
same backend-aware way, so direct callers are safe on real TPUs too.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import topk as T
from repro.core.distances import (
    QuantizedRows,
    get_distance,
    matmul_finalize,
)
from repro.kernels import fused_knn as _fused
from repro.kernels import ivf_scan as _ivf
from repro.kernels import pairwise_distance as _pd
from repro.kernels import pq_scan as _pq
from repro.kernels import rescore as _rs
from repro.kernels import stream_topk as _st
from repro.kernels._backend import resolve_interpret


_RESCORE_BLOCK_BYTES = 4 << 20


def _pad_axis(x, mult, axis, value=0.0):
    pad = (-x.shape[axis]) % mult
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _mxu_operands(x, y, distance: str):
    dist = get_distance(distance)
    mf = dist.matmul_form
    assert mf is not None, f"{distance} has no MXU form"
    fx = mf.fx(x).astype(jnp.float32)
    gy = mf.gy(y).astype(jnp.float32)
    hx = mf.hx(x).astype(jnp.float32)[:, None]
    hy = mf.hy(y).astype(jnp.float32)[None, :]
    return fx, gy, hx, hy, mf.alpha


@functools.partial(
    jax.jit, static_argnames=("distance", "bm", "bn", "bd", "cumulative", "interpret")
)
def pairwise_distance(
    x,
    y,
    *,
    distance: str = "sqeuclidean",
    bm: int = 256,
    bn: int = 256,
    bd: int = 128,
    cumulative: bool = False,
    interpret: bool | None = None,
):
    """[m, n] distance matrix via the Pallas tile kernel.

    Pads m/n with +inf rows (callers slice), d with zero coordinates (safe for
    every registry distance's f/g maps: they send 0 -> 0).
    """
    interpret = resolve_interpret(interpret)
    m, n = x.shape[0], y.shape[0]
    dist = get_distance(distance)
    if cumulative or dist.matmul_form is None:
        if dist.pre is not None:
            x = dist.pre(x)
            y = dist.pre(y)
        xp = _pad_axis(_pad_axis(x, bm, 0), bd, 1)
        yp = _pad_axis(_pad_axis(y, bn, 0), bd, 1)
        out = _pd.pairwise_distance_cumulative_pallas(
            xp,
            yp,
            accumulate=dist.accumulate,
            finalize=dist.finalize,
            init=dist.init,
            bm=bm,
            bn=bn,
            bd=bd,
            interpret=interpret,
        )
        return out[:m, :n]
    fx, gy, hx, hy, alpha = _mxu_operands(x, y, distance)
    fx = _pad_axis(_pad_axis(fx, bm, 0), bd, 1)
    gy = _pad_axis(_pad_axis(gy, bn, 0), bd, 1)
    hx = _pad_axis(hx, bm, 0)
    hy = _pad_axis(hy, bn, 1)
    out = _pd.pairwise_distance_pallas(
        fx,
        gy,
        hx,
        hy,
        alpha=alpha,
        finalize=matmul_finalize(dist),
        bm=bm,
        bn=bn,
        bd=bd,
        interpret=interpret,
    )
    return out[:m, :n]


@functools.partial(
    jax.jit, static_argnames=("k", "bm", "bn", "threshold_skip", "interpret")
)
def stream_topk(
    x,
    k: int,
    *,
    bm: int = 256,
    bn: int | None = None,
    threshold_skip: bool | None = None,
    interpret: bool | None = None,
):
    """Ascending k smallest per row of [m, n] + int32 indices, via Pallas."""
    interpret = resolve_interpret(interpret)
    m, n = x.shape
    K = T.next_pow2(k)
    if bn is None:
        bn = max(K, 512)
    bm = min(bm, T.next_pow2(m))
    xp = _pad_axis(_pad_axis(x, bm, 0, value=T.POS_INF), bn, 1, value=T.POS_INF)
    vals, idx = _st.stream_topk_pallas(
        xp, k, bm=bm, bn=bn, threshold_skip=threshold_skip, interpret=interpret
    )
    return vals[:m, :k], idx[:m, :k]


@functools.partial(
    jax.jit,
    static_argnames=("k", "distance", "tile_m", "tile_n", "bd", "exclude_self",
                     "threshold_skip", "interpret"),
)
def fused_knn(
    q,
    db,
    k: int,
    *,
    distance: str = "sqeuclidean",
    tile_m: int = 256,
    tile_n: int = 512,
    bd: int = 128,
    exclude_self: bool = False,
    db_valid=None,
    db_live=None,
    q_allowed=None,
    threshold_skip: bool | None = None,
    interpret: bool | None = None,
):
    """kNN of q against db with the fused Pallas kernel; returns KNNResult.

    ``db`` is either a raw fp32 [n, d] array or a ``QuantizedRows`` replica
    (bf16 / int8 + per-row scales, already ``gy``-mapped — see
    ``core.distances.quantize_rows``).  A quantized db makes the scan move
    2x/4x fewer HBM bytes; distances are then exact w.r.t. the DEQUANTIZED
    corpus, so callers over-fetch and rescore (DESIGN.md §Quantized).

    ``db_valid``: optional traced count of valid database rows — rows at index
    >= db_valid get +inf distance (via the rank-1 ``hy`` epilogue term), which
    lets SPMD callers mask ragged shards without a per-device static shape.
    ``db_live``: optional traced bool [n] mask — False rows get +inf the same
    way (the serving index's tombstones; arbitrary pattern, same epilogue).
    ``q_allowed``: optional traced bool [m, n] PER-QUERY filter bitmap
    (DESIGN.md §17) — False entries get +inf inside the kernel via a
    [bm, bn]-blocked fp32 mask operand (a per-query pattern cannot ride the
    rank-1 ``hy`` epilogue).  Composes with both masks above; an all-True
    bitmap is bit-identical to passing None.
    """
    from repro.core.knn import KNNResult

    interpret = resolve_interpret(interpret)
    quantized = isinstance(db, QuantizedRows)
    m = q.shape[0]
    n = db.data.shape[0] if quantized else db.shape[0]
    K = T.next_pow2(k)
    tile_n = max(tile_n, K)
    if quantized:
        dist = get_distance(distance)
        mf = dist.matmul_form
        assert mf is not None, f"{distance} has no MXU form"
        fx = mf.fx(q).astype(jnp.float32)
        hx = mf.hx(q).astype(jnp.float32)[:, None]
        gy = db.data  # keep the storage dtype: the kernel upcasts in VMEM
        hy = db.hy.astype(jnp.float32)[None, :]
        gs = None if db.scale is None else db.scale.astype(jnp.float32)[None, :]
    else:
        fx, gy, hx, hy, _ = _mxu_operands(q, db, distance)
        gs = None
    if db_valid is not None:
        hy = jnp.where(jnp.arange(n)[None, :] < db_valid, hy, T.POS_INF)
    if db_live is not None:
        hy = jnp.where(db_live[None, :], hy, T.POS_INF)
    fx = _pad_axis(_pad_axis(fx, tile_m, 0), bd, 1)
    gy = _pad_axis(_pad_axis(gy, tile_n, 0), bd, 1)
    hx = _pad_axis(hx, tile_m, 0)
    hy = _pad_axis(hy, tile_n, 1)
    if gs is not None:
        gs = _pad_axis(gs, tile_n, 1)
    qm = None
    if q_allowed is not None:
        # Pad value 0 (= masked) is safe: the column tail is already +inf via
        # n_real and the row tail is sliced off below.
        qm = _pad_axis(
            _pad_axis(q_allowed.astype(jnp.float32), tile_m, 0), tile_n, 1)
    vals, idx = _fused.fused_knn_pallas(
        fx,
        gy,
        hx,
        hy,
        k,
        gy_scale=gs,
        q_mask=qm,
        distance=distance,
        bm=tile_m,
        bn=tile_n,
        bd=bd,
        n_real=n,
        exclude_self=exclude_self,
        threshold_skip=threshold_skip,
        interpret=interpret,
    )
    return KNNResult(vals[:m, :k], idx[:m, :k])


def _scan_tile_cap(K: int) -> int:
    """Query rows per scan tile: the selection network unrolls into the
    kernel, and at a fetch wider than a lane tile (K > 128) a 256-row tile
    compiles in minutes where a 64-row tile compiles in seconds."""
    return 256 if K <= T.LANES else 64


def _drop_unprobed_tiles(vals, idx, cells, ncells, tile_m):
    """Empty out query tiles none of whose probes is a cell in [0, ncells).

    A shard of the sharded path can own none of a tile's probed cells; its
    probe list then still names one cell, which the scan must not report.
    """
    hit = jnp.logical_and(cells >= 0, cells < ncells)
    hit = jnp.any(hit.reshape(-1, tile_m * cells.shape[1]), axis=1)
    ok = jnp.repeat(hit, tile_m)[:, None]
    return jnp.where(ok, vals, T.POS_INF), jnp.where(ok, idx, -1)


def ivf_scan_impl(
    q,
    db,
    cells,
    k: int,
    *,
    cell_cap: int,
    distance: str = "sqeuclidean",
    tile_m: int = 256,
    bd: int = 128,
    packed_live=None,
    threshold_skip: bool | None = None,
    interpret: bool | None = None,
):
    """Cell-probed kNN scan of a cell-packed corpus; returns KNNResult.

    ``db`` is the cell-packed [S, d] fp32 array (``core.ivf.IVFCells.packed``)
    or its ``QuantizedRows`` replica (already gy-mapped); ``cells`` [m,
    nprobe] int32 is each query's probed-cell shortlist — the wrapper builds
    the per-query-tile union lists (``core.ivf.tile_probe_lists``) that the
    scalar-prefetch kernel's index map consumes, so only probed cell blocks
    are ever DMA'd (kernels/ivf_scan.py).

    ``packed_live``: optional traced bool [S] mask in PACKED slot order
    (pad slots + tombstones — ``core.ivf.packed_live``); dead slots get +inf
    via the rank-1 ``hy`` epilogue, same idiom as ``fused_knn``'s
    ``db_live``.  Indices are PACKED slots (map back via ``row_of_slot``).

    This impl is deliberately un-jitted for shard_map bodies: under the
    Pallas INTERPRETER, a scalar-prefetch ``pallas_call`` nested in
    jit(shard_map) with device-varying operands silently corrupts the
    grid's revisiting state (pinned-toolchain defect — flat ``fused_knn``
    under the same nesting is fine).  The sharded IVF path therefore only
    calls this on real TPU backends and falls back to the jnp probe-mask
    scan elsewhere (``core.distributed.ivf_query_sharded_shard``);
    ``ivf_scan`` below is the jitted entry for local callers, where the
    kernel is correct under the interpreter and tested.
    """
    from repro.core.ivf import tile_probe_lists
    from repro.core.knn import KNNResult

    interpret = resolve_interpret(interpret)
    quantized = isinstance(db, QuantizedRows)
    m = q.shape[0]
    S = db.data.shape[0] if quantized else db.shape[0]
    assert S % cell_cap == 0, (S, cell_cap)
    ncells = S // cell_cap
    K = T.next_pow2(k)
    assert K <= cell_cap, (
        f"fetch width K={K} exceeds the cell block ({cell_cap}); lower k or "
        "rebuild with a larger cell_cap")
    if quantized:
        dist = get_distance(distance)
        mf = dist.matmul_form
        assert mf is not None, f"{distance} has no MXU form"
        fx = mf.fx(q).astype(jnp.float32)
        hx = mf.hx(q).astype(jnp.float32)[:, None]
        gy = db.data  # keep the storage dtype: the kernel upcasts in VMEM
        hy = db.hy.astype(jnp.float32)[None, :]
        gs = None if db.scale is None else db.scale.astype(jnp.float32)[None, :]
    else:
        fx, gy, hx, hy, _ = _mxu_operands(q, db, distance)
        gs = None
    if packed_live is not None:
        hy = jnp.where(packed_live[None, :], hy, T.POS_INF)
    tile_m = min(tile_m, T.next_pow2(max(m, 8)), _scan_tile_cap(K))
    fx = _pad_axis(_pad_axis(fx, tile_m, 0), bd, 1)
    gy = _pad_axis(gy, bd, 1)
    hx = _pad_axis(hx, tile_m, 0)
    # Pad queries replicate the last row's probes: real cells, wider unions.
    pad = fx.shape[0] - m
    if pad:
        cells = jnp.concatenate([cells, jnp.broadcast_to(
            cells[-1:], (pad, cells.shape[1]))], axis=0)
    probes = tile_probe_lists(cells, ncells, tile_m)
    vals, idx = _ivf.ivf_scan_pallas(
        probes,
        fx,
        gy,
        hx,
        hy,
        k,
        cell_cap=cell_cap,
        gy_scale=gs,
        distance=distance,
        bm=tile_m,
        bd=bd,
        threshold_skip=threshold_skip,
        interpret=interpret,
    )
    vals, idx = _drop_unprobed_tiles(vals, idx, cells, ncells, tile_m)
    return KNNResult(vals[:m, :k], idx[:m, :k])


ivf_scan = functools.partial(
    jax.jit,
    static_argnames=("k", "distance", "cell_cap", "tile_m", "bd",
                     "threshold_skip", "interpret"),
)(ivf_scan_impl)


def pq_scan_impl(
    q,
    pq_cb,
    pq_codes,
    cells,
    k: int,
    *,
    cell_cap: int,
    centroids=None,
    distance: str = "sqeuclidean",
    tile_m: int = 256,
    packed_live=None,
    threshold_skip: bool | None = None,
    interpret: bool | None = None,
):
    """Cell-probed ADC scan of a PQ-coded corpus; returns KNNResult.

    ``pq_cb``/``pq_codes`` are the ``core.pq`` codebook + cell-packed code
    replica (codes in PACKED slot order); ``cells`` [m, nprobe] int32 is each
    query's probed-cell shortlist; ``centroids`` (the IVF coarse table) marks
    the codes as RESIDUAL and rides in as the per-(query, cell) cross-term
    bias (``core.pq.pq_cell_bias``) — None means plain (non-residual) codes.

    The wrapper builds the per-query LUTs (``build_pq_luts``) and the
    per-query-tile union probe lists, pads queries, and transposes the codes
    to the kernel's [m, S] streamed layout; ``packed_live`` masks dead slots
    to +inf via ``hy`` exactly like ``ivf_scan``.  Indices are PACKED slots.

    Un-jitted for shard_map bodies for the same pinned-toolchain reason as
    ``ivf_scan_impl`` (scalar-prefetch kernels corrupt under the interpreter
    inside jit(shard_map) with device-varying operands); ``pq_scan`` below is
    the jitted local entry.
    """
    from repro.core.ivf import tile_probe_lists
    from repro.core.knn import KNNResult
    from repro.core.pq import build_pq_luts, pq_cell_bias

    interpret = resolve_interpret(interpret)
    dist = get_distance(distance)
    mf = dist.matmul_form
    assert mf is not None, f"{distance} has no MXU form"
    m = q.shape[0]
    S = pq_codes.codes.shape[0]
    assert S % cell_cap == 0, (S, cell_cap)
    ncells = S // cell_cap
    K = T.next_pow2(k)
    assert K <= cell_cap, (
        f"fetch width K={K} exceeds the cell block ({cell_cap}); lower k or "
        "rebuild with a larger cell_cap")
    # Stage scopes (metadata only) name the device ops in a profile.
    with jax.named_scope("knn.lut"):
        luts = build_pq_luts(pq_cb, q, distance=distance)
        lut_flat = luts.reshape(m, pq_cb.m * pq_cb.ncodes)
        hx = mf.hx(q).astype(jnp.float32)[:, None]
    hy = pq_codes.hy.astype(jnp.float32)[None, :]
    if packed_live is not None:
        with jax.named_scope("knn.live_mask"):
            hy = jnp.where(packed_live[None, :], hy, T.POS_INF)
    with jax.named_scope("knn.lut"):
        qc = (None if centroids is None
              else pq_cell_bias(q, centroids, distance=distance))
    tile_m = min(tile_m, T.next_pow2(max(m, 8)), _scan_tile_cap(K))
    with jax.named_scope("knn.lut"):
        lut_flat = _pad_axis(lut_flat, tile_m, 0)
        lut_split = _pq.split_lut(lut_flat)
        hx = _pad_axis(hx, tile_m, 0)
        if qc is not None:
            qc = _pad_axis(qc, tile_m, 0)
    with jax.named_scope("knn.probe_union"):
        # Pad queries replicate the last row's probes: real cells, wider
        # unions.
        pad = lut_flat.shape[0] - m
        if pad:
            cells = jnp.concatenate([cells, jnp.broadcast_to(
                cells[-1:], (pad, cells.shape[1]))], axis=0)
        probes = tile_probe_lists(cells, ncells, tile_m)
    with jax.named_scope("knn.scan"):
        vals, idx = _pq.pq_scan_pallas(
            probes,
            lut_split,
            pq_codes.codes.T,
            hx,
            hy,
            k,
            cells=cells,
            cell_cap=cell_cap,
            ncodes=pq_cb.ncodes,
            qc=qc,
            distance=distance,
            bm=tile_m,
            threshold_skip=threshold_skip,
            interpret=interpret,
        )
        vals, idx = _drop_unprobed_tiles(vals, idx, cells, ncells, tile_m)
        return KNNResult(vals[:m, :k], idx[:m, :k])


pq_scan = functools.partial(
    jax.jit,
    static_argnames=("k", "distance", "cell_cap", "tile_m",
                     "threshold_skip", "interpret"),
)(pq_scan_impl)


@functools.partial(
    jax.jit,
    static_argnames=("k", "distance", "bm", "bd", "interpret"),
)
def rescore_topk(
    q,
    db,
    cand_idx,
    k: int,
    *,
    distance: str = "sqeuclidean",
    bm: int = 128,
    bd: int = 128,
    interpret: bool | None = None,
):
    """Exact re-rank of per-query candidate rows; returns KNNResult [m, k].

    ``cand_idx`` [m, Kp] int32 database rows from the quantized scan (-1 =
    empty slot).  The gather ``db[cand_idx]`` runs in XLA; the Pallas kernel
    fuses exact distance + selection over the gathered [m, Kp, d] block
    (see kernels/rescore.py).  Candidate slots must be distinct per row
    (scan output is); -1 slots come back as +inf / -1.
    """
    from repro.core.knn import KNNResult

    interpret = resolve_interpret(interpret)
    m, d = q.shape
    n = db.shape[0]
    Kp = cand_idx.shape[1]
    K = T.next_pow2(k)
    dist = get_distance(distance)
    mf = dist.matmul_form
    assert mf is not None, f"{distance} has no MXU form"

    # XLA-side gather of the fp32 corpus rows, then gy-map them rowwise.
    safe = jnp.clip(cand_idx, 0, n - 1)
    rows = jnp.take(db, safe.reshape(-1), axis=0)  # [m * Kp, d]
    cand = mf.gy(rows).astype(jnp.float32).reshape(m, Kp, d)
    hy_c = mf.hy(rows).astype(jnp.float32).reshape(m, Kp)
    hy_c = jnp.where(cand_idx >= 0, hy_c, T.POS_INF)
    fx = mf.fx(q).astype(jnp.float32)
    hx = mf.hx(q).astype(jnp.float32)[:, None]

    # Pad: rows of queries, the d axis, and the candidate axis (to K * 2^t).
    Kp_pad = K * T.next_pow2(-(-max(Kp, K) // K))
    # A [bm, Kp, bd] fp32 candidate block stays within 4 MiB of VMEM (it is
    # double-buffered): wide over-fetches take fewer query rows per block.
    bm = min(bm, T.next_pow2(max(m, 8)),
             max(8, _RESCORE_BLOCK_BYTES // (Kp_pad * bd * 4)))
    fx = _pad_axis(_pad_axis(fx, bm, 0), bd, 1)
    hx = _pad_axis(hx, bm, 0)
    cand = _pad_axis(_pad_axis(_pad_axis(cand, bm, 0), Kp_pad, 1), bd, 2)
    hy_c = _pad_axis(_pad_axis(hy_c, bm, 0), Kp_pad, 1, value=T.POS_INF)
    cip = _pad_axis(_pad_axis(cand_idx, bm, 0, value=-1), Kp_pad, 1, value=-1)

    vals, pos = _rs.rescore_topk_pallas(
        fx, cand, hx, hy_c, k, distance=distance, bm=bm, bd=bd,
        interpret=interpret)
    idx = jnp.take_along_axis(cip, pos, axis=1)
    idx = jnp.where(jnp.isfinite(vals), idx, -1)
    return KNNResult(vals[:m, :k], idx[:m, :k])
