"""Multi-device k-nearest-vector solvers (paper Sect. 4, TPU adaptation).

The paper's multi-GPU design has three load-bearing ideas:

  1. symmetric delta => compute only the upper triangle, each tile feeding
     both its row-heaps and (transposed) its column-heaps;
  2. zigzag assignment of grid rows to devices for static load balance;
  3. per-device private heaps — no inter-device synchronization until one
     final merge (done on the CPU in the paper).

TPU mapping (see DESIGN.md "hardware adaptation"):

* ``knn_allpairs_ring`` — the production path.  Points are row-sharded; a
  half-ring of ``collective_permute`` steps rotates visiting blocks so each
  unordered pair of blocks meets exactly once (idea 1).  Every device computes
  the same number of tiles per step, so balance is *exact* rather than
  zigzag-approximate (idea 2 becomes unnecessary — the triangle is never
  materialized).  Partial results for the visiting block travel with it in a
  "boomerang heap" and are routed home with one static permute (idea 3: still
  no global synchronization, and the final CPU merge becomes an O(1)-depth
  on-device merge).
* ``knn_allpairs_triangle`` — the paper-faithful layout: dataset replicated
  (one all-gather), the exact zigzag schedule from repro.core.grid, per-device
  full-length heaps, and a log2(P)-depth bitonic tree merge instead of the
  paper's CPU merge (beyond-paper: the merge is O(n k log P / P) on-device
  instead of O(n k P) on host).
* ``knn_query_sharded`` — serving path: queries sharded on one mesh axis,
  database on another; local fused kNN then a butterfly top-k merge across the
  database axis.  This is the retrieval engine used by the two-tower config's
  ``retrieval_cand`` shape.

All functions are written against ``jax.shard_map`` with explicit axis names
and are mesh-shape agnostic (any power-of-two axis size).
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import topk as T
from repro.core.distances import QuantizedRows, get_distance, is_symmetric, quantize_rows
from repro.core.knn import (
    KNNResult,
    pairwise_tile,
    quantized_scan,
    rescore,
    scan_width,
)

Array = jnp.ndarray


# ---------------------------------------------------------------------------
# Collective top-k merge primitives.
# ---------------------------------------------------------------------------


def _varying(x, axis_name):
    """Mark a device-invariant value as varying over ``axis_name`` (vma)."""
    names = (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)
    return jax.lax.pcast(x, names, to="varying")


def tree_merge_topk(run_v: Array, run_i: Array, axis_name,
                    *, wire_dtype=None) -> tuple[Array, Array]:
    """All-reduce-style top-k merge: XOR-butterfly of bitonic merges.

    After log2(P) rounds every device holds the K smallest of the union of all
    devices' sorted K-buffers.  Communication: log2(P) x [rows, K] pairs —
    exponentially less than the paper's gather-everything-to-CPU merge.

    ``wire_dtype`` (e.g. bf16): ship each round's value payload compressed,
    via the same stored-dtype + integer-bitcast trick as the ring's boomerang
    heap (``_permute_bits``) — the local buffer is STORED in the wire dtype
    between rounds so every device compares identically-rounded values and
    the merged (values, indices) stay consistent across the axis.  Merges
    still compute in fp32; indices stay int32 (exact).  Reported distances
    then carry one bf16 rounding — callers reserve this for the quantized
    scan path, where the benchmark measures end-to-end recall anyway
    (DESIGN.md §Quantized).
    """
    P = jax.lax.axis_size(axis_name)
    assert P & (P - 1) == 0, f"butterfly merge needs pow2 axis, got {P}"
    wd = wire_dtype
    if wd is not None:
        run_v = run_v.astype(wd)
    d = 1
    while d < P:
        perm = [(i, i ^ d) for i in range(P)]
        if wd is None:
            ov = jax.lax.ppermute(run_v, axis_name, perm)
        else:
            ov = _permute_bits(run_v, axis_name, perm)
        oi = jax.lax.ppermute(run_i, axis_name, perm)
        mv, mi = T.merge_topk_sorted(
            run_v.astype(jnp.float32), run_i, ov.astype(jnp.float32), oi)
        run_v = mv if wd is None else mv.astype(wd)
        run_i = mi
        d *= 2
    return run_v.astype(jnp.float32), run_i


def _rotate(x, axis_name, shift: int):
    """Static-ring permute: device p sends to (p + shift) mod P."""
    P = jax.lax.axis_size(axis_name)
    perm = [(i, (i + shift) % P) for i in range(P)]
    return jax.lax.ppermute(x, axis_name, perm)


def _permute_bits(x, axis_name, perm):
    """ppermute with the payload laundered through an integer bitcast.

    XLA's algebraic simplifier commutes fp converts across collectives and
    re-widens a bf16 payload back to f32 on the wire (measured — §Perf).  A
    bitcast to u16 is opaque to that rewrite, so the permute genuinely
    carries 2 bytes/element.  Shared by the ring's boomerang heap and the
    butterfly merge's compressed wire.
    """
    assert jnp.dtype(x.dtype).itemsize == 2, x.dtype
    bits = jax.lax.bitcast_convert_type(x, jnp.uint16)
    out = jax.lax.ppermute(bits, axis_name, perm)
    return jax.lax.bitcast_convert_type(out, x.dtype)


def _rotate_bits(x, axis_name, shift: int):
    """Ring permute of a 16-bit payload (see ``_permute_bits``)."""
    P = jax.lax.axis_size(axis_name)
    perm = [(i, (i + shift) % P) for i in range(P)]
    return _permute_bits(x, axis_name, perm)


# ---------------------------------------------------------------------------
# Ring all-pairs (production path).
# ---------------------------------------------------------------------------


def _local_tile(x_rows, x_cols, dist, impl: str):
    if impl == "pallas":
        from repro.kernels import ops as kops

        return kops.pairwise_distance(x_rows, x_cols, distance=dist.name)
    return pairwise_tile(x_rows, x_cols, dist)


def ring_allpairs_shard(
    x_local: Array,
    *,
    axis_name,
    k: int,
    distance: str = "sqeuclidean",
    n_real: int,
    impl: str = "jnp",
    threshold_skip: bool | None = None,
    wire_dtype=None,
) -> tuple[Array, Array]:
    """Per-shard body of the half-ring symmetric all-pairs kNN.

    ``x_local``: this device's row block [n_loc, d] (zero-padded rows beyond
    ``n_real`` globally).  Returns this block's ascending (values, indices)
    [n_loc, K].  Runs inside shard_map.

    ``impl="fused"`` never builds the [n_loc, n_loc] tile (6.4 GB at the
    paper's n = 160k over 4 devices): each block pair runs the fused kernel
    twice, rows against the visiting block and the visiting block against
    the rows, and merges the two top-K sets.
    """
    threshold_skip = T.resolve_threshold_skip(threshold_skip, pallas=False)
    dist = get_distance(distance)
    sym = is_symmetric(distance)
    P = jax.lax.axis_size(axis_name)
    p = jax.lax.axis_index(axis_name)
    n_loc, _ = x_local.shape
    K = T.next_pow2(k)

    def masked(tile, row_block, col_block, exclude_diag):
        row_ids = row_block * n_loc + jnp.arange(n_loc)[:, None]
        col_ids = col_block * n_loc + jnp.arange(n_loc)[None, :]
        tile = jnp.where(col_ids >= n_real, T.POS_INF, tile)
        tile = jnp.where(row_ids >= n_real, T.POS_INF, tile)
        if exclude_diag:
            tile = jnp.where(row_ids == col_ids, T.POS_INF, tile)
        return tile

    def fused_block(rows, cols, col_block, exclude_diag):
        # K smallest of rows vs one column block; columns past n_real are
        # dead (rows past it are padding, sliced off by the caller).
        from repro.kernels import ops as kops

        res = kops.fused_knn(rows, cols, k, distance=distance,
                             exclude_self=exclude_diag,
                             db_valid=n_real - col_block * n_loc,
                             threshold_skip=threshold_skip)
        v, i = T.pad_topk(res.distances, res.indices, K)
        return v, jnp.where(jnp.isfinite(v), i + col_block * n_loc, -1)

    # Diagonal tile: own vs own, self-excluded. No communication.
    if impl == "fused":
        run_v, run_i = fused_block(x_local, x_local, p, True)
    else:
        run_v, run_i = T.init_running(n_loc, k)
        tile = _local_tile(x_local, x_local, dist, impl)
        tile = masked(tile, p, p, True)
        run_v, run_i = T.update_running(
            run_v, run_i, tile, p * n_loc, threshold_skip=threshold_skip
        )

    if P == 1:
        return run_v, run_i

    n_steps = P // 2 if sym else P - 1

    # Boomerang state: the visiting block plus the heap being accumulated FOR
    # that block by the devices it visits (symmetric mirror updates).
    # ``wire_dtype`` (e.g. bf16): the traveling state is STORED in the wire
    # dtype, so every hop's ppermute carries the compressed payload natively.
    # (Casting right at the permute does NOT work: XLA's simplifier fuses the
    # down/up converts and ships fp32 — §Perf refuted-then-fixed iteration.
    # Merges/distances still compute in fp32; indices stay int32.)
    wd = wire_dtype
    vis_block = x_local if wd is None else x_local.astype(wd)
    vis_v, vis_i = T.init_running(n_loc, k)
    if wd is not None:
        vis_v = vis_v.astype(wd)
    vis_v = _varying(vis_v, axis_name)
    vis_i = _varying(vis_i, axis_name)

    rot = _rotate if wd is None else _rotate_bits

    def step(s, carry):
        run_v, run_i, vis_block, vis_v, vis_i = carry
        # Rotate visiting state forward one hop: after s hops device p hosts
        # block (p - s) mod P and that block's traveling heap.
        vis_block = rot(vis_block, axis_name, 1)
        vis_v = rot(vis_v, axis_name, 1)
        vis_i = _rotate(vis_i, axis_name, 1)
        src = jax.lax.rem(p - s + P, P)  # owner of the visiting block
        # Even-P final half-step: each unordered pair {p, p+P/2} would be seen
        # twice; only the lower device keeps it (the paper's "virtual mirror").
        active = True
        if sym and P % 2 == 0:
            last = s == n_steps
            active = jnp.logical_or(jnp.logical_not(last), p < P // 2)

        cols = vis_block.astype(x_local.dtype)
        if impl == "fused":
            tv, ti = fused_block(x_local, cols, src, False)
            tv, ti = jnp.where(active, tv, T.POS_INF), jnp.where(active, ti, -1)
            run_v, run_i = T.merge_topk_sorted(run_v, run_i, tv, ti)
            if sym:
                tv, ti = fused_block(cols, x_local, p, False)
                tv = jnp.where(active, tv, T.POS_INF)
                ti = jnp.where(active, ti, -1)
        else:
            tile = _local_tile(x_local, cols, dist, impl)
            tile = jnp.where(active, masked(tile, p, src, False), T.POS_INF)
            run_v, run_i = T.update_running(
                run_v, run_i, tile, src * n_loc, threshold_skip=threshold_skip
            )
            if sym:
                tv, ti = T.tile_topk(tile.T, K, p * n_loc)
        if sym:
            mv, mi = T.merge_topk_sorted(vis_v.astype(jnp.float32), vis_i, tv, ti)
            vis_v = mv if wd is None else mv.astype(wd)
            vis_i = mi
        return run_v, run_i, vis_block, vis_v, vis_i

    from repro import accounting

    if accounting.unrolled():
        # Trip-count-true accounting: unroll the ring so every hop's
        # collective-permute is visible to cost analysis (dry-run only).
        carry = (run_v, run_i, vis_block, vis_v, vis_i)
        for s in range(1, n_steps + 1):
            carry = step(s, carry)
        run_v, run_i, vis_block, vis_v, vis_i = carry
    else:
        run_v, run_i, vis_block, vis_v, vis_i = jax.lax.fori_loop(
            1, n_steps + 1, step, (run_v, run_i, vis_block, vis_v, vis_i)
        )

    if sym:
        # Route each traveling heap home: block q's heap sits at (q + S) mod P.
        vis_v = _rotate(vis_v, axis_name, -n_steps)
        vis_i = _rotate(vis_i, axis_name, -n_steps)
        run_v, run_i = T.merge_topk_sorted(
            run_v, run_i, vis_v.astype(jnp.float32), vis_i)
    return run_v, run_i


# ---------------------------------------------------------------------------
# Paper-faithful triangle with zigzag schedule.
# ---------------------------------------------------------------------------


def triangle_allpairs_shard(
    x_local: Array,
    tiles: Array,
    valid: Array,
    *,
    axis_name,
    k: int,
    distance: str = "sqeuclidean",
    gsize: int,
    n_real: int,
    impl: str = "jnp",
    threshold_skip: bool | None = None,
) -> tuple[Array, Array]:
    """Paper Fig. 5: zigzag-assigned upper-triangle grids, per-device heaps.

    ``tiles``/``valid``: this device's padded static schedule row
    ([max_tiles, 2] int32 / [max_tiles] bool) from grid.make_schedule.
    Returns per-device PARTIAL heaps for ALL rows [n_pad, K]; callers merge
    across devices (tree_merge_topk) exactly as the paper merges per-GPU heaps.
    """
    threshold_skip = T.resolve_threshold_skip(threshold_skip, pallas=False)
    dist = get_distance(distance)
    # One all-gather: the paper ships the whole dataset to every GPU up front.
    x = jax.lax.all_gather(x_local, axis_name, tiled=True)
    n_pad, d = x.shape
    K = T.next_pow2(k)
    run_v = _varying(jnp.full((n_pad, K), T.POS_INF, jnp.float32), axis_name)
    run_i = _varying(jnp.full((n_pad, K), -1, jnp.int32), axis_name)

    def masked(tile, row_off, col_off):
        row_ids = row_off + jnp.arange(gsize)[:, None]
        col_ids = col_off + jnp.arange(gsize)[None, :]
        tile = jnp.where(col_ids >= n_real, T.POS_INF, tile)
        tile = jnp.where(row_ids == col_ids, T.POS_INF, tile)
        return tile

    def step(carry, txy):
        run_v, run_i = carry
        XY, ok = txy
        X, Y = XY[0], XY[1]
        row_off, col_off = Y * gsize, X * gsize
        rows = jax.lax.dynamic_slice(x, (row_off, 0), (gsize, d))
        cols = jax.lax.dynamic_slice(x, (col_off, 0), (gsize, d))
        tile = _local_tile(rows, cols, dist, impl)
        tile = jnp.where(ok, tile, T.POS_INF)

        t_row = masked(tile, row_off, col_off)
        rv = jax.lax.dynamic_slice(run_v, (row_off, 0), (gsize, K))
        ri = jax.lax.dynamic_slice(run_i, (row_off, 0), (gsize, K))
        rv, ri = T.update_running(rv, ri, t_row, col_off, threshold_skip=threshold_skip)
        run_v = jax.lax.dynamic_update_slice(run_v, rv, (row_off, 0))
        run_i = jax.lax.dynamic_update_slice(run_i, ri, (row_off, 0))

        t_col = masked(tile.T, col_off, row_off)
        t_col = jnp.where(X == Y, T.POS_INF, t_col)
        cv = jax.lax.dynamic_slice(run_v, (col_off, 0), (gsize, K))
        ci = jax.lax.dynamic_slice(run_i, (col_off, 0), (gsize, K))
        cv, ci = T.update_running(cv, ci, t_col, row_off, threshold_skip=threshold_skip)
        run_v = jax.lax.dynamic_update_slice(run_v, cv, (col_off, 0))
        run_i = jax.lax.dynamic_update_slice(run_i, ci, (col_off, 0))
        return (run_v, run_i), None

    (run_v, run_i), _ = jax.lax.scan(step, (run_v, run_i), (tiles, valid))
    return run_v, run_i


# ---------------------------------------------------------------------------
# Query-sharded kNN (serving / retrieval path).
# ---------------------------------------------------------------------------


def query_sharded_shard(
    q_local: Array,
    db_local: Array,
    db_live_local: Array | None = None,
    db_q_local: QuantizedRows | None = None,
    *,
    db_axis,
    k: int,
    distance: str = "sqeuclidean",
    n_db_real: int,
    impl: str = "fused",
    scan_dtype: str = "float32",
    overfetch: int = 4,
    wire_dtype=None,
    threshold_skip: bool | None = None,
) -> tuple[Array, Array]:
    """Queries sharded on one axis, database on ``db_axis``; butterfly merge.

    Each device solves its query block against its database shard, then the
    per-shard K-buffers are tree-merged across ``db_axis``.  Index space is
    global database rows.

    ``db_live_local``: optional bool [n_loc] mask of this shard (serving
    tombstones) — dead rows score +inf BEFORE the butterfly merge, so the
    merge wire payload stays K per row instead of an over-fetch width.

    ``scan_dtype`` != "float32" runs the two-stage pipeline PER SHARD
    (DESIGN.md §Quantized): scan the bf16/int8 replica for K' = scan_width
    candidates, rescore them exactly against the local fp32 shard, and only
    then merge — the butterfly payload stays K exact values per row, never
    the over-fetch width.  ``db_q_local`` supplies a prebuilt replica shard
    (the serving index caches one per main-segment epoch); when None the
    shard quantizes on the fly.  ``wire_dtype`` (bf16) additionally
    compresses the merge wire (``tree_merge_topk``).
    """
    P = jax.lax.axis_size(db_axis)
    p = jax.lax.axis_index(db_axis)
    n_loc = db_local.shape[0]
    K = T.next_pow2(k)
    scan_q = scan_dtype != "float32"

    m = q_local.shape[0]
    bm = min(256, T.next_pow2(max(m, 8)))
    local_valid = jnp.clip(n_db_real - p * n_loc, 0, n_loc)

    if scan_q:
        # Stage 1: compressed scan of this shard's replica for K' candidates.
        if db_q_local is None:
            db_q_local = quantize_rows(db_local, scan_dtype, distance=distance)
        from repro.kernels import ops as kops

        k_scan = scan_width(n_loc, min(k, n_loc), overfetch)
        if impl == "fused":
            cand = kops.fused_knn(
                q_local, db_q_local, k_scan, distance=distance, tile_m=bm,
                db_valid=local_valid, db_live=db_live_local,
                threshold_skip=threshold_skip).indices
        else:
            # Tiled jnp reference: scores the stored rows directly (scale in
            # the epilogue) — never a dequantized [n_loc, d] fp32 copy.
            live = jnp.arange(n_loc) < local_valid
            if db_live_local is not None:
                live = jnp.logical_and(live, db_live_local)
            cand = quantized_scan(
                q_local, db_q_local, k_scan, distance=distance,
                db_live=live, threshold_skip=threshold_skip).indices
        # Stage 2: exact fp32 rescore, still shard-local.
        vals, idx = rescore(q_local, db_local, cand, min(k, n_loc),
                            distance=distance,
                            impl=impl if impl == "fused" else "jnp")
        if vals.shape[1] < K:
            vals, idx = T.pad_topk(vals, idx, K)
    elif impl == "fused":
        from repro.kernels import ops as kops

        vals, idx = kops.fused_knn(
            q_local,
            db_local,
            min(k, n_loc),
            distance=distance,
            tile_m=bm,
            db_valid=local_valid,
            db_live=db_live_local,
            threshold_skip=threshold_skip,
        )
        vals = jnp.pad(vals, ((0, 0), (0, K - vals.shape[1])), constant_values=T.POS_INF)
        idx = jnp.pad(idx, ((0, 0), (0, K - idx.shape[1])), constant_values=-1)
    else:
        dist = get_distance(distance)
        tile = pairwise_tile(q_local, db_local, dist)
        col_ids = p * n_loc + jnp.arange(n_loc)[None, :]
        tile = jnp.where(col_ids >= n_db_real, T.POS_INF, tile)
        if db_live_local is not None:
            tile = jnp.where(db_live_local[None, :], tile, T.POS_INF)
        vals, idx0 = T.tile_topk(tile, K, 0)
        idx = idx0

    # local -> global database indices
    idx = jnp.where(idx >= 0, idx + p * n_loc, -1)
    vals, idx = tree_merge_topk(vals, idx, db_axis, wire_dtype=wire_dtype)
    return vals[:, :k], idx[:, :k]


def ivf_query_sharded_shard(
    q_local: Array,
    centroids: Array,
    packed_local: Array,
    row_of_slot_local: Array,
    live_packed_local: Array | None = None,
    packed_q_local: QuantizedRows | None = None,
    *,
    db_axis,
    k: int,
    nprobe: int,
    cell_cap: int,
    distance: str = "sqeuclidean",
    impl: str = "fused",
    scan_dtype: str = "float32",
    overfetch: int = 4,
    wire_dtype=None,
    threshold_skip: bool | None = None,
) -> tuple[Array, Array]:
    """IVF serving path: centroids replicated, cell blocks row-sharded.

    ``ncells % P == 0`` cells shard contiguously over ``db_axis`` (shard p
    owns global cells [p·ncells/P, (p+1)·ncells/P) — the cell-packed layout
    makes a shard boundary a cell boundary for free).  Each shard runs the
    FULL pipeline locally before the butterfly merge (DESIGN.md §IVF):

      1. the GLOBAL centroid shortlist (every shard computes the same
         [m, nprobe] — centroids are replicated, the shortlist is tiny);
      2. probes falling in this shard's cell range scan the local replica
         slice (scalar-prefetch kernel or the jnp probe mask); a shard none
         of whose cells were probed contributes only +inf slots;
      3. exact local rescore against the fp32 packed slice, candidates
         externalized through the local ``row_of_slot`` slice.

    The butterfly payload stays K exact (value, GLOBAL corpus row) pairs per
    query row — never the over-fetch width, and ``wire_dtype=bf16`` reuses
    the quantized path's compressed wire (``tree_merge_topk``).
    """
    from repro.core import ivf as IVF

    P = jax.lax.axis_size(db_axis)
    p = jax.lax.axis_index(db_axis)
    S_loc = packed_local.shape[0]
    assert S_loc % cell_cap == 0, (S_loc, cell_cap)
    ncells_loc = S_loc // cell_cap
    ncells = ncells_loc * P
    K = T.next_pow2(k)
    k_loc = min(k, S_loc)

    # 1. Global shortlist, then this shard's slice of the probe set.  Ids
    # outside [0, ncells_loc) simply match no local cell below.
    cells = IVF.probe_cells(q_local, centroids, min(nprobe, ncells),
                            distance=distance, impl=impl)
    local_cells = cells - p * ncells_loc

    live = row_of_slot_local >= 0  # pad slots are dead by construction
    if live_packed_local is not None:
        live = jnp.logical_and(live, live_packed_local)

    k_scan = scan_width(S_loc, k_loc, overfetch)
    from repro.kernels._backend import resolve_interpret

    # The scalar-prefetch kernel inside jit(shard_map) silently corrupts
    # results under the Pallas INTERPRETER whenever its operands are
    # device-varying (measured on the pinned toolchain: probed slots vanish
    # from the merge; the flat fused_knn kernel under the same nesting is
    # fine, so the defect is PrefetchScalarGridSpec-specific).  Off-TPU the
    # sharded stage 1 therefore runs the jnp probe-mask reference — same
    # candidates, predicated compute instead of pruned DMA; the kernel
    # engages where it lowers through Mosaic (real TPU backends).  The
    # LOCAL fused path (core.knn.ivf_query) uses the kernel everywhere.
    if impl == "fused" and not resolve_interpret(None):
        from repro.kernels import ops as kops

        scan_db = packed_q_local
        if scan_db is None:
            scan_db = (packed_local if scan_dtype == "float32" else
                       quantize_rows(packed_local, scan_dtype,
                                     distance=distance))
        m = q_local.shape[0]
        bm = min(256, T.next_pow2(max(m, 8)))
        cand = kops.ivf_scan_impl(
            q_local, scan_db, local_cells, min(k_scan, cell_cap),
            cell_cap=cell_cap, distance=distance, tile_m=bm,
            packed_live=live, threshold_skip=threshold_skip).indices
    else:
        scan_q = packed_q_local
        if scan_q is None:
            scan_q = quantize_rows(packed_local, scan_dtype,
                                   distance=distance)
        probed = jnp.any(
            local_cells[:, :, None] == jnp.arange(ncells_loc)[None, None, :],
            axis=1)
        cand = quantized_scan(
            q_local, scan_q, k_scan, distance=distance, db_live=live,
            probed=probed, cell_cap=cell_cap,
            threshold_skip=threshold_skip).indices

    # 3. Exact local rescore, then packed slot -> GLOBAL corpus row.
    vals, idx = rescore(q_local, packed_local, cand, k_loc,
                        distance=distance,
                        impl=impl if impl == "fused" else "jnp")
    safe = jnp.clip(idx, 0, S_loc - 1)
    idx = jnp.where(idx >= 0, jnp.take(row_of_slot_local, safe), -1)
    if vals.shape[1] < K:
        vals, idx = T.pad_topk(vals, idx, K)
    vals, idx = tree_merge_topk(vals, idx, db_axis, wire_dtype=wire_dtype)
    return vals[:, :k], idx[:, :k]


def ivfpq_query_sharded_shard(
    q_local: Array,
    centroids: Array,
    pq_cb,
    pq_codes_local,
    packed_local: Array,
    row_of_slot_local: Array,
    live_packed_local: Array | None = None,
    *,
    db_axis,
    k: int,
    wire_dtype=None,
    **kw,
) -> tuple[Array, Array]:
    """IVF-PQ serving path: codebooks replicated, code blocks row-sharded.

    The same shard contract as ``ivf_query_sharded_shard`` (DESIGN.md §PQ):
    ``ncells % P == 0`` cells shard contiguously over ``db_axis``, and each
    shard runs the full pipeline locally (``ivfpq_shard_candidates``) before
    the butterfly merge.  The butterfly payload stays K exact (value, GLOBAL
    corpus row) pairs per query row, optionally on the bf16 wire — the
    n-scaling arrays a shard touches per query are m-byte code rows, which
    is what makes million-row mains servable from HBM (ROADMAP north star).
    """
    vals, idx = ivfpq_shard_candidates(
        q_local, centroids, pq_cb, pq_codes_local, packed_local,
        row_of_slot_local, live_packed_local,
        shard=jax.lax.axis_index(db_axis),
        n_shards=jax.lax.axis_size(db_axis), k=k, **kw)
    vals, idx = tree_merge_topk(vals, idx, db_axis, wire_dtype=wire_dtype)
    return vals[:, :k], idx[:, :k]


def ivfpq_shard_candidates(
    q_local: Array,
    centroids: Array,
    pq_cb,
    pq_codes_local,
    packed_local: Array,
    row_of_slot_local: Array,
    live_packed_local: Array | None = None,
    *,
    shard,
    n_shards: int,
    k: int,
    nprobe: int,
    cell_cap: int,
    distance: str = "sqeuclidean",
    impl: str = "fused",
    overfetch: int = 4,
    threshold_skip: bool | None = None,
    residual: bool = True,
) -> tuple[Array, Array]:
    """One IVF-PQ shard's exact top-K candidates [m, K] (GLOBAL corpus rows).

    Shard ``shard`` of ``n_shards`` owns the contiguous cells
    [shard·ncells/P, (shard+1)·ncells/P):

      1. the GLOBAL centroid shortlist (centroids and the PQ codebook are
         replicated: the shortlist is tiny, the codebook is m·2^nbits·d/m·4
         = 2^nbits·d·4 bytes — 128 KiB at d=128 — and every shard builds
         the same per-query LUTs from it);
      2. probes falling in this shard's cell range ADC-scan the LOCAL code
         slice (``pq_codes_local``: the [S/P, m] uint8 rows + hy of this
         shard's cells; the residual cross term biases against this shard's
         centroid slice);
      3. exact local rescore against the fp32 packed slice, candidates
         externalized through the local ``row_of_slot`` slice.

    Free of collectives, so one device can run every shard in turn and
    merge, which is the single-chip twin of the sharded path.
    """
    from repro.core import ivf as IVF
    from repro.core.knn import quantized_scan as q_scan
    from repro.core.pq import pq_cell_bias
    from repro.kernels._backend import resolve_interpret

    p = shard
    S_loc = packed_local.shape[0]
    assert S_loc % cell_cap == 0, (S_loc, cell_cap)
    ncells_loc = S_loc // cell_cap
    ncells = ncells_loc * n_shards
    d = q_local.shape[1]
    K = T.next_pow2(k)
    k_loc = min(k, S_loc)

    # 1. Global shortlist, then this shard's slice of the probe set.
    cells = IVF.probe_cells(q_local, centroids, min(nprobe, ncells),
                            distance=distance, impl=impl)
    local_cells = cells - p * ncells_loc
    # Residual cross term against THIS shard's centroid rows only — the
    # local cell ids index the slice directly.
    cent_local = jax.lax.dynamic_slice(
        centroids, (p * ncells_loc, 0), (ncells_loc, d))
    cbias = (pq_cell_bias(q_local, cent_local, distance=distance)
             if residual else None)

    live = row_of_slot_local >= 0  # pad slots are dead by construction
    if live_packed_local is not None:
        live = jnp.logical_and(live, live_packed_local)

    k_scan = scan_width(S_loc, k_loc, overfetch)
    # Same pinned-toolchain guard as the IVF shard: a scalar-prefetch kernel
    # inside jit(shard_map) with device-varying operands corrupts under the
    # Pallas INTERPRETER, so off-TPU the sharded stage 1 runs the jnp ADC
    # reference (predicated compute); the kernel engages on real TPUs.
    if impl == "fused" and not resolve_interpret(None):
        from repro.kernels import ops as kops

        m = q_local.shape[0]
        bm = min(256, T.next_pow2(max(m, 8)))
        cand = kops.pq_scan_impl(
            q_local, pq_cb, pq_codes_local, local_cells,
            min(k_scan, cell_cap), cell_cap=cell_cap,
            centroids=cent_local if residual else None, distance=distance,
            tile_m=bm, packed_live=live,
            threshold_skip=threshold_skip).indices
    else:
        probed = jnp.any(
            local_cells[:, :, None] == jnp.arange(ncells_loc)[None, None, :],
            axis=1)
        cand = q_scan(
            q_local, pq_codes_local, k_scan, distance=distance, db_live=live,
            probed=probed, cell_cap=cell_cap, pq_codebook=pq_cb,
            cell_bias=cbias, threshold_skip=threshold_skip).indices

    # 3. Exact local rescore, then packed slot -> GLOBAL corpus row.
    vals, idx = rescore(q_local, packed_local, cand, k_loc,
                        distance=distance,
                        impl=impl if impl == "fused" else "jnp")
    safe = jnp.clip(idx, 0, S_loc - 1)
    idx = jnp.where(idx >= 0, jnp.take(row_of_slot_local, safe), -1)
    if vals.shape[1] < K:
        vals, idx = T.pad_topk(vals, idx, K)
    return vals, idx


# ---------------------------------------------------------------------------
# Host-level jitted entry points (build shard_map closures over a mesh).
# ---------------------------------------------------------------------------


def _flat_spec(axes) -> jax.sharding.PartitionSpec:
    return jax.sharding.PartitionSpec(axes)


def pad_rows_to(x: Array, mult: int) -> Array:
    pad = (-x.shape[0]) % mult
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad, x.shape[1]), x.dtype)], axis=0)
    return x


def make_ring_allpairs(
    mesh: jax.sharding.Mesh,
    *,
    axes: Sequence[str] | str | None = None,
    k: int,
    distance: str = "sqeuclidean",
    impl: str = "jnp",
    threshold_skip: bool | None = None,
    wire_dtype=None,
):
    """Build a jitted all-pairs kNN over ``mesh`` (ring over flattened axes).

    Returns fn(x [n, d]) -> KNNResult with n % P == 0 (use pad_rows_to).
    """
    axes = tuple(mesh.axis_names) if axes is None else (
        (axes,) if isinstance(axes, str) else tuple(axes)
    )
    P = int(np.prod([mesh.shape[a] for a in axes]))

    def fn(x: Array, n_real: int) -> KNNResult:
        n_pad = x.shape[0]
        assert n_pad % P == 0

        @functools.partial(
            jax.shard_map,
            mesh=mesh,
            in_specs=_flat_spec(axes),
            out_specs=(_flat_spec(axes), _flat_spec(axes)),
            check_vma=False,  # pallas_call inside shard_map has no vma info
        )
        def body(x_local):
            return ring_allpairs_shard(
                x_local,
                axis_name=axes,
                k=k,
                distance=distance,
                n_real=n_real,
                impl=impl,
                threshold_skip=threshold_skip,
                wire_dtype=wire_dtype,
            )

        v, i = body(x)
        return KNNResult(v[:n_real, :k], i[:n_real, :k])

    return jax.jit(fn, static_argnames=("n_real",))


def make_triangle_allpairs(
    mesh: jax.sharding.Mesh,
    *,
    axes: Sequence[str] | str | None = None,
    k: int,
    gsize: int,
    distance: str = "sqeuclidean",
    impl: str = "jnp",
    threshold_skip: bool | None = None,
):
    """Paper-faithful zigzag/triangle kNN over ``mesh``; final tree merge."""
    from repro.core import grid as G

    axes = tuple(mesh.axis_names) if axes is None else (
        (axes,) if isinstance(axes, str) else tuple(axes)
    )
    P = int(np.prod([mesh.shape[a] for a in axes]))

    def fn(x: Array, n_real: int) -> KNNResult:
        n_pad = x.shape[0]
        assert n_pad % (P * gsize) == 0 or n_pad % gsize == 0
        sched = G.make_schedule(n_pad, gsize, P)
        tiles = jnp.asarray(sched.tiles)
        valid = jnp.asarray(sched.valid)

        @functools.partial(
            jax.shard_map,
            mesh=mesh,
            in_specs=(_flat_spec(axes), _flat_spec(axes), _flat_spec(axes)),
            out_specs=(_flat_spec(axes), _flat_spec(axes)),
            check_vma=False,  # pallas_call inside shard_map has no vma info
        )
        def body(x_local, tiles_local, valid_local):
            rv, ri = triangle_allpairs_shard(
                x_local,
                tiles_local[0],
                valid_local[0],
                axis_name=axes,
                k=k,
                distance=distance,
                gsize=gsize,
                n_real=n_real,
                impl=impl,
                threshold_skip=threshold_skip,
            )
            # Paper: merge per-GPU heaps at the end. Beyond-paper: log-depth
            # on-device butterfly, then keep this device's row slice.
            rv, ri = tree_merge_topk(rv, ri, axes)
            p = jax.lax.axis_index(axes)
            n_loc = x_local.shape[0]
            rv = jax.lax.dynamic_slice(rv, (p * n_loc, 0), (n_loc, rv.shape[1]))
            ri = jax.lax.dynamic_slice(ri, (p * n_loc, 0), (n_loc, ri.shape[1]))
            return rv, ri

        v, i = body(x, tiles, valid)
        return KNNResult(v[:n_real, :k], i[:n_real, :k])

    return jax.jit(fn, static_argnames=("n_real",))


def make_query_sharded(
    mesh: jax.sharding.Mesh,
    *,
    query_axis: str,
    db_axis: str,
    k: int,
    distance: str = "sqeuclidean",
    impl: str = "fused",
    scan_dtype: str = "float32",
    overfetch: int = 4,
    wire_dtype=None,
    threshold_skip: bool | None = None,
):
    """Serving-path kNN: queries over ``query_axis``, database over ``db_axis``.

    fn(q [m, d], db [n, d], n_db_real, db_live=None, db_q=None) -> KNNResult;
    m % size(query_axis) == 0, n % size(db_axis) == 0.  ``db_live`` (optional
    bool [n]) is sharded over ``db_axis`` alongside the database — the serving
    index's tombstone mask.

    ``scan_dtype``/``overfetch``/``wire_dtype``: the quantized two-stage
    per-shard pipeline (see ``query_sharded_shard``).  ``db_q`` (optional
    ``QuantizedRows`` over the FULL padded database, sharded over ``db_axis``
    like the fp32 rows) avoids re-quantizing per call.  ``threshold_skip``
    threads down to the scan kernel (None = backend policy,
    ``topk.resolve_threshold_skip``).
    """
    q_axes = (query_axis,) if isinstance(query_axis, str) else tuple(query_axis)
    assert db_axis not in q_axes, (
        "queries must be replicated over db_axis (the butterfly merge runs "
        f"across it); got query_axis={query_axis!r} == db_axis={db_axis!r}")

    def fn(q: Array, db: Array, n_db_real: int, db_live: Array | None = None,
           db_q: QuantizedRows | None = None) -> KNNResult:
        q_spec = jax.sharding.PartitionSpec(query_axis)
        db_spec = jax.sharding.PartitionSpec(db_axis)
        row_spec = jax.sharding.PartitionSpec(db_axis)  # 1-D per-row arrays
        # None args are empty pytrees: a matching None spec threads them
        # through shard_map with zero per-call transfer (no fabricated masks).
        live_spec = None if db_live is None else row_spec
        dbq_spec = None if db_q is None else QuantizedRows(
            db_spec, None if db_q.scale is None else row_spec, row_spec)

        @functools.partial(
            jax.shard_map,
            mesh=mesh,
            in_specs=(q_spec, db_spec, live_spec, dbq_spec),
            out_specs=(q_spec, q_spec),
            # The butterfly merge leaves results replicated over db_axis; vma
            # tracking cannot infer replication through ppermute chains.
            check_vma=False,
        )
        def body(q_local, db_local, live_local, db_q_local):
            return query_sharded_shard(
                q_local,
                db_local,
                live_local,
                db_q_local,
                db_axis=db_axis,
                k=k,
                distance=distance,
                n_db_real=n_db_real,
                impl=impl,
                scan_dtype=scan_dtype,
                overfetch=overfetch,
                wire_dtype=wire_dtype,
                threshold_skip=threshold_skip,
            )

        v, i = body(q, db, db_live, db_q)
        return KNNResult(v, i)

    return jax.jit(fn, static_argnames=("n_db_real",))


def make_ivf_query_sharded(
    mesh: jax.sharding.Mesh,
    *,
    query_axis: str,
    db_axis: str,
    k: int,
    nprobe: int,
    cell_cap: int,
    distance: str = "sqeuclidean",
    impl: str = "fused",
    scan_dtype: str = "float32",
    overfetch: int = 4,
    wire_dtype=None,
    threshold_skip: bool | None = None,
):
    """IVF serving-path kNN over ``mesh`` (see ``ivf_query_sharded_shard``).

    fn(q [m, d], centroids [ncells, d], packed [S, d], row_of_slot [S],
    live_packed [S] bool | None, packed_q QuantizedRows | None) -> KNNResult
    with GLOBAL corpus-row indices.  ``q`` shards over ``query_axis``;
    ``centroids`` replicate (the shortlist problem is tiny and every shard
    needs the same global ranking); ``packed``/``row_of_slot``/``live_packed``
    /``packed_q`` shard over ``db_axis`` — requires m % size(query_axis) == 0
    and ncells % size(db_axis) == 0 (cell blocks never straddle shards).
    """
    q_axes = (query_axis,) if isinstance(query_axis, str) else tuple(query_axis)
    assert db_axis not in q_axes, (
        "queries must be replicated over db_axis (the butterfly merge runs "
        f"across it); got query_axis={query_axis!r} == db_axis={db_axis!r}")
    P_db = int(mesh.shape[db_axis])

    def fn(q: Array, centroids: Array, packed: Array, row_of_slot: Array,
           live_packed: Array | None = None,
           packed_q: QuantizedRows | None = None) -> KNNResult:
        S = packed.shape[0]
        assert S % (P_db * cell_cap) == 0, (
            f"ncells = {S // cell_cap} must divide over db_axis ({P_db})")
        q_spec = jax.sharding.PartitionSpec(query_axis)
        rep_spec = jax.sharding.PartitionSpec()  # centroids: replicated
        db_spec = jax.sharding.PartitionSpec(db_axis)
        row_spec = jax.sharding.PartitionSpec(db_axis)
        live_spec = None if live_packed is None else row_spec
        dbq_spec = None if packed_q is None else QuantizedRows(
            db_spec, None if packed_q.scale is None else row_spec, row_spec)

        @functools.partial(
            jax.shard_map,
            mesh=mesh,
            in_specs=(q_spec, rep_spec, db_spec, row_spec, live_spec,
                      dbq_spec),
            out_specs=(q_spec, q_spec),
            # The butterfly merge leaves results replicated over db_axis; vma
            # tracking cannot infer replication through ppermute chains.
            check_vma=False,
        )
        def body(q_local, cent, packed_local, ros_local, live_local,
                 packed_q_local):
            return ivf_query_sharded_shard(
                q_local,
                cent,
                packed_local,
                ros_local,
                live_local,
                packed_q_local,
                db_axis=db_axis,
                k=k,
                nprobe=nprobe,
                cell_cap=cell_cap,
                distance=distance,
                impl=impl,
                scan_dtype=scan_dtype,
                overfetch=overfetch,
                wire_dtype=wire_dtype,
                threshold_skip=threshold_skip,
            )

        v, i = body(q, centroids, packed, row_of_slot, live_packed, packed_q)
        return KNNResult(v, i)

    return jax.jit(fn)


def make_ivfpq_query_sharded(
    mesh: jax.sharding.Mesh,
    *,
    query_axis: str,
    db_axis: str,
    k: int,
    nprobe: int,
    cell_cap: int,
    distance: str = "sqeuclidean",
    impl: str = "fused",
    overfetch: int = 4,
    wire_dtype=None,
    threshold_skip: bool | None = None,
    residual: bool = True,
):
    """IVF-PQ serving-path kNN over ``mesh`` (see ``ivfpq_query_sharded_shard``).

    fn(q [m, d], centroids [ncells, d], pq_cb PQCodebook, pq_codes PQCodes,
    packed [S, d], row_of_slot [S], live_packed [S] bool | None) -> KNNResult
    with GLOBAL corpus-row indices.  ``q`` shards over ``query_axis``;
    ``centroids`` and the codebook replicate (every shard builds the same
    LUTs); the uint8 code rows, ``hy``, the fp32 packed rows (rescore
    operand), ``row_of_slot`` and ``live_packed`` shard over ``db_axis`` —
    requires m % size(query_axis) == 0 and ncells % size(db_axis) == 0.
    ``residual`` must match how the replica was built (``build_ivfpq``).
    """
    from repro.core.pq import PQCodebook, PQCodes

    q_axes = (query_axis,) if isinstance(query_axis, str) else tuple(query_axis)
    assert db_axis not in q_axes, (
        "queries must be replicated over db_axis (the butterfly merge runs "
        f"across it); got query_axis={query_axis!r} == db_axis={db_axis!r}")
    P_db = int(mesh.shape[db_axis])

    def fn(q: Array, centroids: Array, pq_cb, pq_codes, packed: Array,
           row_of_slot: Array, live_packed: Array | None = None) -> KNNResult:
        S = packed.shape[0]
        assert S % (P_db * cell_cap) == 0, (
            f"ncells = {S // cell_cap} must divide over db_axis ({P_db})")
        q_spec = jax.sharding.PartitionSpec(query_axis)
        rep_spec = jax.sharding.PartitionSpec()  # centroids + codebook
        db_spec = jax.sharding.PartitionSpec(db_axis)
        row_spec = jax.sharding.PartitionSpec(db_axis)
        live_spec = None if live_packed is None else row_spec
        cb_spec = PQCodebook(rep_spec)
        codes_spec = PQCodes(db_spec, row_spec)

        @functools.partial(
            jax.shard_map,
            mesh=mesh,
            in_specs=(q_spec, rep_spec, cb_spec, codes_spec, db_spec,
                      row_spec, live_spec),
            out_specs=(q_spec, q_spec),
            # The butterfly merge leaves results replicated over db_axis; vma
            # tracking cannot infer replication through ppermute chains.
            check_vma=False,
        )
        def body(q_local, cent, cb, codes_local, packed_local, ros_local,
                 live_local):
            return ivfpq_query_sharded_shard(
                q_local,
                cent,
                cb,
                codes_local,
                packed_local,
                ros_local,
                live_local,
                db_axis=db_axis,
                k=k,
                nprobe=nprobe,
                cell_cap=cell_cap,
                distance=distance,
                impl=impl,
                overfetch=overfetch,
                wire_dtype=wire_dtype,
                threshold_skip=threshold_skip,
                residual=residual,
            )

        v, i = body(q, centroids, pq_cb, pq_codes, packed, row_of_slot,
                    live_packed)
        return KNNResult(v, i)

    return jax.jit(fn)
