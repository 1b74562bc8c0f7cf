"""Single-device k-nearest-vector solver (paper Sect. 4-6).

Faithful structure:

* Phase 1 (Sect. 5): distances are computed tile-by-tile, streaming coordinate
  chunks (the paper's C2 loop) — here a VMEM-tiled Pallas kernel or an
  MXU-form jnp einsum; the tile never needs the whole d-dimensional vectors
  resident.
* Phase 2 (Sect. 6): each row's k smallest are maintained in a running sorted
  buffer with a threshold filter (the heap-top trick), see repro.core.topk.
  NOTE: ``threshold_skip`` defaults to False on the jnp paths — measured on
  CPU XLA the ``lax.cond`` costs more than the merges it skips
  (EXPERIMENTS.md §Perf, refuted-hypothesis log); the Pallas kernels keep the
  tile skip via ``pl.when`` where predication is near-free on TPU.
* Symmetric delta (Sect. 4): only upper-triangle tiles (X >= Y) are computed;
  each tile updates the heaps of its rows AND (transposed) of its columns —
  "each GPU virtually computes the mirror side".

Beyond-paper (TPU adaptation): ``impl="fused"`` never materializes distance
tiles in HBM at all — distance + selection fuse in one Pallas kernel, turning
the O(n^2) intermediate into O(n * k) (see DESIGN.md roofline discussion).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import topk as T
from repro.core.distances import (
    EXACT,
    Distance,
    QuantizedRows,
    get_distance,
    matmul_finalize,
)

Array = jnp.ndarray


class KNNResult(NamedTuple):
    distances: Array  # [m, k] ascending
    indices: Array  # [m, k] int32, -1 for padding (k > n_valid)


def pairwise_tile(
    x_tile: Array,
    y_tile: Array,
    dist: Distance,
    *,
    use_matmul: bool = True,
    chunk: int | None = None,
) -> Array:
    """One [m_tile, n_tile] distance tile, fp32 accumulate."""
    if use_matmul and dist.matmul_form is not None:
        return dist.matmul_form.pairwise(x_tile, y_tile, matmul_finalize(dist))
    return dist.pairwise(x_tile, y_tile, chunk)


def _pad_rows(x: Array, mult: int) -> Array:
    n = x.shape[0]
    pad = (-n) % mult
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad, x.shape[1]), x.dtype)], axis=0)
    return x


def _mask_tile(tile, row_off, col_off, n_rows, n_cols, exclude_diag):
    m, nn = tile.shape
    col_ids = col_off + jnp.arange(nn)
    tile = jnp.where(col_ids[None, :] >= n_cols, T.POS_INF, tile)
    if exclude_diag:
        row_ids = row_off + jnp.arange(m)
        tile = jnp.where(row_ids[:, None] == col_ids[None, :], T.POS_INF, tile)
    return tile


@functools.partial(
    jax.jit,
    static_argnames=(
        "k",
        "distance",
        "tile_m",
        "tile_n",
        "impl",
        "exclude_self",
        "threshold_skip",
    ),
)
def knn_query(
    queries: Array,
    database: Array,
    k: int,
    *,
    distance: str = "sqeuclidean",
    tile_m: int = 256,
    tile_n: int = 1024,
    impl: str = "jnp",
    exclude_self: bool = False,
    threshold_skip: bool | None = None,
    db_live: Array | None = None,
    q_allowed: Array | None = None,
) -> KNNResult:
    """k nearest database rows for each query row (asymmetric problem).

    ``impl``: "jnp" (XLA einsum tiles), "pallas" (Pallas distance kernel +
    jnp selection) or "fused" (single Pallas distance+select kernel).

    ``threshold_skip=None`` resolves per substrate (off here on the jnp
    selection, on inside the fused kernel) — ``topk.resolve_threshold_skip``.

    ``db_live``: optional traced bool [n] row mask — False rows score +inf
    and are never selected (the serving index's tombstones).  A mask keeps
    the compiled shapes independent of how many rows are dead, unlike
    over-fetch-and-filter schemes.

    ``q_allowed``: optional traced bool [m, n] PER-QUERY filter bitmap
    (DESIGN.md §17) — row j scores +inf for query i when
    ``q_allowed[i, j]`` is False, the per-query generalization of
    ``db_live``.  Both masks compose (a row must be live AND allowed);
    an all-True bitmap is bit-identical to passing None.  On the fused
    path the bitmap rides as a [bm, bn]-blocked kernel operand (the
    rank-1 ``hy`` epilogue can only express per-ROW masks).
    """
    dist = get_distance(distance)
    m_real, d = queries.shape
    n_real = database.shape[0]
    assert database.shape[1] == d
    k = min(k, n_real if not exclude_self else max(n_real - 1, 1))

    if impl == "fused":
        from repro.kernels import ops as kops

        return kops.fused_knn(
            queries,
            database,
            k,
            distance=distance,
            tile_m=tile_m,
            tile_n=tile_n,
            exclude_self=exclude_self,
            db_live=db_live,
            q_allowed=q_allowed,
            threshold_skip=threshold_skip,
        )
    threshold_skip = T.resolve_threshold_skip(threshold_skip, pallas=False)

    q = _pad_rows(queries, tile_m)
    db = _pad_rows(database, tile_n)
    n_row_tiles = q.shape[0] // tile_m
    n_col_tiles = db.shape[0] // tile_n
    live = None
    if db_live is not None:
        pad = db.shape[0] - n_real
        live = jnp.concatenate([db_live, jnp.zeros((pad,), bool)])
    allowed = None
    if q_allowed is not None:
        # Pad rows (sliced off) and columns (already +inf via n_real) False.
        allowed = _pad_rows(q_allowed, tile_m)
        pad_n = db.shape[0] - n_real
        if pad_n:
            allowed = jnp.concatenate(
                [allowed, jnp.zeros((allowed.shape[0], pad_n), bool)], axis=1)

    def tile_fn(qt, dbt):
        if impl == "pallas":
            from repro.kernels import ops as kops

            return kops.pairwise_distance(qt, dbt, distance=distance)
        return pairwise_tile(qt, dbt, dist)

    def row_block(_, r):
        row_off = r * tile_m
        qt = jax.lax.dynamic_slice(q, (row_off, 0), (tile_m, d))
        run = T.init_running(tile_m, k)

        def col_step(c, run):
            col_off = c * tile_n
            dbt = jax.lax.dynamic_slice(db, (col_off, 0), (tile_n, d))
            tile = tile_fn(qt, dbt)
            tile = _mask_tile(tile, row_off, col_off, m_real, n_real, exclude_self)
            if live is not None:
                live_sl = jax.lax.dynamic_slice(live, (col_off,), (tile_n,))
                tile = jnp.where(live_sl[None, :], tile, T.POS_INF)
            if allowed is not None:
                asl = jax.lax.dynamic_slice(
                    allowed, (row_off, col_off), (tile_m, tile_n))
                tile = jnp.where(asl, tile, T.POS_INF)
            return T.update_running(*run, tile, col_off, threshold_skip=threshold_skip)

        run = jax.lax.fori_loop(0, n_col_tiles, col_step, run)
        return None, T.finalize_topk(*run, k)

    _, (vals, idx) = jax.lax.scan(row_block, None, jnp.arange(n_row_tiles))
    vals = vals.reshape(-1, k)[:m_real]
    idx = idx.reshape(-1, k)[:m_real]
    return KNNResult(vals, idx)


@functools.partial(
    jax.jit,
    static_argnames=(
        "k",
        "distance",
        "gsize",
        "impl",
        "symmetric",
        "exclude_self",
        "threshold_skip",
    ),
)
def knn_allpairs(
    x: Array,
    k: int,
    *,
    distance: str = "sqeuclidean",
    gsize: int = 512,
    impl: str = "jnp",
    symmetric: bool = True,
    exclude_self: bool = True,
    threshold_skip: bool | None = None,
) -> KNNResult:
    """k nearest vectors to each vector (the paper's problem, nDevices = 1).

    ``symmetric=True`` computes only upper-triangle grids and pushes each tile
    into both its row heaps and (transposed) its column heaps — exactly the
    paper's Fig. 5 with one device.  ``symmetric=False`` falls back to the
    full-square ``knn_query(x, x)`` (the non-symmetric-delta variant), and
    so does ``impl="fused"``: the fused kernel keeps no column heaps, so it
    scores the full square in one kernel.
    """
    dist = get_distance(distance)
    from repro.core.distances import is_symmetric

    if impl == "fused" or not symmetric or not is_symmetric(distance):
        return knn_query(
            x,
            x,
            k,
            distance=distance,
            tile_m=min(gsize, 256),
            tile_n=gsize,
            impl=impl,
            exclude_self=exclude_self,
            threshold_skip=threshold_skip,
        )

    threshold_skip = T.resolve_threshold_skip(threshold_skip, pallas=False)
    n_real, d = x.shape
    k = min(k, max(n_real - 1, 1) if exclude_self else n_real)
    xp = _pad_rows(x, gsize)
    n_grids = xp.shape[0] // gsize

    # Static upper-triangle tile list (X >= Y), the nDevices=1 schedule.
    import numpy as np

    tile_list = np.array(
        [(X, Y) for Y in range(n_grids) for X in range(Y, n_grids)], np.int32
    )

    K = T.next_pow2(k)
    run_v = jnp.full((xp.shape[0], K), T.POS_INF, jnp.float32)
    run_i = jnp.full((xp.shape[0], K), -1, jnp.int32)

    def tile_fn(a, b):
        if impl == "pallas":
            from repro.kernels import ops as kops

            return kops.pairwise_distance(a, b, distance=distance)
        return pairwise_tile(a, b, dist)

    def step(carry, XY):
        run_v, run_i = carry
        X, Y = XY[0], XY[1]
        row_off = Y * gsize
        col_off = X * gsize
        rows = jax.lax.dynamic_slice(xp, (row_off, 0), (gsize, d))
        cols = jax.lax.dynamic_slice(xp, (col_off, 0), (gsize, d))
        tile = tile_fn(rows, cols)

        # Row-side update (grid (X, Y)).
        t_row = _mask_tile(tile, row_off, col_off, n_real, n_real, exclude_self)
        rv = jax.lax.dynamic_slice(run_v, (row_off, 0), (gsize, K))
        ri = jax.lax.dynamic_slice(run_i, (row_off, 0), (gsize, K))
        rv, ri = T.update_running(rv, ri, t_row, col_off, threshold_skip=threshold_skip)
        run_v = jax.lax.dynamic_update_slice(run_v, rv, (row_off, 0))
        run_i = jax.lax.dynamic_update_slice(run_i, ri, (row_off, 0))

        # Mirror-side update (grid (Y, X)) — skip on diagonal tiles.
        t_col = _mask_tile(tile.T, col_off, row_off, n_real, n_real, exclude_self)
        t_col = jnp.where(X == Y, T.POS_INF, t_col)
        cv = jax.lax.dynamic_slice(run_v, (col_off, 0), (gsize, K))
        ci = jax.lax.dynamic_slice(run_i, (col_off, 0), (gsize, K))
        cv, ci = T.update_running(cv, ci, t_col, row_off, threshold_skip=threshold_skip)
        run_v = jax.lax.dynamic_update_slice(run_v, cv, (col_off, 0))
        run_i = jax.lax.dynamic_update_slice(run_i, ci, (col_off, 0))
        return (run_v, run_i), None

    (run_v, run_i), _ = jax.lax.scan(step, (run_v, run_i), jnp.asarray(tile_list))
    vals, idx = T.finalize_topk(run_v, run_i, k)
    return KNNResult(vals[:n_real], idx[:n_real])


# ---------------------------------------------------------------------------
# Two-stage quantized retrieval: compressed scan + exact rescore
# (DESIGN.md §Quantized).
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("k", "distance", "impl"))
def rescore(
    queries: Array,
    database: Array,
    cand_idx: Array,
    k: int,
    *,
    distance: str = "sqeuclidean",
    impl: str = "jnp",
) -> KNNResult:
    """Exact top-k re-rank of per-query candidate rows [m, Kp] (-1 = empty).

    The repair stage of the quantized scan: gather the fp32 rows the scan
    nominated, score them exactly, keep the k best.  ``impl="fused"`` uses
    the Pallas rescore kernel (kernels/rescore.py); "jnp" is the XLA
    reference (gather + batched MXU-form scoring + ``lax.top_k``).
    Candidate slots must be distinct within a row (scan output is).
    """
    if impl == "fused":
        from repro.kernels import ops as kops

        return kops.rescore_topk(queries, database, cand_idx, k,
                                 distance=distance)
    m, d = queries.shape
    n = database.shape[0]
    Kp = cand_idx.shape[1]
    dist = get_distance(distance)
    mf = dist.matmul_form
    assert mf is not None, f"{distance} has no MXU form"
    safe = jnp.clip(cand_idx, 0, n - 1)
    rows = jnp.take(database, safe.reshape(-1), axis=0)  # [m * Kp, d]
    gy = mf.gy(rows).astype(jnp.float32).reshape(m, Kp, d)
    hy = mf.hy(rows).astype(jnp.float32).reshape(m, Kp)
    fx = mf.fx(queries).astype(jnp.float32)
    hx = mf.hx(queries).astype(jnp.float32)[:, None]
    dots = jnp.einsum("md,mcd->mc", fx, gy, precision=EXACT)
    tile = matmul_finalize(dist)(mf.alpha * dots + hx + hy)
    tile = jnp.where(cand_idx >= 0, tile, T.POS_INF)
    kk = min(k, Kp)
    vals, pos = T.topk_smallest(tile, kk)
    idx = jnp.take_along_axis(cand_idx, pos, axis=1)
    idx = jnp.where(jnp.isfinite(vals), idx, -1)
    if kk < k:
        vals, idx = T.pad_topk(vals, idx, k)
    return KNNResult(vals, idx)


@functools.partial(
    jax.jit,
    static_argnames=("k", "distance", "tile_m", "tile_n", "threshold_skip"),
)
def quantized_scan(
    queries: Array,
    db_q,
    k: int,
    *,
    distance: str = "sqeuclidean",
    tile_m: int = 256,
    tile_n: int = 1024,
    threshold_skip: bool | None = None,
    db_live: Array | None = None,
    probed: Array | None = None,
    cell_cap: int | None = None,
    pq_codebook=None,
    cell_bias: Array | None = None,
    q_allowed: Array | None = None,
) -> KNNResult:
    """Tiled jnp scan of a compressed replica — stage 1 reference.

    ``db_q`` is a ``QuantizedRows`` replica (scalar path) or a
    ``core.pq.PQCodes`` replica (ADC path, pass ``pq_codebook``).

    Scalar path — the XLA counterpart of the fused kernel's quantized scan:
    per column tile, the stored-dtype rows upcast to fp32 and the per-row
    int8 scale folds into the rank-1 epilogue (``finalize(alpha·(fx@dataᵀ)·
    scale + hx + hy)``).  The replica is NEVER dequantized wholesale — the
    only fp32 database-shaped arrays are [tile_n, d] per-tile upcasts, so
    the compressed replica's memory win survives on the jnp path (pinned by
    the jaxpr peak-shape test in tests/test_quantized.py).

    ADC path (DESIGN.md §PQ) — the reference for ``kernels/pq_scan.py``: the
    per-query LUTs build once (``build_pq_luts``) and each column tile
    scores through the SAME one-hot MXU contraction as the kernel
    (``kernels.pq_scan.adc_tile``), so at tile_n = cell_cap the two paths
    are bit-identical under the interpreter (tested).  ``cell_bias``
    [m, ncells] is the residual-PQ cross term (``pq_cell_bias``), gathered
    per column by cell id.

    ``db_live``: [n] bool row mask (tombstones).  ``probed``/``cell_cap``:
    optional per-QUERY cell mask [m, ncells] for the IVF jnp path — a column
    of cell ``c`` is masked +inf for queries that did not probe ``c``
    (the ``db_live``-style fallback when the scalar-prefetch kernels are not
    in play; cells here cost predicated compute, not zero DMA).

    ``q_allowed``: optional bool [m, n] PER-QUERY filter bitmap in the SAME
    row order as ``db_q`` (packed-slot order for a cell-packed replica —
    see ``ivf_query``, which permutes it); column j is +inf for query i
    when False, composing with both ``db_live`` and ``probed``
    (DESIGN.md §17).
    """
    from repro.core.pq import PQCodes, build_pq_luts
    from repro.kernels.pq_scan import adc_tile

    threshold_skip = T.resolve_threshold_skip(threshold_skip, pallas=False)
    dist = get_distance(distance)
    mf = dist.matmul_form
    assert mf is not None, f"{distance} has no MXU form"
    fin = matmul_finalize(dist)
    m_real, d = queries.shape
    pq = isinstance(db_q, PQCodes)
    n_real = (db_q.codes if pq else db_q.data).shape[0]
    k = min(k, n_real)

    if pq:
        assert pq_codebook is not None, "PQCodes scan needs its codebook"
        ncodes = pq_codebook.ncodes
        luts = build_pq_luts(pq_codebook, queries, distance=distance)
        fx = _pad_rows(luts.reshape(m_real, -1), tile_m)  # flattened LUTs
    else:
        fx = _pad_rows(mf.fx(queries).astype(jnp.float32), tile_m)
    hx = _pad_rows(mf.hx(queries).astype(jnp.float32)[:, None], tile_m)
    # Dead rows (pad, tombstones) die through the hy epilogue term — one
    # [n] where() instead of per-tile masks, same idiom as the kernels.
    hy = db_q.hy
    if db_live is not None:
        hy = jnp.where(db_live, hy, T.POS_INF)
    pad_n = (-n_real) % tile_n
    if pq:
        # Transposed codes: the column (row-of-corpus) axis last, like the
        # kernel's streamed operand; pad columns are dead via hy below.
        data = jnp.pad(db_q.codes, ((0, pad_n), (0, 0))).T  # [m_sub, n_pad]
        scale = None
    else:
        data = jnp.pad(db_q.data, ((0, pad_n), (0, 0)))
        scale = (None if db_q.scale is None else
                 jnp.pad(db_q.scale, (0, pad_n), constant_values=1.0)[None, :])
    hy = jnp.pad(hy, (0, pad_n), constant_values=T.POS_INF)[None, :]
    if probed is not None:
        assert cell_cap is not None
        probed = _pad_rows(probed, tile_m)
    if q_allowed is not None:
        q_allowed = _pad_rows(q_allowed, tile_m)
        if pad_n:
            q_allowed = jnp.concatenate(
                [q_allowed, jnp.zeros((q_allowed.shape[0], pad_n), bool)],
                axis=1)
    if cell_bias is not None:
        assert pq and cell_cap is not None
        cell_bias = _pad_rows(cell_bias, tile_m)

    n_row_tiles = fx.shape[0] // tile_m
    n_col_tiles = data.shape[1 if pq else 0] // tile_n

    def row_block(_, r):
        row_off = r * tile_m
        fxt = jax.lax.dynamic_slice(fx, (row_off, 0), (tile_m, fx.shape[1]))
        hxt = jax.lax.dynamic_slice(hx, (row_off, 0), (tile_m, 1))
        pbt = (None if probed is None else jax.lax.dynamic_slice(
            probed, (row_off, 0), (tile_m, probed.shape[1])))
        cbt = (None if cell_bias is None else jax.lax.dynamic_slice(
            cell_bias, (row_off, 0), (tile_m, cell_bias.shape[1])))
        run = T.init_running(tile_m, k)

        def col_step(c, run):
            col_off = c * tile_n
            if pq:
                ct = jax.lax.dynamic_slice(
                    data, (0, col_off), (data.shape[0], tile_n))
                t = adc_tile(fxt, ct, ncodes)  # the kernel's exact tile math
                if cbt is not None:
                    cell_ids = (col_off + jnp.arange(tile_n)) // cell_cap
                    cell_ids = jnp.clip(cell_ids, 0, cbt.shape[1] - 1)
                    t = t + jnp.take(cbt, cell_ids, axis=1)
            else:
                dt = jax.lax.dynamic_slice(data, (col_off, 0), (tile_n, d))
                dots = jnp.matmul(fxt, dt.astype(jnp.float32).T,  # per-tile upcast
                                  precision=EXACT)
                t = mf.alpha * dots
                if scale is not None:
                    t = t * jax.lax.dynamic_slice(scale, (0, col_off),
                                                  (1, tile_n))
            hyt = jax.lax.dynamic_slice(hy, (0, col_off), (1, tile_n))
            tile = fin(t + hxt + hyt)
            if pbt is not None:
                cell_ids = (col_off + jnp.arange(tile_n)) // cell_cap
                cell_ids = jnp.clip(cell_ids, 0, pbt.shape[1] - 1)
                tile = jnp.where(jnp.take(pbt, cell_ids, axis=1), tile,
                                 T.POS_INF)
            if q_allowed is not None:
                asl = jax.lax.dynamic_slice(
                    q_allowed, (row_off, col_off), (tile_m, tile_n))
                tile = jnp.where(asl, tile, T.POS_INF)
            return T.update_running(*run, tile, col_off,
                                    threshold_skip=threshold_skip)

        run = jax.lax.fori_loop(0, n_col_tiles, col_step, run)
        return None, T.finalize_topk(*run, k)

    _, (vals, idx) = jax.lax.scan(row_block, None, jnp.arange(n_row_tiles))
    return KNNResult(vals.reshape(-1, k)[:m_real], idx.reshape(-1, k)[:m_real])


def scan_width(n: int, k: int, overfetch: int) -> int:
    """Candidate fetch width K' of the quantized scan (overfetch math).

    K' = min(n, overfetch * next_pow2(k)): the scan's only failure mode is a
    true top-k row ranked below K' by the quantization error, so recall@k is
    the probability that the corpus holds > (overfetch-1) * K impostors whose
    DEQUANTIZED distance beats a true neighbor's — driven to ~0 exponentially
    in ``overfetch`` (measured: EXPERIMENTS.md §Quantized).  At K' = n the
    two-stage pipeline is exhaustive and exact by construction.
    """
    assert overfetch >= 1, overfetch
    return min(n, overfetch * T.next_pow2(k))


@functools.partial(
    jax.jit,
    static_argnames=("k", "distance", "impl", "overfetch", "threshold_skip"),
)
def two_stage_query(
    queries: Array,
    database: Array,
    db_q: QuantizedRows,
    k: int,
    *,
    distance: str = "sqeuclidean",
    impl: str = "jnp",
    overfetch: int = 4,
    threshold_skip: bool | None = None,
    db_live: Array | None = None,
    q_allowed: Array | None = None,
) -> KNNResult:
    """Quantized scan of ``db_q`` + exact fp32 rescore against ``database``.

    Stage 1 scans the low-precision replica for K' = scan_width(n, k,
    overfetch) candidates (tombstones masked inside the scan); stage 2
    re-scores the candidates against the fp32 corpus and returns the exact
    top-k OF THE CANDIDATE SET.  With a float32 replica the candidate set
    provably contains the true top-k, so the result is exact; quantized
    replicas trade recall for a 2x/4x smaller database stream
    (DESIGN.md §Quantized).  ``impl="fused"`` scans with the Pallas kernel;
    anything else uses the tiled jnp reference (``quantized_scan`` — scores
    the stored rows directly, never a dequantized corpus copy).
    ``q_allowed`` ([m, n] bool, DESIGN.md §17) masks the SCAN per query, so
    the candidate set — and therefore the exact rescore — only ever holds
    allowed rows.
    """
    n = database.shape[0]
    k_scan = scan_width(n, k, overfetch)
    if impl == "fused":
        from repro.kernels import ops as kops

        m = queries.shape[0]
        bm = min(256, T.next_pow2(max(m, 8)))
        cand = kops.fused_knn(
            queries, db_q, k_scan, distance=distance, tile_m=bm,
            db_live=db_live, q_allowed=q_allowed,
            threshold_skip=threshold_skip).indices
    else:
        cand = quantized_scan(
            queries, db_q, k_scan, distance=distance,
            db_live=db_live, q_allowed=q_allowed,
            threshold_skip=threshold_skip).indices
    return rescore(queries, database, cand, min(k, n), distance=distance,
                   impl=impl)


def _packed_allowed(ivf, q_allowed: Array | None) -> Array | None:
    """Per-query bitmap [m, n] in ORIGINAL row order -> packed-slot order.

    The per-query analogue of ``core.ivf.packed_live``: the mask rides the
    cell-packing permutation (pad slots disallowed), never retraining it
    (DESIGN.md §17).
    """
    if q_allowed is None:
        return None
    safe = jnp.clip(ivf.row_of_slot, 0, q_allowed.shape[1] - 1)
    return jnp.logical_and(ivf.row_of_slot >= 0,
                           jnp.take(q_allowed, safe, axis=1))


def _mask_excluded_rows(rows: Array, exclude_rows: Array | None) -> Array:
    """Drop candidate rows named by a per-query exclusion list.

    ``exclude_rows`` [m, E] int32 database rows, -1 padded; matching
    candidates become -1 (the empty-slot convention ``rescore`` maps to
    +inf / id -1).  Exactness needs the candidate width to exceed k + E —
    callers widen ``overfetch`` (the serving layer's post-filter budget,
    DESIGN.md §17).
    """
    if exclude_rows is None:
        return rows
    hit = jnp.any(rows[:, :, None] == exclude_rows[:, None, :], axis=2)
    return jnp.where(hit, -1, rows)


# ---------------------------------------------------------------------------
# IVF cell-probed retrieval: coarse quantizer + pruned scan + exact rescore
# (DESIGN.md §IVF).
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit,
    static_argnames=("k", "nprobe", "distance", "impl", "overfetch",
                     "threshold_skip"),
)
def ivf_query(
    queries: Array,
    database: Array,
    ivf,
    k: int,
    *,
    nprobe: int = 8,
    distance: str = "sqeuclidean",
    impl: str = "jnp",
    overfetch: int = 4,
    threshold_skip: bool | None = None,
    db_live: Array | None = None,
    packed_q: QuantizedRows | None = None,
    q_allowed: Array | None = None,
    exclude_rows: Array | None = None,
) -> KNNResult:
    """Cell-probed kNN: centroid shortlist → pruned scan → exact rescore.

    ``ivf`` is a trained ``core.ivf.IVFCells`` over ``database``; the
    pipeline (DESIGN.md §IVF) is

      1. shortlist: ``nprobe`` nearest centroids per query — one more kNN
         problem over [ncells, d], solved by the repo's own solver;
      2. pruned scan: the cell-packed replica (``packed_q`` if given, else
         the fp32 packed rows) is scanned ONLY in probed cells for
         K' = scan_width(n, k, overfetch) candidates.  ``impl="fused"`` uses
         the scalar-prefetch Pallas kernel — unprobed cell blocks are never
         DMA'd, each query tile scanning the union of its queries' probes;
         other impls use the ``quantized_scan`` jnp reference with a
         per-query probe mask (``db_live``-style: predicated, not pruned);
      3. rescore: candidates externalize through ``row_of_slot`` and
         re-rank exactly against the fp32 corpus (``rescore``).

    ``nprobe = ncells`` probes everything — with the default fp32 packed
    replica the result is identical to ``knn_query`` (the exactness escape
    hatch, tested).  ``db_live`` is the [n] tombstone mask in ORIGINAL row
    order; it rides through the packing permutation, never retraining it.

    ``q_allowed`` ([m, n] bool in ORIGINAL row order, DESIGN.md §17) is the
    per-query filter bitmap: on jnp impls it permutes to slot order and
    masks INSIDE the pruned scan (pre-filter — exact under the same escape
    hatch); on ``impl="fused"`` the scalar-prefetch kernel is left
    untouched and the bitmap drops disallowed CANDIDATES before rescore
    instead (post-filter at scan width — widen ``overfetch`` for selective
    filters).  ``exclude_rows`` ([m, E] int32, -1 padded) names per-query
    rows dropped at the rescore stage on every impl.
    """
    from repro.core import ivf as IVF

    n = database.shape[0]
    k = min(k, n)
    ncells, cap = ivf.ncells, ivf.cell_cap
    nprobe = min(nprobe, ncells)
    cells = IVF.probe_cells(queries, ivf.centroids, nprobe,
                            distance=distance, impl=impl)
    live_p = IVF.packed_live(ivf, db_live)
    allowed_p = _packed_allowed(ivf, q_allowed)
    k_scan = scan_width(n, k, overfetch)
    if impl == "fused":
        from repro.kernels import ops as kops

        # The kernel's per-tile fetch width is bounded by the cell block.
        assert T.next_pow2(k) <= cap, (k, cap)
        cand = kops.ivf_scan(
            queries, ivf.packed if packed_q is None else packed_q, cells,
            min(k_scan, cap), cell_cap=cap, distance=distance,
            packed_live=live_p, threshold_skip=threshold_skip).indices
        if allowed_p is not None:
            # Post-filter: the scalar-prefetch kernel stays mask-free; the
            # bitmap culls its candidate slots before the exact rescore.
            ok = jnp.take_along_axis(
                allowed_p, jnp.clip(cand, 0, allowed_p.shape[1] - 1), axis=1)
            cand = jnp.where(ok, cand, -1)
    else:
        scan_q = packed_q
        if scan_q is None:
            from repro.core.distances import quantize_rows

            scan_q = quantize_rows(ivf.packed, "float32", distance=distance)
        probed = jnp.any(
            cells[:, :, None] == jnp.arange(ncells)[None, None, :], axis=1)
        cand = quantized_scan(
            queries, scan_q, k_scan, distance=distance, db_live=live_p,
            probed=probed, cell_cap=cap, q_allowed=allowed_p,
            threshold_skip=threshold_skip).indices
    safe = jnp.clip(cand, 0, ivf.row_of_slot.shape[0] - 1)
    rows = jnp.where(cand >= 0, jnp.take(ivf.row_of_slot, safe), -1)
    rows = _mask_excluded_rows(rows, exclude_rows)
    return rescore(queries, database, rows, k, distance=distance,
                   impl="fused" if impl == "fused" else "jnp")


# ---------------------------------------------------------------------------
# IVF-PQ: coarse quantizer + product-quantized ADC scan + exact rescore
# (DESIGN.md §PQ).
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit,
    static_argnames=("k", "nprobe", "distance", "impl", "overfetch",
                     "threshold_skip", "residual"),
)
def ivfpq_query(
    queries: Array,
    database: Array,
    ivf,
    pq_cb,
    pq_codes,
    k: int,
    *,
    nprobe: int = 8,
    distance: str = "sqeuclidean",
    impl: str = "jnp",
    overfetch: int = 4,
    threshold_skip: bool | None = None,
    db_live: Array | None = None,
    residual: bool = True,
    q_allowed: Array | None = None,
    exclude_rows: Array | None = None,
) -> KNNResult:
    """IVF-PQ kNN: centroid shortlist → ADC scan of m-byte codes → rescore.

    The IVFADC pipeline (DESIGN.md §PQ): ``ivf`` is a trained
    ``core.ivf.IVFCells`` over ``database`` and ``pq_cb``/``pq_codes`` its
    PQ replica in PACKED slot order (``core.pq.build_ivfpq`` — codes encode
    residuals to the cell centroid when ``residual=True``, which MUST match
    how the replica was built).  Stage 1 probes ``nprobe`` cells and scans
    their uint8 code blocks by LUT accumulation — ``impl="fused"`` uses the
    scalar-prefetch Pallas kernel (``kernels/pq_scan.py``: unprobed cells
    are never DMA'd), other impls the ``quantized_scan`` ADC reference with
    a per-query probe mask; stage 2 re-ranks the K' = ``scan_width(n, k,
    overfetch)`` survivors exactly against the fp32 corpus.

    PQ is lossy, so there is no nprobe escape hatch to bit-exactness — but
    the candidate ordering is the ONLY error source (the scanned value is
    exactly the distance to the decoded corpus, and rescore is exact), so
    ``nprobe = ncells`` with ``overfetch`` spanning the corpus reproduces
    ``knn_query`` (tested).  ``db_live`` is the [n] tombstone mask in
    ORIGINAL row order, riding the packing permutation as in ``ivf_query``.
    ``q_allowed``/``exclude_rows`` follow ``ivf_query`` exactly: per-query
    bitmap pre-filtered inside the jnp ADC scan (post-filtered at the
    candidate stage on ``impl="fused"``), per-query exclusion rows dropped
    at rescore (DESIGN.md §17).
    """
    from repro.core import ivf as IVF
    from repro.core.pq import pq_cell_bias

    n = database.shape[0]
    k = min(k, n)
    ncells, cap = ivf.ncells, ivf.cell_cap
    nprobe = min(nprobe, ncells)
    cells = IVF.probe_cells(queries, ivf.centroids, nprobe,
                            distance=distance, impl=impl)
    live_p = IVF.packed_live(ivf, db_live)
    allowed_p = _packed_allowed(ivf, q_allowed)
    k_scan = scan_width(n, k, overfetch)
    if impl == "fused":
        from repro.kernels import ops as kops

        # The kernel's per-tile fetch width is bounded by the cell block.
        assert T.next_pow2(k) <= cap, (k, cap)
        cand = kops.pq_scan(
            queries, pq_cb, pq_codes, cells, min(k_scan, cap), cell_cap=cap,
            centroids=ivf.centroids if residual else None, distance=distance,
            packed_live=live_p, threshold_skip=threshold_skip).indices
        if allowed_p is not None:
            ok = jnp.take_along_axis(
                allowed_p, jnp.clip(cand, 0, allowed_p.shape[1] - 1), axis=1)
            cand = jnp.where(ok, cand, -1)
    else:
        probed = jnp.any(
            cells[:, :, None] == jnp.arange(ncells)[None, None, :], axis=1)
        cbias = (pq_cell_bias(queries, ivf.centroids, distance=distance)
                 if residual else None)
        cand = quantized_scan(
            queries, pq_codes, k_scan, distance=distance, db_live=live_p,
            probed=probed, cell_cap=cap, pq_codebook=pq_cb, cell_bias=cbias,
            q_allowed=allowed_p, threshold_skip=threshold_skip).indices
    safe = jnp.clip(cand, 0, ivf.row_of_slot.shape[0] - 1)
    rows = jnp.where(cand >= 0, jnp.take(ivf.row_of_slot, safe), -1)
    rows = _mask_excluded_rows(rows, exclude_rows)
    return rescore(queries, database, rows, k, distance=distance,
                   impl="fused" if impl == "fused" else "jnp")
