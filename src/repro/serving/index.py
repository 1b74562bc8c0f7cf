"""RetrievalIndex: an exact-kNN index with an online update path.

The kNN solvers in ``repro.core`` answer "k nearest of THIS array" — a batch
primitive.  Serving needs an *index*: a corpus that changes while queries are
in flight.  The classic design (faiss's IndexIVF add/remove, LSM trees) is a
two-segment split, adapted here to the constraint that XLA recompiles on any
shape change:

* **main segment** — an immutable packed ``[n, d]`` array scored with the
  existing engines (``core.knn.knn_query`` locally, the query-sharded
  butterfly path on a mesh).  Deletes tombstone rows instead of repacking, so
  the device array and every compiled executable stay valid.
* **delta segment** — an append-only array with power-of-two capacity
  doubling, so inserts hit at most log2(n) distinct shapes.  Rows past the
  write head are dead by construction.
* **tombstones as a live-row mask** — dead rows (deleted, superseded, or past
  the delta write head) are masked to +inf *inside* the scorers
  (``db_live`` on ``knn_query`` / the fused kernel's rank-1 epilogue /
  the query-sharded path), so selection never sees them.  Exact by
  construction, and the compiled shapes are independent of how many rows are
  dead — mutations never change the fetch width.
* **compact()** — re-packs live main+delta rows into a fresh immutable main
  segment (re-sharding it over the mesh when one is configured) and clears
  the delta.  This is the LSM merge; serving continues across it because
  search never mutates.

External ids are caller-chosen int32 keys; searches return (distances, ids)
with ``-1`` id padding when fewer than k live rows exist.  Exactness after any
interleaving of insert/upsert/delete/compact — equality with a brute-force
rebuild — is the contract ``tests/test_serving.py`` checks.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import accounting
from repro.core import topk as T
from repro.core.distances import QUANTIZABLE, canonical_scan_dtype, quantize_rows
from repro.core.knn import ivf_query, ivfpq_query, knn_query, two_stage_query

Array = jnp.ndarray

_MIN_DELTA_CAP = 64


class SearchResult(NamedTuple):
    distances: Array  # [m, k] ascending
    ids: Array  # [m, k] int32 external ids, -1 past the live count
    # Fault-tolerance accounting (DESIGN.md §14), populated by ShardRouter
    # (all-ones coverage on a healthy fleet); None on single-host paths, so
    # the 2-tuple construction/unpacking everywhere else keeps working.
    coverage: np.ndarray | None = None  # [m] fraction of probed cells served
    shard_status: tuple | None = None  # ((shard_id, "ok|skipped|failed"),...)


@functools.partial(jax.jit,
                   static_argnames=("k_out", "distance", "impl", "post"))
def _segment_candidates(q, vecs, live, ids, allowed=None, *, k_out, distance,
                        impl, post=False):
    """Top-``k_out`` LIVE candidates of one segment, ascending, padded.

    Dead rows are masked to +inf inside the scorer (``db_live``), so the
    result is exact at fetch width ``k_out`` no matter how many rows are
    tombstoned.  ``allowed`` is the optional [m, n] per-query filter bitmap
    (DESIGN.md §17): ``post=False`` pre-filters inside the scan,
    ``post=True`` scans unfiltered and drops disallowed candidates after —
    the caller widens ``k_out`` to keep that exact enough.  Returns
    ([m, k_out] vals, [m, k_out] external ids).
    """
    vals, idx = knn_query(q, vecs, k_out, distance=distance, impl=impl,
                          db_live=live,
                          q_allowed=None if post else allowed)
    if post and allowed is not None:
        vals, idx = _drop_disallowed(vals, idx, allowed)
    return _externalize(vals, idx, ids, k_out)


def _drop_disallowed(vals, idx, allowed):
    """Post-filter scored candidates by the [m, n] bitmap; re-sorts.

    Disallowed entries become +inf / -1 and are sorted past every survivor
    (stable, so surviving order is preserved) — the output obeys the same
    ascending/-1-padded contract as the scorers (DESIGN.md §17).
    """
    ok = jnp.take_along_axis(
        allowed, jnp.clip(idx, 0, allowed.shape[1] - 1), axis=1)
    ok = jnp.logical_and(ok, idx >= 0)
    vals = jnp.where(ok, vals, T.POS_INF)
    idx = jnp.where(ok, idx, -1)
    order = jnp.argsort(vals, axis=1, stable=True)
    return (jnp.take_along_axis(vals, order, axis=1),
            jnp.take_along_axis(idx, order, axis=1))


def _externalize(vals, idx, ids, k_out):
    """Row indices -> external ids, padded out to fetch width ``k_out``."""
    with jax.named_scope("knn.merge"):
        safe = jnp.clip(idx, 0, ids.shape[0] - 1)
        ok = idx >= 0  # -1 where masked/padded (val == +inf)
        ext = jnp.where(ok, jnp.take(ids, safe, axis=0), jnp.int32(-1))
        if vals.shape[-1] < k_out:  # scorers clamp k to the row count
            vals, ext = T.pad_topk(vals, ext, k_out)
        return vals, ext


@functools.partial(jax.jit, static_argnames=("k_out", "nprobe", "overfetch",
                                             "distance", "impl", "post"))
def _segment_candidates_ivf(q, vecs, ivf, qrows, live, ids, allowed=None, *,
                            k_out, nprobe, overfetch, distance, impl,
                            post=False):
    """Cell-probed top-``k_out`` of one segment (DESIGN.md §IVF).

    ``ivf`` is the segment's trained ``IVFCells`` (epoch-keyed: rebuilt at
    build/compact only); ``qrows`` the quantized replica of its PACKED rows
    (None = fp32 scan); ``live`` the tombstone mask in ORIGINAL row order —
    it rides through the packing permutation, never retraining it.
    ``allowed``/``post`` as in ``_segment_candidates`` (DESIGN.md §17).
    """
    vals, idx = ivf_query(q, vecs, ivf, k_out, nprobe=nprobe,
                          distance=distance, impl=impl, overfetch=overfetch,
                          db_live=live, packed_q=qrows,
                          q_allowed=None if post else allowed)
    if post and allowed is not None:
        vals, idx = _drop_disallowed(vals, idx, allowed)
    return _externalize(vals, idx, ids, k_out)


@functools.partial(jax.jit, static_argnames=("k_out", "nprobe", "overfetch",
                                             "distance", "impl", "post"))
def _segment_candidates_ivfpq(q, vecs, ivf, pq_cb, pq_codes, live, ids,
                              allowed=None, *, k_out, nprobe, overfetch,
                              distance, impl, post=False):
    """IVF-PQ top-``k_out`` of one segment (DESIGN.md §PQ).

    ``pq_cb``/``pq_codes`` are the segment's epoch-keyed residual-PQ replica
    over its PACKED rows (``core.pq.build_ivfpq``); everything else matches
    ``_segment_candidates_ivf`` — the live mask rides the packing
    permutation, the rescore stage is exact fp32.
    """
    vals, idx = ivfpq_query(q, vecs, ivf, pq_cb, pq_codes, k_out,
                            nprobe=nprobe, distance=distance, impl=impl,
                            overfetch=overfetch, db_live=live,
                            q_allowed=None if post else allowed)
    if post and allowed is not None:
        vals, idx = _drop_disallowed(vals, idx, allowed)
    return _externalize(vals, idx, ids, k_out)


@functools.partial(jax.jit, static_argnames=("k_out", "overfetch", "distance",
                                             "impl", "post"))
def _segment_candidates_quantized(q, vecs, qrows, live, ids, allowed=None, *,
                                  k_out, overfetch, distance, impl,
                                  post=False):
    """Two-stage top-``k_out`` of one segment: quantized scan + exact rescore.

    Stage 1 scans the segment's low-precision replica (``qrows``, tombstones
    masked inside the scan) for overfetch * k_out candidates; stage 2
    re-scores them against the segment's fp32 rows (DESIGN.md §Quantized).
    Returns ([m, k_out] exact vals, [m, k_out] external ids).
    """
    vals, idx = two_stage_query(q, vecs, qrows, k_out, distance=distance,
                                impl=impl, overfetch=overfetch, db_live=live,
                                q_allowed=None if post else allowed)
    if post and allowed is not None:
        vals, idx = _drop_disallowed(vals, idx, allowed)
    return _externalize(vals, idx, ids, k_out)


@functools.partial(jax.jit, static_argnames=("k",))
def _merge_candidates(av, ai, bv, bi, *, k):
    """Merge two ascending equal-width candidate sets, keep k smallest."""
    with jax.named_scope("knn.merge"):
        mv, mi = T.merge_topk_sorted(av, ai, bv, bi)
        return T.finalize_topk(mv, mi, k)


@functools.partial(jax.jit, static_argnames=("k",))
def _finalize_filtered(vals, ids, exclude_ids, *, k):
    """Apply per-query EXTERNAL-id exclusions and cut to width ``k``.

    ``exclude_ids`` [m, E] int32, -1 padded (None = no exclusions).  The
    candidate width arriving here is >= k + E (``_search_filtered`` widens
    the fetch), so masking E rows still leaves k exact survivors
    (DESIGN.md §17).  Same stable re-sort contract as ``_drop_disallowed``.
    """
    if exclude_ids is not None:
        hit = jnp.any(ids[:, :, None] == exclude_ids[:, None, :], axis=2)
        hit = jnp.logical_and(hit, ids >= 0)
        vals = jnp.where(hit, T.POS_INF, vals)
        ids = jnp.where(hit, -1, ids)
        order = jnp.argsort(vals, axis=1, stable=True)
        vals = jnp.take_along_axis(vals, order, axis=1)
        ids = jnp.take_along_axis(ids, order, axis=1)
    return vals[:, :k], ids[:, :k]


class RetrievalIndex:
    """Mutable exact-kNN index over (id, vector) rows.  See module docstring.

    ``impl``: "jnp" or "fused" — forwarded to the per-segment scorer.
    ``mesh``/``db_axis``: optional — shard the main segment over ``db_axis``
    and score it with the butterfly-merge serving path
    (``core.distributed.make_query_sharded``); the delta segment always
    scores locally (it is small by construction).

    ``scan_dtype``/``overfetch``: the quantized two-stage retrieval knob
    (DESIGN.md §Quantized).  "bfloat16"/"int8" keep a low-precision replica
    of the MAIN segment (rebuilt when its rows change, i.e. at build and
    compact — tombstones are a mask and never touch the replica), scan it
    for overfetch * k candidates, and rescore those exactly against the fp32
    rows; the delta segment always scans fp32 (it is small by construction).
    The default "float32" bypasses the two-stage path entirely — results
    stay bit-exact.

    ``ivf_cells``/``nprobe``: the cell-probed sublinear scan (DESIGN.md
    §IVF).  ``ivf_cells > 0`` trains a coarse quantizer over the MAIN
    segment and scans only each query's ``nprobe`` nearest cells (composing
    with ``scan_dtype``: the cell-packed replica is quantized, IVFADC-style).
    The IVF structure is keyed on the row EPOCH exactly like the quantized
    replica — rebuilt at build/compact only; tombstones flip the live mask
    through the packing permutation and never retrain; the delta segment
    stays flat-scanned.  ``nprobe >= ivf_cells`` probes everything (exact
    with a fp32 scan).

    ``pq_m``/``pq_nbits``: product-quantized ADC scan of the MAIN segment
    (DESIGN.md §PQ; requires ``ivf_cells > 0`` — the IVFADC composition).
    ``pq_m > 0`` trains residual-PQ codebooks over the cell-packed rows and
    scans ``pq_m``-byte uint8 code rows instead of the ``scan_dtype``
    replica (which the main scan then ignores); candidates still rescore
    exactly in fp32.  Epoch policy is identical to IVF: build/compact
    retrain codebooks + re-encode, tombstones never do, delta stays
    flat-scanned fp32.  A main segment with fewer than 2^pq_nbits rows
    cannot train a codebook and falls back to the plain IVF scan.
    """

    def __init__(self, dim: int, *, distance: str = "sqeuclidean",
                 impl: str = "jnp", mesh=None, db_axis: str = "model",
                 query_axis: str = "data", scan_dtype: str = "float32",
                 overfetch: int = 4, ivf_cells: int = 0, nprobe: int = 8,
                 pq_m: int = 0, pq_nbits: int = 8):
        self.dim = int(dim)
        self.distance = distance
        self.impl = impl
        self.mesh = mesh
        self.db_axis = db_axis
        self.query_axis = query_axis
        self.scan_dtype = canonical_scan_dtype(scan_dtype)
        self.overfetch = int(overfetch)
        self.ivf_cells = int(ivf_cells)
        self.nprobe = int(nprobe)
        self.pq_m = int(pq_m)
        self.pq_nbits = int(pq_nbits)
        assert self.overfetch >= 1, overfetch
        assert self.ivf_cells >= 0 and self.nprobe >= 1, (ivf_cells, nprobe)
        if self.scan_dtype != "float32" and distance not in QUANTIZABLE:
            raise ValueError(
                f"scan_dtype={scan_dtype!r} needs a quantizable distance; "
                f"{distance!r} is not in {QUANTIZABLE}")
        if self.ivf_cells and distance not in QUANTIZABLE:
            raise ValueError(
                f"ivf_cells needs a distance with a row-local gy map; "
                f"{distance!r} is not in {QUANTIZABLE}")
        if self.pq_m:
            from repro.core.pq import _check_pq_geometry

            if not self.ivf_cells:
                raise ValueError(
                    "pq_m needs a coarse quantizer: set ivf_cells > 0 "
                    "(the IVFADC composition, DESIGN.md §PQ)")
            _check_pq_geometry(self.dim, self.pq_m, self.pq_nbits)
        # Bumped only when the main segment's ROWS are replaced (build /
        # compact) — tombstones bump _version but must not trigger a replica
        # rebuild.
        self._main_epoch = 0
        self._main_vecs = np.zeros((0, dim), np.float32)
        self._main_ids = np.zeros((0,), np.int32)
        self._main_live = np.zeros((0,), bool)
        # Per-row namespace tags (DESIGN.md §17): int32, default tenant 0.
        # Data, not config — they ride mutations/compaction/snapshots next to
        # ids and never key a recompile.
        self._main_tenant = np.zeros((0,), np.int32)
        self._delta_vecs = np.zeros((0, dim), np.float32)
        self._delta_ids = np.zeros((0,), np.int32)
        self._delta_live = np.zeros((0,), bool)
        self._delta_tenant = np.zeros((0,), np.int32)
        self._delta_n = 0  # write head; rows past it are dead capacity
        self._loc: dict[int, tuple[str, int]] = {}  # id -> (segment, row)
        # Per-segment versions: a delta append must not re-upload the
        # (possibly huge) unchanged main segment to the device.
        self._version = {"main": 0, "delta": 0}
        self._dev_version = {"main": -1, "delta": -1}
        self._dev: dict = {}
        self._sharded_cache: dict = {}
        # Lifecycle tripwire (DESIGN.md §16): when True, a search that would
        # train IVF/PQ synchronously (enter core.kmeans.lloyd inside
        # _device_state) raises instead — the lifecycle layer guarantees
        # training happens in its background worker, never on the query path.
        self._forbid_sync_train = False

    # -- construction -------------------------------------------------------

    @classmethod
    def build(cls, ids, vectors, *, tenants=None, **kw) -> "RetrievalIndex":
        """Pack (ids, vectors) straight into the main segment.

        ``tenants``: optional per-row int32 namespace tags (DESIGN.md §17);
        None tags every row tenant 0 — the untenanted default.
        """
        vectors = np.asarray(vectors, np.float32)
        idx = cls(vectors.shape[1], **kw)
        ids = idx._check_ids(ids, vectors)
        idx._main_vecs = np.ascontiguousarray(vectors)
        idx._main_ids = ids.copy()
        idx._main_live = np.ones(len(ids), bool)
        idx._main_tenant = idx._check_tenants(tenants, len(ids))
        idx._loc = {int(i): ("main", r) for r, i in enumerate(ids)}
        idx._bump("main")
        idx._main_epoch += 1
        return idx

    # -- persistence (DESIGN.md §Persistence) --------------------------------

    def save(self, directory: str, *, include_replicas: bool = True,
             extra: dict | None = None, wal: bool = False) -> str:
        """Snapshot the full index state under ``directory``.

        Versioned, atomic, integrity-stamped — see ``serving.snapshot``.
        ``include_replicas=False`` omits the scalar quantized-scan replicas
        (they are deterministic maps, rebuilt on load); trained IVF/PQ state
        is always included — that is the point of the snapshot.  ``extra``
        rides in the manifest verbatim (callers pin provenance there, e.g.
        the service's tower-params fingerprint).  ``wal=True`` stamps the
        journal as a verified PREFIX so a ``lifecycle.WalWriter`` can extend
        it in place (see ``serving.snapshot``).
        """
        from repro.serving.snapshot import save_index

        return save_index(self, directory, include_replicas=include_replicas,
                          extra=extra, wal=wal)

    @classmethod
    def restore(cls, directory: str, *, mesh=None, db_axis: str = "model",
                query_axis: str = "data",
                impl: str | None = None) -> "RetrievalIndex":
        """Rebuild an index from a snapshot with ZERO training work.

        The snapshot's config/shape signature is hard-checked (a mismatch
        raises ``serving.snapshot.SnapshotError``, never a mis-scanning
        index); searches on the restored index are bit-identical to the
        source's.  ``mesh`` is runtime state and passed here, not restored.
        """
        from repro.serving.snapshot import restore_index

        return restore_index(directory, mesh=mesh, db_axis=db_axis,
                             query_axis=query_axis, impl=impl)

    def _check_ids(self, ids, vectors) -> np.ndarray:
        ids = np.asarray(ids, np.int64)
        assert vectors.shape == (len(ids), self.dim), (vectors.shape, len(ids))
        assert (ids >= 0).all() and (ids < 2**31).all(), "ids must fit int32"
        assert len(np.unique(ids)) == len(ids), "duplicate ids in one call"
        return ids.astype(np.int32)

    @staticmethod
    def _check_tenants(tenants, n: int) -> np.ndarray:
        if tenants is None:
            return np.zeros((n,), np.int32)
        tenants = np.asarray(tenants, np.int64)
        assert tenants.shape == (n,), (tenants.shape, n)
        assert (tenants >= 0).all() and (tenants < 2**31).all(), \
            "tenant tags must fit int32"
        return tenants.astype(np.int32)

    # -- introspection ------------------------------------------------------

    def __len__(self) -> int:
        return len(self._loc)

    def __contains__(self, item_id: int) -> bool:
        return int(item_id) in self._loc

    @property
    def n_dead(self) -> int:
        """Tombstoned + unfilled-capacity rows (wasted score work until compact)."""
        return self._dead_main() + self._dead_delta()

    def _dead_main(self) -> int:
        return int(len(self._main_live) - self._main_live.sum())

    def _dead_delta(self) -> int:
        return int(len(self._delta_live) - self._delta_live.sum())

    # -- mutation -----------------------------------------------------------

    def insert(self, ids, vectors, *, tenants=None) -> None:
        """Append new rows; error on an id that already exists (use upsert)."""
        vectors = np.asarray(vectors, np.float32)
        ids = self._check_ids(ids, vectors)
        for i in ids:
            if int(i) in self._loc:
                raise KeyError(f"id {int(i)} already indexed (use upsert)")
        self._append_delta(ids, vectors, self._check_tenants(tenants, len(ids)))

    def upsert(self, ids, vectors, *, tenants=None) -> None:
        """Insert-or-replace: an existing id is tombstoned, then re-appended."""
        vectors = np.asarray(vectors, np.float32)
        ids = self._check_ids(ids, vectors)
        for i in ids:
            self._tombstone(int(i), missing_ok=True)
        self._append_delta(ids, vectors, self._check_tenants(tenants, len(ids)))

    def delete(self, ids) -> int:
        """Tombstone ids; returns how many existed."""
        n = 0
        for i in np.asarray(ids).ravel():
            n += self._tombstone(int(i), missing_ok=True)
        return n

    def _tombstone(self, item_id: int, *, missing_ok: bool) -> int:
        loc = self._loc.pop(item_id, None)
        if loc is None:
            if missing_ok:
                return 0
            raise KeyError(item_id)
        seg, row = loc
        (self._main_live if seg == "main" else self._delta_live)[row] = False
        self._bump(seg)
        return 1

    def _append_delta(self, ids: np.ndarray, vectors: np.ndarray,
                      tenants: np.ndarray | None = None) -> None:
        if tenants is None:
            tenants = np.zeros((len(ids),), np.int32)
        need = self._delta_n + len(ids)
        if need > len(self._delta_vecs):
            cap = max(_MIN_DELTA_CAP, T.next_pow2(need))
            grown = np.zeros((cap, self.dim), np.float32)
            grown[: self._delta_n] = self._delta_vecs[: self._delta_n]
            self._delta_vecs = grown
            for name in ("_delta_ids", "_delta_live", "_delta_tenant"):
                old = getattr(self, name)
                fresh = np.zeros((cap,), old.dtype)
                fresh[: self._delta_n] = old[: self._delta_n]
                setattr(self, name, fresh)
        r0 = self._delta_n
        self._delta_vecs[r0 : r0 + len(ids)] = vectors
        self._delta_ids[r0 : r0 + len(ids)] = ids
        self._delta_live[r0 : r0 + len(ids)] = True
        self._delta_tenant[r0 : r0 + len(ids)] = tenants
        for off, i in enumerate(ids):
            self._loc[int(i)] = ("delta", r0 + off)
        self._delta_n = r0 + len(ids)
        self._bump("delta")

    def _live_rows(self) -> tuple[np.ndarray, np.ndarray]:
        """Live (vecs, ids) in compact order: main rows, then delta rows.

        This IS the row order ``compact()`` packs — the lifecycle layer cuts
        its background-epoch training set with the same call, so a handoff
        index is bit-identical to a synchronous compact of the same state.
        """
        segs = [
            (self._main_vecs, self._main_ids, self._main_live),
            (self._delta_vecs[: self._delta_n], self._delta_ids[: self._delta_n],
             self._delta_live[: self._delta_n]),
        ]
        vecs = np.concatenate([v[m] for v, _, m in segs], axis=0)
        ids = np.concatenate([i[m] for _, i, m in segs], axis=0)
        return np.ascontiguousarray(vecs), ids

    def _live_tenants(self) -> np.ndarray:
        """Live tenant tags in the exact ``_live_rows`` order (DESIGN.md §17)."""
        return np.concatenate([
            self._main_tenant[self._main_live],
            self._delta_tenant[: self._delta_n][
                self._delta_live[: self._delta_n]],
        ])

    def config_kwargs(self) -> dict:
        """Constructor kwargs reproducing this index's search config.

        ``RetrievalIndex(self.dim, **idx.config_kwargs())`` scans identically
        — the lifecycle layer builds each background epoch with exactly this.
        (Runtime state — mesh, axes — is the caller's to thread through.)
        """
        return {"distance": self.distance, "impl": self.impl,
                "scan_dtype": self.scan_dtype, "overfetch": self.overfetch,
                "ivf_cells": self.ivf_cells, "nprobe": self.nprobe,
                "pq_m": self.pq_m, "pq_nbits": self.pq_nbits}

    def compact(self) -> None:
        """Re-pack live rows into a fresh immutable main segment.

        Clears every tombstone and the delta; on a mesh this is also the
        re-shard point (the new main is re-split over ``db_axis``).
        """
        vecs, ids = self._live_rows()
        tenants = self._live_tenants()
        self._main_vecs = vecs
        self._main_ids = ids
        self._main_live = np.ones(len(ids), bool)
        self._main_tenant = tenants
        self._delta_vecs = np.zeros((0, self.dim), np.float32)
        self._delta_ids = np.zeros((0,), np.int32)
        self._delta_live = np.zeros((0,), bool)
        self._delta_tenant = np.zeros((0,), np.int32)
        self._delta_n = 0
        self._loc = {int(i): ("main", r) for r, i in enumerate(ids)}
        self._bump("main")
        self._bump("delta")
        self._main_epoch += 1  # replica rebuild point (DESIGN.md §Quantized)

    def _bump(self, seg: str) -> None:
        self._version[seg] += 1

    # -- search -------------------------------------------------------------

    def _device_state(self) -> dict:
        for seg in ("main", "delta"):
            if self._dev_version[seg] != self._version[seg]:
                vecs, live, ids = {
                    "main": (self._main_vecs, self._main_live, self._main_ids),
                    "delta": (self._delta_vecs, self._delta_live, self._delta_ids),
                }[seg]
                self._dev[seg] = (jnp.asarray(vecs), jnp.asarray(live),
                                  jnp.asarray(ids))
                self._dev_version[seg] = self._version[seg]
        if self.scan_dtype != "float32" and self.mesh is None and \
                not self._use_ivf():
            # Quantized replica of the main rows: keyed on the row EPOCH, not
            # the version — tombstones must not trigger a requantize.  (The
            # mesh path keeps its own PADDED replica, ``main_padded_q``; the
            # IVF path quantizes its CELL-PACKED layout instead, below.)
            if self._dev_version.get("main_q") != self._main_epoch:
                self._dev["main_q"] = quantize_rows(
                    jnp.asarray(self._main_vecs), self.scan_dtype,
                    distance=self.distance)
                self._dev_version["main_q"] = self._main_epoch
        if self._use_ivf():
            # IVF structure (centroids + packing + packed replica): keyed on
            # the row EPOCH exactly like the quantized replica — build and
            # compact retrain/repack; tombstones never do (they ride the
            # live mask through the permutation at query time).
            if self._dev_version.get("main_ivf") != self._main_epoch:
                if self._forbid_sync_train:
                    raise RuntimeError(
                        f"synchronous IVF/PQ training tripwire: epoch "
                        f"{self._main_epoch} has no trained structure and "
                        f"_forbid_sync_train is set — the lifecycle layer "
                        f"must train it in the background worker "
                        f"(serving.lifecycle, DESIGN.md §16)")
                from repro.core.ivf import build_ivf

                self._dev["main_ivf"] = build_ivf(
                    self._main_vecs, self._effective_ncells(),
                    distance=self.distance, impl=self.impl,
                    seed=self._main_epoch)
                if self._use_pq():
                    # PQ replaces the scalar replica for the main scan:
                    # residual codebooks + codes of the PACKED rows, same
                    # epoch key (build/compact retrain; tombstones never).
                    from repro.core.pq import build_ivfpq

                    self._dev["main_pq"] = build_ivfpq(
                        self._main_vecs, self._dev["main_ivf"], self.pq_m,
                        nbits=self.pq_nbits, distance=self.distance,
                        impl=self.impl, seed=self._main_epoch)
                else:
                    # Scan replica of the PACKED rows — built for float32
                    # too: a None would make the jnp scan path re-derive the
                    # gy/hy replica (an O(S·d) full-corpus pass) inside
                    # every query batch instead of once per epoch.
                    self._dev["main_ivf_q"] = quantize_rows(
                        self._dev["main_ivf"].packed, self.scan_dtype,
                        distance=self.distance)
                self._dev_version["main_ivf"] = self._main_epoch
        return self._dev

    def _use_ivf(self) -> bool:
        return bool(self.ivf_cells) and self._effective_ncells() > 0

    def _use_pq(self) -> bool:
        # A codebook needs 2^nbits distinct init rows; a main segment below
        # that serves through the plain IVF scan instead (never a truncated
        # codebook — the LUT width is a compiled shape).
        return (bool(self.pq_m) and self._use_ivf()
                and len(self._main_vecs) >= 2 ** self.pq_nbits)

    def _effective_ncells(self) -> int:
        """``ivf_cells`` clamped so cells stay meaningfully populated.

        A cell under ~4 expected rows is pure coarse-quantizer overhead
        (centroid scan + padding) with nothing left to prune; tiny corpora
        degrade toward fewer cells rather than empty ones.  On a mesh the
        count rounds DOWN to a multiple of the db-axis size so cell blocks
        shard evenly; 0 means this main segment is too small for IVF at
        all (e.g. fewer than ~4·P rows) and the flat scan path serves it —
        never a quantizer with more cells than rows.
        """
        n = len(self._main_vecs)
        if n == 0:
            return 0
        ncells = max(1, min(self.ivf_cells, n // 4 or 1))
        if self.mesh is not None:
            P = int(self.mesh.shape[self.db_axis])
            ncells = (ncells // P) * P
        return ncells

    def effective_nprobe(self) -> int:
        """``nprobe`` clamped to the TRAINED cell count — the explicit policy.

        The trained count can undershoot ``ivf_cells`` (tiny corpora, mesh
        rounding — ``_effective_ncells``), so a config or restored snapshot
        whose ``nprobe`` exceeds it is legal and means "probe every cell":
        clamp, never raise.  Rationale: ``nprobe > ncells`` has exactly one
        sensible semantics (the exhaustive probe, exact with an fp32 scan),
        and a restore must not fail on a config a fresh ``build()`` with the
        same knobs would happily serve.  A non-positive ``nprobe`` stays a
        hard config error (``__init__`` asserts).  Pinned by
        tests/test_snapshot.py::test_restore_nprobe_above_trained_ncells.
        """
        if not self._use_ivf():
            return self.nprobe
        return min(self.nprobe, self._device_state()["main_ivf"].ncells)

    def shape_signature(self, k: int) -> tuple:
        """Everything that determines the compiled shapes of a k-search.

        Two searches with equal signatures (and equal padded batch) hit the
        same executables — the engine uses this to tell compile batches from
        steady-state ones.  Because tombstones are a mask, only the segment
        ROW COUNTS matter: main size (changes at compact) and delta capacity
        (pow2 doubling), never the number of dead rows.  With IVF the
        cell-packed size (ncells · cell_cap — ``cell_cap`` can move across
        epochs with the largest cell) joins the signature: it is a compiled
        shape of the scan.
        """
        del k  # fetch width is next_pow2(k), already part of the batch key
        packed = 0
        if self._use_ivf():
            if self._dev_version.get("main_ivf") == self._main_epoch:
                packed = int(self._dev["main_ivf"].packed.shape[0])
            else:
                # Not yet (re)built: a distinct per-epoch marker so the first
                # batch after a compact is conservatively tagged cold.
                packed = -(self._main_epoch + 1)
        return (len(self._main_vecs),
                len(self._delta_vecs) if self._delta_n else 0,
                packed)

    def search(self, queries, k: int, *, filter=None) -> SearchResult:
        """Exact k nearest live rows for each query row.

        Result width is exactly ``k``; rows beyond the live count carry
        +inf distance and id -1 (same convention as ``core.knn``).

        ``filter``: optional ``serving.filters.QueryFilter`` (DESIGN.md §17)
        — tenant isolation, allow-lists, per-query exclusions.  A None or
        trivially-true filter takes this exact code path (bit-identical to
        unfiltered search, pinned by tests/test_filters.py).
        """
        with accounting.span("state") as sp:
            before = dict(self._dev_version)
            dev = self._device_state()
            sp.set_metadata(rebuilt=int(self._dev_version != before))
        with accounting.span("dispatch"):
            q = jnp.asarray(queries, jnp.float32)
            assert q.ndim == 2 and q.shape[1] == self.dim, q.shape
            k = int(k)
            assert k >= 1
            if filter is not None:
                from repro.serving import filters as F

                f = F.normalize(filter, q.shape[0])
                if f is not None:
                    return self._search_filtered(q, k, f)
            k_out = T.next_pow2(k)

            sets = []
            if len(self._main_vecs):
                sets.append(self._main_candidates(q, k_out, dev))
            if self._delta_n:
                vecs, live, ids = dev["delta"]
                sets.append(_segment_candidates(
                    q, vecs, live, ids, k_out=k_out,
                    distance=self.distance, impl=self.impl))
            if not sets:
                m = q.shape[0]
                return SearchResult(jnp.full((m, k), T.POS_INF, jnp.float32),
                                    jnp.full((m, k), -1, jnp.int32))
            if len(sets) == 1:
                with jax.named_scope("knn.merge"):
                    vals, ids = T.finalize_topk(*sets[0], k)
                return SearchResult(vals, ids)
            (av, ai), (bv, bi) = sets
            vals, ids = _merge_candidates(av, ai, bv, bi, k=k)
            return SearchResult(vals, ids)

    # -- filtered search (DESIGN.md §17) -------------------------------------

    def _search_filtered(self, q, k: int, f) -> SearchResult:
        """Search under a canonical (non-trivial) ``QueryFilter``.

        Strategy: measure the filter's live selectivity ``s`` exactly on the
        host (cheap numpy counts — it drives a static compile-key choice),
        resolve ``mode`` ("auto" → pre when s < 0.5), and set the fetch
        width: always widened by the exclusion width E (so dropping E seen
        rows still leaves k exact survivors), and in post mode additionally
        by ~1/s (clamped, ``filters.widen``).  Row predicates become
        per-segment [m, n] bitmaps applied pre (inside the scan) or post
        (``_drop_disallowed``); exclusions are applied once, by EXTERNAL id,
        on the merged candidate set — uniform across scan families and the
        same mechanism the shard router uses (DESIGN.md §17).

        The mesh path always post-filters: the shard_map scorers take no
        per-query bitmap operand, but they return row-space indices before
        externalization, which is exactly the post-filter hook.
        """
        from repro.serving import filters as F

        m = q.shape[0]
        dev = self._device_state()
        E = F.exclusion_width(f)
        s = F.selectivity(
            f,
            live=np.concatenate([self._main_live,
                                 self._delta_live[: self._delta_n]]),
            ids=np.concatenate([self._main_ids,
                                self._delta_ids[: self._delta_n]]),
            tenants=np.concatenate([self._main_tenant,
                                    self._delta_tenant[: self._delta_n]]))
        mode = F.resolve_mode(f.mode, s)
        if self.mesh is not None:
            mode = "post"
        k_fetch = k + E
        if mode == "post":
            k_fetch = max(k_fetch, F.widen(k, s) + E)
        if self._use_ivf() and self.impl == "fused" and len(self._main_vecs):
            # The scalar-prefetch kernels bound the fetch width by the cell
            # block; clamp the widening rather than trip their assert.
            k_fetch = max(k, min(k_fetch, int(dev["main_ivf"].cell_cap)))
        k_out = T.next_pow2(k_fetch)

        sets = []
        if len(self._main_vecs):
            allowed = self._allowed_bitmap("main", f, m)
            sets.append(self._main_candidates(q, k_out, dev, allowed=allowed,
                                              post=(mode == "post")))
        if self._delta_n:
            vecs, live, ids = dev["delta"]
            # The delta is small by construction: pre-filter its flat scan
            # regardless of mode (the bitmap operand costs nothing here).
            sets.append(_segment_candidates(
                q, vecs, live, ids, self._allowed_bitmap("delta", f, m),
                k_out=k_out, distance=self.distance, impl=self.impl))
        if not sets:
            return SearchResult(jnp.full((m, k), T.POS_INF, jnp.float32),
                                jnp.full((m, k), -1, jnp.int32))
        if len(sets) == 1:
            vals, ids_out = sets[0]
        else:
            (av, ai), (bv, bi) = sets
            vals, ids_out = T.merge_topk_sorted(av, ai, bv, bi)
        ex = None if f.exclude_ids is None else jnp.asarray(f.exclude_ids)
        vals, ids_out = _finalize_filtered(vals, ids_out, ex, k=k)
        return SearchResult(vals, ids_out)

    def _allowed_bitmap(self, seg: str, f, m: int):
        """[m, n_seg] bool row-predicate bitmap on device; None if all-true.

        Combines the batch-wide allow-list (host ``np.isin`` on external
        ids, broadcast over queries) with the per-query tenant equality
        (device compare against the version-keyed tenant column).  Dead and
        capacity rows may come out True — the live mask already kills them.
        """
        if f.tenant is None and f.allowed_ids is None:
            return None
        ids, tenants = {
            "main": (self._main_ids, self._main_tenant),
            "delta": (self._delta_ids, self._delta_tenant),
        }[seg]
        n = len(ids)
        ok = None
        if f.allowed_ids is not None:
            ok = jnp.broadcast_to(
                jnp.asarray(np.isin(ids, f.allowed_ids))[None, :], (m, n))
        if f.tenant is not None:
            key = seg + "_tenant"
            if self._dev_version.get(key) != self._version[seg]:
                self._dev[key] = jnp.asarray(tenants)
                self._dev_version[key] = self._version[seg]
            t_ok = self._dev[key][None, :] == jnp.asarray(f.tenant)[:, None]
            ok = t_ok if ok is None else jnp.logical_and(ok, t_ok)
        return ok

    # -- main-segment scoring (local or query-sharded) ----------------------

    def _main_candidates(self, q, k_out, dev, allowed=None, post=False):
        vecs, live, ids = dev["main"]
        if self.mesh is not None:
            return self._main_candidates_sharded(q, k_out, dev,
                                                 allowed=allowed)
        if self._use_pq():
            ivf = dev["main_ivf"]
            pq_cb, pq_codes = dev["main_pq"]
            return _segment_candidates_ivfpq(
                q, vecs, ivf, pq_cb, pq_codes, live, ids, allowed,
                k_out=k_out, nprobe=self.effective_nprobe(),
                overfetch=self.overfetch, distance=self.distance,
                impl=self.impl, post=post)
        if self._use_ivf():
            ivf = dev["main_ivf"]
            return _segment_candidates_ivf(
                q, vecs, ivf, dev["main_ivf_q"], live, ids, allowed,
                k_out=k_out, nprobe=self.effective_nprobe(),
                overfetch=self.overfetch, distance=self.distance,
                impl=self.impl, post=post)
        if self.scan_dtype != "float32":
            return _segment_candidates_quantized(
                q, vecs, dev["main_q"], live, ids, allowed, k_out=k_out,
                overfetch=self.overfetch, distance=self.distance,
                impl=self.impl, post=post)
        return _segment_candidates(
            q, vecs, live, ids, allowed, k_out=k_out,
            distance=self.distance, impl=self.impl, post=post)

    def _main_candidates_sharded(self, q, k_out, dev, allowed=None):
        """Score main over the mesh: the paper's serving path + tombstones.

        The tombstone mask shards over ``db_axis`` next to the database, so
        dead rows are +inf BEFORE the butterfly merge — wire payload stays
        k per row, identical to a tombstone-free index.

        With a quantized ``scan_dtype`` each shard runs the two-stage scan +
        rescore on its slice of the cached padded replica, and the butterfly
        merge's value payload travels bf16 (``wire_dtype``) — the wire cost
        shrinks with the scan (DESIGN.md §Quantized).

        ``allowed`` ([m, n] bitmap, DESIGN.md §17) is always POST-filtered
        on mesh paths: the shard_map scorers take no per-query bitmap
        operand, but they hand back row-space indices right before
        externalization — exactly the post-filter hook
        (``_search_filtered`` widens ``k_out`` accordingly).
        """
        from repro.core import distributed as KD

        if self._use_pq():
            return self._main_candidates_sharded_ivfpq(q, k_out, dev, allowed)
        if self._use_ivf():
            return self._main_candidates_sharded_ivf(q, k_out, dev, allowed)
        quant = self.scan_dtype != "float32"
        _, _, ids = dev["main"]
        P_db = int(self.mesh.shape[self.db_axis])
        P_q = int(self.mesh.shape[self.query_axis])
        n = len(self._main_vecs)
        n_pad = n + (-n) % P_db
        # The maker closes over the query-time knobs (overfetch here;
        # nprobe too on the IVF paths), so they join the key — a caller
        # tuning idx.overfetch between searches must get a fresh builder,
        # not a silently stale closure (benchmarks/serving.py does this).
        key = (k_out, n_pad, self.mesh, self.overfetch)
        fn = self._sharded_cache.get(key)
        if fn is None:
            fn = KD.make_query_sharded(
                self.mesh, query_axis=self.query_axis, db_axis=self.db_axis,
                k=k_out, distance=self.distance, impl=self.impl,
                scan_dtype=self.scan_dtype, overfetch=self.overfetch,
                wire_dtype=jnp.bfloat16 if quant else None)
            self._sharded_cache[key] = fn
        # Padded main + mask are cached per main-segment version: re-padding
        # the whole corpus per query batch would be an O(n d) copy on the hot
        # path (the main segment only changes at build/compact/tombstone).
        # They are placed row-sharded over db_axis, as the scorer reads them,
        # so a search moves no corpus bytes between devices.
        if self._dev_version.get("main_padded") != self._version["main"]:
            rows = jax.sharding.NamedSharding(
                self.mesh, jax.sharding.PartitionSpec(self.db_axis))
            self._dev["main_padded"] = (
                jax.device_put(
                    np.pad(self._main_vecs, ((0, n_pad - n), (0, 0))), rows),
                jax.device_put(np.pad(self._main_live, (0, n_pad - n)), rows),
            )
            self._dev_version["main_padded"] = self._version["main"]
        db, live_p = self._dev["main_padded"]  # pad rows are dead
        db_q = None
        if quant:
            # Padded replica keyed on the row epoch (pad rows quantize to
            # zeros and are dead via ``live_p`` anyway).
            if self._dev_version.get("main_padded_q") != (self._main_epoch, n_pad):
                self._dev["main_padded_q"] = quantize_rows(
                    db, self.scan_dtype, distance=self.distance)
                self._dev_version["main_padded_q"] = (self._main_epoch, n_pad)
            db_q = self._dev["main_padded_q"]
        m = q.shape[0]
        m_pad = m + (-m) % P_q
        qp = jnp.pad(q, ((0, m_pad - m), (0, 0)))
        vals, idx = fn(qp, db, n, live_p, db_q)
        vals, idx = vals[:m], idx[:m]
        if allowed is not None:
            vals, idx = _drop_disallowed(vals, idx, allowed)
        return _externalize(vals, idx, ids, k_out)

    def _main_candidates_sharded_ivf(self, q, k_out, dev, allowed=None):
        """Mesh + IVF: cell blocks row-sharded, centroids replicated.

        The epoch-keyed IVF structure already rounds ``ncells`` to a
        multiple of the db-axis size (``_effective_ncells``), so the
        cell-packed array splits on cell boundaries for free; the tombstone
        mask rides through the permutation (keyed on the main VERSION — it
        flips at deletes without touching the epoch-keyed packing).
        """
        from repro.core import distributed as KD
        from repro.core.ivf import packed_live

        _, _, ids = dev["main"]
        ivf = dev["main_ivf"]
        quant = self.scan_dtype != "float32"
        key = ("ivf", k_out, ivf.packed.shape[0], ivf.ncells, self.mesh,
               self.nprobe, self.overfetch)
        fn = self._sharded_cache.get(key)
        if fn is None:
            fn = KD.make_ivf_query_sharded(
                self.mesh, query_axis=self.query_axis, db_axis=self.db_axis,
                k=k_out, nprobe=self.effective_nprobe(),
                cell_cap=ivf.cell_cap, distance=self.distance,
                impl=self.impl, scan_dtype=self.scan_dtype,
                overfetch=self.overfetch,
                wire_dtype=jnp.bfloat16 if quant else None)
            self._sharded_cache[key] = fn
        live_key = (self._version["main"], self._main_epoch)
        if self._dev_version.get("main_ivf_live") != live_key:
            self._dev["main_ivf_live"] = packed_live(
                ivf, jnp.asarray(self._main_live))
            self._dev_version["main_ivf_live"] = live_key
        P_q = int(self.mesh.shape[self.query_axis])
        m = q.shape[0]
        m_pad = m + (-m) % P_q
        qp = jnp.pad(q, ((0, m_pad - m), (0, 0)))
        vals, idx = fn(qp, ivf.centroids, ivf.packed, ivf.row_of_slot,
                       self._dev["main_ivf_live"], dev["main_ivf_q"])
        vals, idx = vals[:m], idx[:m]
        if allowed is not None:
            vals, idx = _drop_disallowed(vals, idx, allowed)
        return _externalize(vals, idx, ids, k_out)

    def _main_candidates_sharded_ivfpq(self, q, k_out, dev, allowed=None):
        """Mesh + IVF-PQ: code blocks row-sharded, codebook replicated.

        Identical sharding story to ``_main_candidates_sharded_ivf`` —
        ``_effective_ncells`` already rounds cell count to the db-axis size,
        so the uint8 code rows split on cell boundaries next to the fp32
        packed rows (the rescore operand); the tombstone mask rides the
        permutation keyed on the main VERSION.
        """
        from repro.core import distributed as KD
        from repro.core.ivf import packed_live

        _, _, ids = dev["main"]
        ivf = dev["main_ivf"]
        pq_cb, pq_codes = dev["main_pq"]
        key = ("ivfpq", k_out, ivf.packed.shape[0], ivf.ncells, self.mesh,
               self.nprobe, self.overfetch)
        fn = self._sharded_cache.get(key)
        if fn is None:
            fn = KD.make_ivfpq_query_sharded(
                self.mesh, query_axis=self.query_axis, db_axis=self.db_axis,
                k=k_out, nprobe=self.effective_nprobe(),
                cell_cap=ivf.cell_cap, distance=self.distance,
                impl=self.impl, overfetch=self.overfetch,
                wire_dtype=jnp.bfloat16)
            self._sharded_cache[key] = fn
        live_key = (self._version["main"], self._main_epoch)
        if self._dev_version.get("main_ivf_live") != live_key:
            self._dev["main_ivf_live"] = packed_live(
                ivf, jnp.asarray(self._main_live))
            self._dev_version["main_ivf_live"] = live_key
        P_q = int(self.mesh.shape[self.query_axis])
        m = q.shape[0]
        m_pad = m + (-m) % P_q
        qp = jnp.pad(q, ((0, m_pad - m), (0, 0)))
        vals, idx = fn(qp, ivf.centroids, pq_cb, pq_codes, ivf.packed,
                       ivf.row_of_slot, self._dev["main_ivf_live"])
        vals, idx = vals[:m], idx[:m]
        if allowed is not None:
            vals, idx = _drop_disallowed(vals, idx, allowed)
        return _externalize(vals, idx, ids, k_out)
