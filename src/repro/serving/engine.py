"""Batched query engine over a RetrievalIndex.

Online traffic arrives as single queries with ragged batch sizes; XLA wants a
small closed set of shapes.  The engine sits between the two:

* **pow2 padding** — a flush of ``m`` queries runs at shape
  ``next_pow2(max(m, min_batch))`` (capped at ``max_batch``; larger flushes
  split into ``max_batch`` chunks).  Together with the index's pow2 fetch
  widths this bounds the executable count at log2(max_batch) per index epoch.  Padding
  rows are zero vectors whose results are sliced off — every row of the kNN
  computation is independent, so padding is invariant (checked by
  ``tests/test_serving.py::test_batch_padding_invariance``).
* **micro-batch queue** — ``submit()`` enqueues (request_id, vector) pairs;
  ``flush()`` drains them in one padded batch and returns per-request
  results.  This is the classic serving pattern (cf. faiss-serving /
  TF-Serving batching) in its smallest honest form; async arrival is the
  caller's concern.
* **metering** — every chunk is timed blocking-on-device and recorded in an
  ``accounting.ServingMeter`` (first batch at a fresh shape is tagged as a
  compile batch so steady-state p50/p99/qps stay clean).  The same interval
  is the ``knn.chunk`` span: with a profiler session open
  (``jax.profiler.trace``) the engine writes host spans ``knn.flush``
  (args ``flush_id``, ``rows``) ⊃ ``knn.chunk`` (``rows``, ``padded``, the
  pow2 bucket, and ``cold``, 1 at a new shape key) ⊃ ``knn.pad`` (host pad
  copy and shape-key bookkeeping), ``knn.state`` (the ``before_batch`` hook;
  inside the index, uploads and training, arg ``rebuilt``),
  ``knn.dispatch`` (query upload and program enqueue, in the index),
  ``knn.wait`` (``block_until_ready``) and ``knn.fetch`` (results leaving
  the search path).  One span a flush and one a chunk, none a query.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import accounting
from repro.core import topk as T
from repro.serving.index import SearchResult


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    k: int = 10
    min_batch: int = 8  # smallest compiled shape (tiny flushes pad up to it)
    max_batch: int = 1024  # largest compiled shape (bigger flushes chunk)

    def __post_init__(self):
        assert self.min_batch & (self.min_batch - 1) == 0, self.min_batch
        assert self.max_batch & (self.max_batch - 1) == 0, self.max_batch
        assert self.min_batch <= self.max_batch


class QueryEngine:
    """Batches queries onto anything with the index search surface.

    ``index`` is duck-typed: a ``RetrievalIndex``, or any object exposing
    ``search(q, k) -> SearchResult``, ``shape_signature(k) -> tuple`` and
    ``dim`` — ``serving.shards.ShardRouter`` plugs in here, so a shard fleet
    serves through the same padding/metering path as a local index.
    """

    def __init__(self, index, cfg: EngineConfig = EngineConfig(),
                 meter: accounting.ServingMeter | None = None):
        self.index = index
        self.cfg = cfg
        self.meter = meter if meter is not None else accounting.ServingMeter()
        # Keyed on request_id: re-submitting an id before flush REPLACES the
        # pending vector (latest wins, scored once) — a plain list would
        # score both and silently drop one result at the dict build.
        self._queue: dict[object, np.ndarray] = {}
        # (batch, k, index shape signature) keys already compiled.  Entries
        # whose MAIN component no longer matches the live packed main are
        # evicted — a compact that changes the row count strands them, so a
        # long-lived churn workload holds one main-epoch's keys instead of
        # one tuple per epoch forever.  Delta-capacity signatures are kept
        # for the live main: they legitimately RECUR (delta refills through
        # the same pow2 caps after every compact), and re-tagging a warm
        # recurrence as a compile batch would skew the steady-state stats.
        self._seen_shapes: set = set()
        self._live_main: int | None = None
        self._flushes = 0  # the ``flush_id`` of the next flush's span

    def rebind(self, index) -> None:
        """Point the engine at a replacement index (rebuild, restore, or a
        ``ShardRouter`` over restored shard images).

        Drops the compile-tracking state: the old index's shape-signature
        keys are meaningless against a new object, and keeping them would
        mis-tag the new index's first batches as warm (skewing steady-state
        p50/p99) or strand keys forever.  Pending queue entries survive —
        they are vectors, not index state.
        """
        assert index.dim == self.index.dim, (index.dim, self.index.dim)
        self.index = index
        self._seen_shapes = set()
        self._live_main = None

    # -- batched search -----------------------------------------------------

    def _bucket(self, m: int) -> int:
        return min(self.cfg.max_batch, T.next_pow2(max(m, self.cfg.min_batch)))

    def search(self, queries, k: int | None = None, *,
               filter=None) -> SearchResult:
        """Exact top-k for [m, d] queries, padded/chunked to engine shapes.

        ``filter``: optional ``serving.filters.QueryFilter`` (DESIGN.md §17).
        Per-query predicate rows (tenant tags, exclusion lists) are chunked
        and pow2-padded in lockstep with the query rows — pad rows get
        tenant 0 / no exclusions and their results are sliced off, so the
        batching layer stays invariant under filtering.
        """
        from repro.serving import filters as F

        k = self.cfg.k if k is None else int(k)
        # Batch-boundary hook: a lifecycle-managed index swaps a ready
        # background epoch in HERE, never mid-batch — the shape signature
        # read below then sees the post-swap index (DESIGN.md §16).
        hook = getattr(self.index, "before_batch", None)
        if hook is not None:
            with accounting.span("state"):
                hook()
        q = np.asarray(queries, np.float32)
        assert q.ndim == 2, q.shape
        if len(q) == 0:  # nothing to score, nothing to meter
            return SearchResult(jnp.zeros((0, k), jnp.float32),
                                jnp.zeros((0, k), jnp.int32))
        f = F.normalize(filter, len(q)) if filter is not None else None
        out_v, out_i, out_c, out_s = [], [], [], []
        for s in range(0, len(q), self.cfg.max_batch):
            chunk = q[s : s + self.cfg.max_batch]
            r = self._search_padded(chunk, k,
                                    F.slice_rows(f, s, s + len(chunk)))
            out_v.append(r.distances)
            out_i.append(r.ids)
            if r.coverage is not None:
                out_c.append(r.coverage)
            if r.shard_status is not None:
                out_s.append(r.shard_status)
        # Degraded-serving accounting rides along: per-query coverage
        # concatenates chunk-wise; per-shard status folds worst-wins.
        coverage = np.concatenate(out_c) if len(out_c) == len(out_v) else None
        status = None
        if out_s:
            from repro.serving.shards import merge_shard_status

            status = merge_shard_status(out_s)
        with accounting.span("fetch"):
            return SearchResult(jnp.concatenate(out_v),
                                jnp.concatenate(out_i), coverage=coverage,
                                shard_status=status)

    def _search_padded(self, chunk: np.ndarray, k: int,
                       f=None) -> SearchResult:
        from repro.serving import filters as F

        m = len(chunk)
        mp = self._bucket(m)
        # The meter times the same interval as the span, so an operator's
        # p50/p99 and the trace agree.
        t0 = time.perf_counter()
        with accounting.span("chunk", rows=m, padded=mp) as sp:
            with accounting.span("pad"):
                qp = np.zeros((mp, chunk.shape[1]), np.float32)
                qp[:m] = chunk
                # A shape is "cold" (compile expected) once per (batch, k,
                # index shape signature) — delta appends that stay inside
                # the current capacity/fetch buckets do NOT recompile and
                # stay steady-state.
                sig = self.index.shape_signature(k)
                if sig[0] != self._live_main:  # new packed main: keys stranded
                    self._seen_shapes = {s for s in self._seen_shapes
                                         if s[2][0] == sig[0]}
                    self._live_main = sig[0]
                # The filter's compiled-shape contribution: which predicates
                # exist, the execution mode, and the exclusion width (a
                # traced-array dim).
                fkey = None if f is None else (f.mode, f.tenant is not None,
                                               f.allowed_ids is not None,
                                               F.exclusion_width(f))
                shape_key = (mp, k, sig, fkey)
                cold = shape_key not in self._seen_shapes
                self._seen_shapes.add(shape_key)
            sp.set_metadata(cold=int(cold))
            if f is None:
                res = self.index.search(qp, k)
            else:
                res = self.index.search(qp, k, filter=F.pad_rows(f, mp))
            # Block on the array legs only: coverage is host numpy and
            # shard_status is plain python — neither has device futures.
            with accounting.span("wait"):
                jax.block_until_ready((res.distances, res.ids))
            with accounting.span("fetch"):
                cov = None if res.coverage is None else res.coverage[:m]
                out = SearchResult(res.distances[:m], res.ids[:m],
                                   coverage=cov,
                                   shard_status=res.shard_status)
        t1 = time.perf_counter()
        self.meter.record(m, t1 - t0, compile_batch=cold, end=t1)
        return out

    # -- micro-batch queue --------------------------------------------------

    def submit(self, request_id, vector) -> None:
        v = np.asarray(vector, np.float32).ravel()
        assert v.shape == (self.index.dim,), v.shape
        self._queue[request_id] = v

    @property
    def pending(self) -> int:
        return len(self._queue)

    def flush(self, k: int | None = None) -> dict:
        """Drain the queue in one padded batch; {request_id: (dists, ids)}."""
        if not self._queue:
            return {}
        reqs, vecs = zip(*self._queue.items())
        self._queue = {}
        self._flushes += 1
        with accounting.span("flush", flush_id=self._flushes, rows=len(reqs)):
            res = self.search(np.stack(vecs), k)
            with accounting.span("fetch"):
                dv = np.asarray(res.distances)
                di = np.asarray(res.ids)
                return {r: (dv[i], di[i]) for i, r in enumerate(reqs)}
