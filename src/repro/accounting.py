"""Global accounting-mode flag + serving-side latency/throughput metering.

Unroll flag: XLA's cost_analysis counts while-loop bodies ONCE regardless of
trip count; under this flag every repro loop (model scans, the kNN ring)
compiles fully unrolled so FLOPs / bytes / collective counts are
trip-count-true.  Set only by the dry-run's accounting pass
(launch/dryrun.py --unroll).

ServingMeter: the per-batch latency/throughput account the query engine
(repro.serving.engine) reports — wall-clock per flushed batch, blocking on
device results, aggregated into p50/p99/mean latency and queries/sec.  The
first recorded batch after a (re)compile is tagged separately so steady-state
numbers are not polluted by compilation (EXPERIMENTS.md §Serving).

span: the serving path's profiler spans (``knn.<name>``), written into the
trace of an open ``jax.profiler`` session and costing a flag check otherwise.

scan_bytes_per_query: the analytic HBM-traffic model of the two-stage
quantized scan (DESIGN.md §Quantized) and its IVF cell-probed extension
(``ncells``/``nprobe`` — DESIGN.md §IVF) — what the precision and IVF
sweep benchmarks report next to measured qps so the bandwidth claims are
auditable.
"""
from __future__ import annotations

import time

import jax

_UNROLL = [False]

# itemsize of the database stream per scan dtype (core.distances.SCAN_DTYPES).
_SCAN_ITEMSIZE = {"float32": 4, "bfloat16": 2, "int8": 1}


def scan_bytes_per_query(n_rows: int, d: int, *, scan_dtype: str = "float32",
                         k: int = 10, overfetch: int = 4,
                         ncells: int | None = None,
                         nprobe: int | None = None,
                         pq_m: int | None = None,
                         pq_nbits: int = 8) -> dict:
    """Analytic HBM bytes one query's corpus scan moves (model, not a probe).

    The scan is bandwidth-bound in the database stream (the paper's whole
    premise); per query it reads
      * ``centroids``— the IVF coarse-quantizer pass: the [ncells, d] fp32
                      centroid table (zero for a flat scan),
      * ``scan``    — the database stream over the scanned rows: all
                      [n] rows for a flat scan, or the ``nprobe`` probed
                      cells' rows (nprobe · n/ncells — the average cell, the
                      honest expectation under a balanced quantizer) for the
                      IVF cell-probed scan (DESIGN.md §IVF); each row is
                      d bytes × the scan dtype's width, or ``pq_m`` uint8
                      code bytes when product-quantized (DESIGN.md §PQ —
                      codes are byte-stored for any ``pq_nbits`` ≤ 8;
                      sub-byte packing is an open item, ROADMAP),
      * ``epilogue``— the rank-1 terms over the scanned rows: ``hy`` fp32
                      always, plus the per-row int8 scales when scalar-
                      quantized (PQ folds everything else into the LUT),
      * ``rescore`` — stage 2's gather of K' = overfetch * next_pow2(k)
                      fp32 corpus rows (zero only for the flat fp32 scan,
                      which has no second stage; IVF/PQ always rescore).
    Query-side operands and the [*, K] outputs are O(d + k) per query —
    noise next to the database stream — and are omitted, identically for
    every configuration; that includes the PQ lookup tables, whose build
    reads the [2^nbits · d] fp32 codebook once per query BATCH and whose
    m·2^nbits-entry table lives in VMEM per query tile, amortizing to O(d)
    HBM bytes per query at serving batch sizes.
    """
    from repro.core.topk import next_pow2

    ivf = ncells is not None and ncells > 0
    pq = pq_m is not None and pq_m > 0
    centroids = ncells * d * 4 if ivf else 0
    if ivf:
        nprobe = min(ncells if nprobe is None else nprobe, ncells)
        scanned_rows = min(n_rows, -(-n_rows // ncells) * nprobe)
    else:
        scanned_rows = n_rows
    if pq:
        assert 1 <= pq_nbits <= 8, pq_nbits
        row_bytes = pq_m  # one byte per code, any nbits <= 8
        scaled = False
    else:
        row_bytes = d * _SCAN_ITEMSIZE[scan_dtype]
        scaled = scan_dtype == "int8"
    scan = scanned_rows * row_bytes
    epilogue = scanned_rows * 4 + (scanned_rows * 4 if scaled else 0)
    two_stage = ivf or pq or scan_dtype != "float32"
    rescore = (min(n_rows, overfetch * next_pow2(k)) * d * 4 if two_stage
               else 0)
    return {
        "centroids": centroids,
        "scan": scan,
        "epilogue": epilogue,
        "rescore": rescore,
        "total": centroids + scan + epilogue + rescore,
    }


def shard_bytes_per_query(n_rows: int, d: int, n_shards: int, *,
                          scan_dtype: str = "float32", k: int = 10,
                          overfetch: int = 4, ncells: int = 0,
                          nprobe: int | None = None, pq_m: int | None = None,
                          pq_nbits: int = 8,
                          wire_bytes_per_value: int = 2) -> dict:
    """Analytic per-shard traffic of the shard-routed path (DESIGN.md §13).

    Extends ``scan_bytes_per_query`` to a fleet of ``n_shards`` cell-range
    shards: the probe set (``nprobe`` distinct cells, uniform under a
    balanced quantizer) lands on an expected ``shards_dispatched`` =
    S · (1 − C(ncells−c, nprobe)/C(ncells, nprobe)) distinct shards
    (c = ncells/S cells per shard — the hypergeometric "shard owns none of
    the probes" complement).  Each dispatched shard then
      * reads the full replicated centroid table (every worker probes
        locally — the replicated-quantizer contract),
      * streams its share of the probed rows: the global IVF ``scan`` +
        ``epilogue`` bytes split over the dispatched shards,
      * rescores its own overfetch window (up to K' fp32 rows — per-shard,
        NOT divided: each worker overfetches independently),
    and ships one sorted [K = next_pow2(k)] run to the aggregator —
    ``wire_bytes_per_value`` (2 = the bf16 wire) + 4 id bytes per entry,
    the thin-aggregator ingest this architecture exists to keep thin.

    Returns per-shard component bytes plus fleet totals; the ``--shards``
    bench sweep reports this next to measured qps at small scale so the
    10⁸-row projections stay auditable.
    """
    import math

    from repro.core.topk import next_pow2

    assert n_shards >= 1 and ncells >= n_shards, (n_shards, ncells)
    whole = scan_bytes_per_query(
        n_rows, d, scan_dtype=scan_dtype, k=k, overfetch=overfetch,
        ncells=ncells, nprobe=nprobe, pq_m=pq_m, pq_nbits=pq_nbits)
    nprobe_eff = min(ncells if nprobe is None else nprobe, ncells)
    cells_per_shard = ncells / n_shards
    # P(one shard owns none of the nprobe distinct probed cells); guard the
    # exhaustive probe where the combinatorics degenerate to 0.
    free = ncells - cells_per_shard
    if nprobe_eff > free:
        p_none = 0.0
    else:
        p_none = math.exp(
            math.lgamma(free + 1) - math.lgamma(free - nprobe_eff + 1)
            - math.lgamma(ncells + 1) + math.lgamma(ncells - nprobe_eff + 1))
    dispatched = n_shards * (1.0 - p_none)
    K = next_pow2(k)
    per_shard = {
        "centroids": whole["centroids"],
        "scan": whole["scan"] / dispatched,
        "epilogue": whole["epilogue"] / dispatched,
        "rescore": whole["rescore"],  # each worker overfetches independently
        "wire": K * (wire_bytes_per_value + 4),
    }
    per_shard["total"] = sum(per_shard.values())
    return {
        "shards_dispatched": dispatched,
        "per_shard": per_shard,
        "aggregator_wire": dispatched * per_shard["wire"],
        "fleet_total": dispatched * per_shard["total"],
        "single_host_total": whole["total"],
    }


def rpc_bytes_per_batch(m: int, d: int, *, k: int = 10,
                        shards_dispatched: float = 1.0,
                        wire_bytes_per_value: int = 4) -> dict:
    """Analytic wire traffic of the RPC hop per search batch (DESIGN.md §15).

    The process-worker transport ships, per dispatched shard,
      * ``request``  — one QUERY frame: the fixed header + JSON meta
        overhead plus the [m, d] fp32 query block (the replicated-quantizer
        contract means the FULL batch goes to every dispatched shard — the
        worker probes locally and masks; queries are the one payload that
        scales with d),
      * ``reply``    — one RESULT frame: overhead plus the sorted [m, K]
        run, K = next_pow2(k) entries of ``wire_bytes_per_value`` value
        bytes (4 = fp32 exact, 2 = the bf16 wire ``aggregate_topk``
        already rounds to) + 4 id bytes.
    Frame overhead is taken from the transport's own framing (header +
    meta), so the model tracks the implementation rather than guessing.
    ``shards_dispatched`` (from ``shard_bytes_per_query``) scales both to
    the expected fan-out.  The asymmetry is the architecture's point: the
    request is O(m·d) but the reply is O(m·K) — the aggregator stays thin
    because workers never ship candidates, only merged runs.
    """
    from repro.core.topk import next_pow2
    from repro.serving.transport import frame_overhead_bytes

    assert m >= 1 and d >= 1 and shards_dispatched >= 0.0, (m, d)
    K = next_pow2(k)
    req_overhead = frame_overhead_bytes(
        {"seq": 10 ** 9, "k": int(k), "nprobe": 10 ** 4, "overfetch": 10 ** 4},
        n_arrays=1)
    rep_overhead = frame_overhead_bytes({"seq": 10 ** 9}, n_arrays=2)
    request = req_overhead + m * d * 4
    reply = rep_overhead + m * K * (wire_bytes_per_value + 4)
    return {
        "request": request,
        "reply": reply,
        "per_shard": request + reply,
        "fleet_request": shards_dispatched * request,
        "fleet_reply": shards_dispatched * reply,
        "fleet_total": shards_dispatched * (request + reply),
        "per_query": shards_dispatched * (request + reply) / m,
    }


def replicated_fleet_model(n_shards: int, replicas: int, *,
                           shards_dispatched: float,
                           fault_rate: float = 0.0) -> dict:
    """Availability/storage model of an R-replicated fleet (DESIGN.md §14).

    Under independent per-call worker failures at probability ``fault_rate``
    (the ``FaultPolicy.bernoulli`` harness), a dispatched shard is lost only
    when ALL ``replicas`` of it fail — probability ``f^R`` — so
      * ``p_shard_served``     = 1 − f^R,
      * ``p_query_complete``   = (1 − f^R)^dispatched (every dispatched
        shard of the query's probe set served — coverage 1.0),
      * ``expected_coverage``  ≈ 1 − f^R (each probed cell's owner is served
        independently in expectation),
      * ``storage_factor``     = R (replication is routing-level: R workers
        hold the same image, so fleet bytes scale by R while per-query scan
        bytes do NOT — exactly one replica per shard computes), and
      * ``dispatch_factor``    = 1/(1 − f) expected attempts per served call
        (geometric retries, capped by the router's attempt budget).

    This is the model the ``faults`` bench sweep prints next to measured
    coverage/recall so the availability claims stay auditable.
    """
    assert replicas >= 1 and 0.0 <= fault_rate < 1.0, (replicas, fault_rate)
    f = float(fault_rate)
    p_lost = f ** replicas
    return {
        "p_shard_served": 1.0 - p_lost,
        "p_query_complete": (1.0 - p_lost) ** shards_dispatched,
        "expected_coverage": 1.0 - p_lost,
        "storage_factor": float(replicas),
        "dispatch_factor": 1.0 / (1.0 - f),
    }


def set_unroll(value: bool) -> None:
    _UNROLL[0] = bool(value)


def unrolled() -> bool:
    return _UNROLL[0]


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """Host span ``knn.<name>`` with ``args`` as its trace stats.

    Recorded only while a profiler session is open (``jax.profiler.trace``,
    ``bench/run.py --trace 1``); otherwise entering it checks a flag.  An
    arg known only inside the span is added with ``.set_metadata(...)``.
    Never opened inside a jitted function: device stages are named with
    ``jax.named_scope`` instead.
    """
    return jax.profiler.TraceAnnotation("knn." + name, **args)


class ServingMeter:
    """Accumulates (batch_size, wall_seconds) samples from the query engine."""

    def __init__(self) -> None:
        self.reset()

    def reset(self) -> None:
        self._sizes: list[int] = []
        # (start, end) of each batch on the ``time.perf_counter`` clock.
        self._spans: list[tuple[float, float]] = []
        self._compile_spans: list[tuple[float, float]] = []
        # Per-worker dispatch accounting (the shard router's failover path):
        # worker key -> [calls, failures, total seconds, last error].
        self._shard: dict[str, list] = {}
        # Lifecycle accounting (DESIGN.md §16): WAL fsync-acked appends
        # [records, bytes, seconds] and background-retrain handoff times.
        self._wal: list = [0, 0, 0.0]
        self._handoffs: list[float] = []

    def record(self, batch_size: int, seconds: float, *,
               compile_batch: bool = False, end: float | None = None) -> None:
        """One batch of ``seconds`` ending at ``end`` (``time.perf_counter``
        clock; now when None)."""
        end = time.perf_counter() if end is None else float(end)
        span = (end - float(seconds), end)
        if compile_batch:
            self._compile_spans.append(span)
            return
        self._sizes.append(int(batch_size))
        self._spans.append(span)

    def record_wal(self, records: int, nbytes: int, seconds: float) -> None:
        """One fsync-acked WAL append (serving.lifecycle durability path)."""
        self._wal[0] += int(records)
        self._wal[1] += int(nbytes)
        self._wal[2] += float(seconds)

    def record_handoff(self, train_seconds: float) -> None:
        """One background-retrain epoch handoff completed off the query path."""
        self._handoffs.append(float(train_seconds))

    def record_shard_call(self, worker: str, seconds: float, *, ok: bool,
                          error: str | None = None) -> None:
        """One shard-dispatch attempt (including failed/retried ones)."""
        s = self._shard.setdefault(str(worker), [0, 0, 0.0, None])
        s[0] += 1
        s[2] += float(seconds)
        if not ok:
            s[1] += 1
            s[3] = error

    def shard_summary(self) -> dict:
        """Per-worker calls/failures/latency + fleet failover totals."""
        workers = {
            key: {"calls": c, "failures": f,
                  "error_rate": f / c if c else 0.0,
                  "mean_ms": secs / c * 1e3 if c else float("nan"),
                  "last_error": err}
            for key, (c, f, secs, err) in sorted(self._shard.items())
        }
        calls = sum(w["calls"] for w in workers.values())
        failures = sum(w["failures"] for w in workers.values())
        return {"workers": workers, "calls": calls, "failures": failures,
                "error_rate": failures / calls if calls else 0.0}

    @property
    def _secs(self) -> list[float]:
        return [e - s for s, e in self._spans]

    @property
    def n_batches(self) -> int:
        return len(self._spans)

    @property
    def n_queries(self) -> int:
        return sum(self._sizes)

    def latency_ms(self, pct: float) -> float:
        """Percentile (0-100) of per-batch wall latency, in milliseconds."""
        if not self._secs:
            return float("nan")
        xs = sorted(self._secs)
        # nearest-rank percentile: unambiguous at the tiny sample counts a
        # smoke run produces (no interpolation between two compile regimes)
        rank = min(len(xs) - 1, max(0, int(round(pct / 100.0 * (len(xs) - 1)))))
        return xs[rank] * 1e3

    def qps(self) -> float:
        """Metered queries over the wall time from the first metered batch's
        start to the last one's end: host time between batches counts,
        compile batches inside that time do not."""
        if not self._spans:
            return float("nan")
        t0 = min(s for s, _ in self._spans)
        t1 = max(e for _, e in self._spans)
        wall = (t1 - t0) - sum(e - s for s, e in self._compile_spans
                               if s >= t0 and e <= t1)
        return self.n_queries / wall if wall > 0 else float("nan")

    def summary(self) -> dict:
        out = {
            "batches": self.n_batches,
            "queries": self.n_queries,
            "qps": self.qps(),
            "p50_ms": self.latency_ms(50),
            "p99_ms": self.latency_ms(99),
            "mean_ms": (sum(self._secs) / len(self._secs) * 1e3
                        if self._secs else float("nan")),
            "compile_batches": len(self._compile_spans),
            "compile_s": sum(e - s for s, e in self._compile_spans),
        }
        if self._shard:
            sh = self.shard_summary()
            out["shard_calls"] = sh["calls"]
            out["shard_failures"] = sh["failures"]
        if self._wal[0]:
            out["wal_records"] = self._wal[0]
            out["wal_bytes"] = self._wal[1]
            out["wal_fsync_ms"] = self._wal[2] / self._wal[0] * 1e3
        if self._handoffs:
            out["handoffs"] = len(self._handoffs)
            out["handoff_train_s"] = sum(self._handoffs)
        return out
