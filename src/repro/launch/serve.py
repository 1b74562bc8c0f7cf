"""Retrieval serving driver — thin CLI over ``repro.serving``.

Builds a two-tower model, embeds an item corpus into a RetrievalIndex, then
serves batched user queries through the QueryEngine, optionally exercising the
online index lifecycle (ingest into the delta segment, deletes, compaction)
while traffic flows:

  PYTHONPATH=src python -m repro.launch.serve --corpus 16384 --queries 64 \
      --batches 20 --k 10 --churn 256 --repeat-frac 0.5

Flags (see README.md "CLI reference"):
  --corpus N        item corpus size (embedded offline, packed main segment)
  --queries M       users per served batch
  --batches B       number of online batches (first is compile, excluded)
  --k K             neighbors per query
  --impl {jnp,fused}  segment scorer (fused = Pallas distance+select kernel)
  --scan-dtype {float32,bf16,int8}  two-stage quantized main-segment scan
                    (DESIGN.md §Quantized; float32 = exact, the default)
  --overfetch O     scan candidate multiple for the quantized path
  --ivf-cells C     IVF cell-probed main-segment scan: train C k-means cells
                    and probe only the nearest per query (DESIGN.md §IVF;
                    0 = flat scan, the default)
  --nprobe P        cells probed per query (>= C probes everything = exact
                    with a float32 scan)
  --pq-m M          product-quantized ADC main-segment scan: M uint8 codes
                    per row instead of d coordinates (DESIGN.md §PQ; needs
                    --ivf-cells > 0 — the IVFADC recipe; 0 = off)
  --pq-nbits B      bits per PQ code (codebook = 2^B words per subspace)
  --churn C         items upserted into the delta segment per batch (0 = off)
  --compact-every E compact() after every E batches (0 = never)
  --repeat-frac F   fraction of each batch drawn from repeat users (cache hits)
  --cache N         user embedding cache capacity (0 disables)
  --mesh            shard the main segment over the host mesh (query-sharded
                    butterfly scoring — the paper's multi-device serving path)
  --shards S        shard-routed serving (DESIGN.md §13): cut the built index
                    into S cell-range shard images, restore them into
                    ShardWorkers and serve through the probe-set router +
                    butterfly aggregator (needs --ivf-cells > 0; shard
                    images land under --snapshot-dir or a temp dir)
  --replicas R      fault-tolerance tier (DESIGN.md §14): restore each shard
                    image into R independent workers with per-query failover
                    and per-worker health tracking (needs --shards)
  --fault-rate F    chaos demo: wrap every worker in a seeded Bernoulli
                    FaultPolicy injecting failures/latency/garbage at rate F
                    and report coverage + health afterwards (needs --shards)
  --degraded P      "refuse" (default: a lost shard raises the structured
                    error) | "partial" (serve survivors, report coverage)
  --workers B       "inproc" (default: the restored fleet lives in this
                    process) | "proc" (DESIGN.md §15: one supervised OS
                    process per replica behind the RPC transport — real
                    crash detection, heartbeats, snapshot respawn; needs
                    --shards)
  --heartbeat-s S   idle seconds before the supervisor PING-probes a proc
                    worker (0 disables; needs --workers proc)
  --queue-depth N   per-worker bound on abandoned in-flight requests before
                    calls fail over with BackpressureError (needs
                    --workers proc)
  --snapshot-dir D  persist the index under D after the corpus build
                    (DESIGN.md §Persistence: versioned, atomic, CRC-stamped)
  --restore         cold-start from the --snapshot-dir snapshot instead of
                    re-embedding + retraining (prints the wall-clock saved)
  --wal             crash-safe lifecycle (DESIGN.md §16): journal every churn
                    mutation fsync-acked into --snapshot-dir between
                    compacts, train post-compact epochs in the background,
                    and finish with a simulated crash-restart (torn journal
                    tail) + recovery-stats report; with --restore the run
                    starts by recovering snapshot + WAL instead of
                    re-embedding (needs --snapshot-dir; excludes
                    --shards/--mesh)
  --delta-budget N  admission control: mutations that would grow the delta
                    past N rows raise BackpressureError — the driver then
                    compacts and retries (0 = unbounded; needs --wal)
  --sync-compact    disable background retrain: compact() blocks through
                    repack + IVF/PQ training + full save (the latency-cliff
                    baseline the lifecycle bench compares against)
  --filter-mode M   filtered-search execution policy for ``recommend()``
                    calls that carry a QueryFilter (DESIGN.md §17):
                    "auto" (default: selectivity-driven pre/post choice) |
                    "pre" (mask inside the scan) | "post" (widened fetch,
                    filter after)
  --seed S
"""
from __future__ import annotations

import argparse


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--corpus", type=int, default=16384)
    ap.add_argument("--queries", type=int, default=64, help="queries per batch")
    ap.add_argument("--batches", type=int, default=20)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--impl", choices=("jnp", "fused"), default="jnp")
    ap.add_argument("--scan-dtype", default="float32",
                    choices=("float32", "fp32", "bf16", "bfloat16", "int8"))
    ap.add_argument("--overfetch", type=int, default=4)
    ap.add_argument("--ivf-cells", type=int, default=0,
                    help="IVF cells for the main-segment scan (0 = flat)")
    ap.add_argument("--nprobe", type=int, default=8,
                    help="IVF cells probed per query")
    ap.add_argument("--pq-m", type=int, default=0,
                    help="PQ codes per row for the main-segment ADC scan "
                         "(0 = off; needs --ivf-cells)")
    ap.add_argument("--pq-nbits", type=int, default=8,
                    help="bits per PQ code (2^nbits codewords per subspace)")
    ap.add_argument("--churn", type=int, default=0,
                    help="items upserted into the delta per batch")
    ap.add_argument("--compact-every", type=int, default=0)
    ap.add_argument("--repeat-frac", type=float, default=0.0,
                    help="fraction of repeat users per batch (cache hits)")
    ap.add_argument("--cache", type=int, default=4096)
    ap.add_argument("--mesh", action="store_true",
                    help="shard the main segment over the host mesh and score "
                         "it with the query-sharded butterfly path")
    ap.add_argument("--shards", type=int, default=0,
                    help="cut the index into this many cell-range shard "
                         "images and serve through the probe-set router "
                         "(DESIGN.md §13; needs --ivf-cells > 0; 0 = off)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="workers per shard cell range with per-query "
                         "failover (DESIGN.md §14; needs --shards)")
    ap.add_argument("--fault-rate", type=float, default=0.0,
                    help="inject seeded worker faults at this per-call rate "
                         "(chaos demo; needs --shards)")
    ap.add_argument("--degraded", choices=("refuse", "partial"),
                    default="refuse",
                    help="what a shard with all replicas dead costs: refuse "
                         "= structured error, partial = serve survivors "
                         "with per-query coverage")
    ap.add_argument("--workers", choices=("inproc", "proc"),
                    default="inproc",
                    help="worker backend (DESIGN.md §15): inproc = restored "
                         "fleet in this process; proc = one supervised OS "
                         "process per replica over the RPC transport "
                         "(needs --shards)")
    ap.add_argument("--heartbeat-s", type=float, default=5.0,
                    help="idle seconds before a proc worker is PING-probed "
                         "(0 = no heartbeat; needs --workers proc)")
    ap.add_argument("--queue-depth", type=int, default=8,
                    help="per-proc-worker in-flight request bound before "
                         "BackpressureError (needs --workers proc)")
    ap.add_argument("--snapshot-dir", default=None,
                    help="persist the built index here (DESIGN.md §Persistence)")
    ap.add_argument("--restore", action="store_true",
                    help="cold-start from --snapshot-dir instead of "
                         "re-embedding + retraining")
    ap.add_argument("--wal", action="store_true",
                    help="crash-safe lifecycle: fsync-acked journaling + "
                         "background epoch handoff + simulated crash-restart "
                         "report (DESIGN.md §16; needs --snapshot-dir)")
    ap.add_argument("--delta-budget", type=int, default=0,
                    help="max delta rows before mutations raise "
                         "BackpressureError (0 = unbounded; needs --wal)")
    ap.add_argument("--sync-compact", action="store_true",
                    help="block compact() through retrain + full save "
                         "instead of background handoff (needs --wal)")
    ap.add_argument("--filter-mode", choices=("auto", "pre", "post"),
                    default="auto",
                    help="execution policy for filtered recommend() calls "
                         "(DESIGN.md §17): auto = selectivity-driven, "
                         "pre = mask in scan, post = widened fetch + filter")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if args.restore and not args.snapshot_dir:
        ap.error("--restore needs --snapshot-dir")
    if args.wal and not args.snapshot_dir:
        ap.error("--wal needs --snapshot-dir (the journal lives inside the "
                 "snapshot)")
    if args.wal and (args.shards or args.mesh):
        ap.error("--wal is the single-host lifecycle tier; --shards/--mesh "
                 "have their own persistence (DESIGN.md §13-§15)")
    if (args.delta_budget or args.sync_compact) and not args.wal:
        ap.error("--delta-budget/--sync-compact need --wal")
    if args.delta_budget < 0:
        ap.error("--delta-budget must be >= 0")
    if args.shards:
        if not args.ivf_cells:
            ap.error("--shards needs --ivf-cells > 0 (cells are the "
                     "partition unit)")
        if args.mesh:
            ap.error("--shards and --mesh are alternative scale-out paths; "
                     "pick one")
        if args.churn or args.compact_every:
            ap.error("--shards serves immutable shard images; delta churn "
                     "is a single-host path (--churn/--compact-every)")
    if not args.shards and (args.replicas != 1 or args.fault_rate):
        ap.error("--replicas/--fault-rate need --shards (they are fleet "
                 "properties)")
    if args.workers == "proc" and not args.shards:
        ap.error("--workers proc needs --shards (process workers serve "
                 "shard images)")
    if args.queue_depth < 1:
        ap.error("--queue-depth must be >= 1")
    if args.heartbeat_s < 0:
        ap.error("--heartbeat-s must be >= 0")
    if args.replicas < 1:
        ap.error("--replicas must be >= 1")
    if not 0.0 <= args.fault_rate < 1.0:
        ap.error("--fault-rate must be in [0, 1)")

    import jax
    import numpy as np

    from repro.compile_cache import configure
    from repro.configs import registry as REG

    configure()
    from repro.configs.two_tower import serving_defaults
    from repro.models.nn import split_params
    from repro.serving import ServiceConfig, TwoTowerRetrievalService

    arch = REG.get("two-tower-retrieval")
    cfg = arch.smoke_config()
    params = arch.init_params(jax.random.PRNGKey(args.seed), cfg)
    values, _ = split_params(params)

    from repro.core.topk import next_pow2

    defaults = serving_defaults()
    defaults.update(k=args.k, impl=args.impl, cache_capacity=args.cache,
                    max_batch=next_pow2(max(64, args.queries)),
                    scan_dtype=args.scan_dtype, overfetch=args.overfetch,
                    ivf_cells=args.ivf_cells, nprobe=args.nprobe,
                    pq_m=args.pq_m, pq_nbits=args.pq_nbits,
                    snapshot_dir=args.snapshot_dir,
                    replicas=args.replicas, degraded=args.degraded,
                    workers=args.workers, heartbeat_s=args.heartbeat_s,
                    queue_depth=args.queue_depth,
                    wal=args.wal, delta_budget=args.delta_budget,
                    background_retrain=not args.sync_compact,
                    filter_mode=args.filter_mode)
    mesh = None
    if args.mesh:
        from repro.launch.mesh import make_host_mesh

        mesh = make_host_mesh()
        print(f"[serve] query-sharded over mesh {dict(mesh.shape)}")
    svc = TwoTowerRetrievalService(values, cfg, ServiceConfig(**defaults),
                                   mesh=mesh)

    # Offline: embed + pack the corpus — or restore a snapshot and skip the
    # whole pass (the cold-start path, DESIGN.md §Persistence).
    import time

    rng = np.random.default_rng(args.seed)
    item_lim = min(cfg.i_sizes())
    user_lim = min(cfg.u_sizes())
    corpus_fields = rng.integers(
        0, item_lim, size=(args.corpus, cfg.n_item_fields)).astype(np.int32)
    if args.restore and args.wal:
        t0 = time.perf_counter()
        rec = svc.recover_lifecycle()
        print(f"[serve] recovered {len(svc.lifecycle)} rows from snapshot + "
              f"WAL at {args.snapshot_dir} in {time.perf_counter() - t0:.2f}s")
        print(f"[serve] recovery: {rec.tail_records} acked tail record(s) "
              f"replayed past the {rec.stamped_bytes}-byte stamp, "
              f"{rec.torn_bytes} torn in-flight byte(s) dropped")
    elif args.restore:
        t0 = time.perf_counter()
        svc.restore_index()
        print(f"[serve] restored {len(svc.index)} x {svc.index.dim} from "
              f"{args.snapshot_dir} in {time.perf_counter() - t0:.2f}s "
              f"(no embedding, no training)")
    else:
        t0 = time.perf_counter()
        svc.build_corpus(np.arange(args.corpus), corpus_fields)
        t_build = time.perf_counter() - t0
        print(f"[serve] corpus embedded + indexed: {len(svc.index)} x "
              f"{svc.index.dim} in {t_build:.2f}s")
        if args.wal:
            # The lifecycle's attach writes the full WAL image itself: from
            # here every churn mutation is one fsync-acked journal record,
            # and save() between compacts is a manifest-only checkpoint.
            t0 = time.perf_counter()
            svc.enable_lifecycle()
            print(f"[serve] lifecycle armed -> {args.snapshot_dir} in "
                  f"{time.perf_counter() - t0:.2f}s (WAL journaling, "
                  f"{'sync' if args.sync_compact else 'background'} "
                  f"compaction, delta budget "
                  f"{args.delta_budget or 'unbounded'})")
        elif args.snapshot_dir:
            # save() finalizes any lazily-pending IVF/PQ training first, so
            # this wall clock includes it — which is exactly the work a
            # later --restore run skips (benchmarks.serving --cold-start
            # separates the two).
            t0 = time.perf_counter()
            svc.save_index()
            print(f"[serve] snapshot -> {args.snapshot_dir} in "
                  f"{time.perf_counter() - t0:.2f}s (--restore skips the "
                  f"embedding pass and all IVF/PQ training)")

    if args.shards:
        # Shard-routed serving (DESIGN.md §13): cut cell-range images, restore
        # each into a self-contained ShardWorker, rebind the engine onto the
        # probe-set router.  In production each image restores in its own
        # worker process (tests/test_shards.py proves that path); one process
        # hosting the whole fleet exercises identical code.
        import tempfile

        shard_root = (args.snapshot_dir + "-shards" if args.snapshot_dir
                      else tempfile.mkdtemp(prefix="repro-shards-"))
        t0 = time.perf_counter()
        paths = svc.save_shards(shard_root, args.shards)
        svc.restore_shards(shard_root)
        r = svc.router
        backend = "proc" if r.supervisor is not None else "inproc"
        print(f"[serve] {len(paths)} shard images -> {shard_root} + routed "
              f"restore in {time.perf_counter() - t0:.2f}s (zero retraining; "
              f"{r.n_replicas} replica(s)/shard, workers={backend!r}, "
              f"degraded={r.degraded!r})")
        for w in r.workers:
            pid = f" pid={w.pid}" if backend == "proc" else ""
            print(f"[serve]   {w.key}: cells "
                  f"[{w.spec.cell_lo}, {w.spec.cell_hi}) "
                  f"{w.n_slots} slots, {w.n_live} live rows{pid}")
        if args.fault_rate:
            # Chaos demo (DESIGN.md §14): every worker behind a seeded
            # Bernoulli FaultPolicy — failures/latency/garbage at the given
            # per-call rate; the router fails over / degrades through them.
            from repro.serving import inject_faults

            svc.router = inject_faults(r, rate=args.fault_rate,
                                       seed=args.seed)
            svc.engine.rebind(svc.router)
            print(f"[serve] fault injection armed: rate={args.fault_rate} "
                  f"seed={args.seed}")

    # Online: batches of user queries with optional churn/compaction.
    n_users = 4 * args.queries
    user_pool = rng.integers(
        0, user_lim, size=(n_users, cfg.n_user_fields)).astype(np.int32)
    next_item = args.corpus
    refused = 0
    backpressured = 0
    for b in range(args.batches):
        n_rep = int(args.queries * args.repeat_frac)
        keys = np.concatenate([
            rng.integers(0, n_users, size=n_rep),  # repeat visitors
            np.arange(args.queries - n_rep) + n_users + b * args.queries,
        ])
        fields = np.concatenate([
            user_pool[keys[:n_rep]],
            rng.integers(0, user_lim,
                         size=(args.queries - n_rep, cfg.n_user_fields)),
        ]).astype(np.int32)
        if args.fault_rate:
            # Under degraded="refuse" a lost shard refuses the whole batch —
            # that IS the contract; count it instead of crashing the demo.
            from repro.serving import MissingShardError

            try:
                ids, scores = svc.recommend(keys, fields)
            except MissingShardError as e:
                refused += 1
                print(f"[serve] batch {b} refused: shards "
                      f"{list(e.shard_ids)} unavailable "
                      f"({len(e.attempts)} failover attempts)")
                continue
        else:
            ids, scores = svc.recommend(keys, fields)

        if args.churn:
            churn_ids = np.arange(next_item, next_item + args.churn)
            next_item += args.churn
            churn_fields = rng.integers(
                0, item_lim,
                size=(args.churn, cfg.n_item_fields)).astype(np.int32)
            if args.wal:
                from repro.serving import BackpressureError

                try:
                    svc.ingest_items(churn_ids, churn_fields)
                except BackpressureError:
                    # Admission control fired: fold the delta down (blocking
                    # — the budget says we MUST NOT grow it) and retry once.
                    backpressured += 1
                    svc.compact(wait=True)
                    svc.ingest_items(churn_ids, churn_fields)
                # Incremental save between compacts: manifest-only — the
                # acked records are already durable, this just folds them
                # into the strictly-verified prefix.
                if not svc.lifecycle.handoff_pending:
                    svc.lifecycle.checkpoint()
            else:
                svc.ingest_items(churn_ids, churn_fields)
        if args.compact_every and (b + 1) % args.compact_every == 0:
            svc.compact()

    st = svc.stats()
    s, e = st["serving"], st["engine"]
    print(f"[serve] {s['batches']} steady-state batches of {args.queries} "
          f"queries, k={args.k} (+{s['compile_batches']} compile batches, "
          f"{s['compile_s']:.2f}s)")
    print(f"[serve] end-to-end ms (embed+scan): p50={s['p50_ms']:.2f} "
          f"p99={s['p99_ms']:.2f} mean={s['mean_ms']:.2f}  "
          f"throughput={s['qps']:.0f} qps")
    print(f"[serve] kNN scan only ms: p50={e['p50_ms']:.2f} "
          f"p99={e['p99_ms']:.2f}")
    print(f"[serve] index: {st['index_rows']} rows, {st['index_dead']} dead; "
          f"cache hit-rate={st['cache']['hit_rate']:.2f} "
          f"({st['cache']['hits']}/{st['cache']['hits'] + st['cache']['misses']})")
    print(f"[serve] top-1 sample: ids={ids[0, :5]} score={scores[0, :5].round(3)}")
    fleet = st.get("fleet")
    if fleet is not None and (args.fault_rate or args.replicas > 1):
        d = fleet["dispatch"]
        print(f"[serve] fleet: {fleet['n_shards']} shards x "
              f"{fleet['replicas']} replicas, degraded={fleet['degraded']!r}"
              f"; dispatches={d['calls']} failures={d['failures']} "
              f"(error rate {d['error_rate']:.3f}); refused batches="
              f"{refused}")
        for key, h in fleet["health"].items():
            print(f"[serve]   {key}: {h['state']} "
                  f"(ok={h['successes']} fail={h['failures']})")
        sup = fleet.get("supervisor")
        if sup is not None:
            print(f"[serve] supervisor: {sup['respawns']} respawn(s), "
                  f"heartbeat={sup['heartbeat_s']}s "
                  f"queue_depth={sup['queue_depth']}")
    lc = st.get("lifecycle")
    if lc is not None:
        w = lc["wal"]
        print(f"[serve] lifecycle: epoch {lc['epoch']}, "
              f"{lc['handoffs']} background handoff(s) "
              f"(last train {lc['last_train_s']:.2f}s off the query path); "
              f"WAL: {w['records']} fsync-acked record(s), {w['bytes']} B, "
              f"{w['seconds'] * 1e3 / max(w['records'], 1):.2f} ms/ack; "
              f"backpressure retries={backpressured} "
              f"rejected={lc['rejected']}")

        # Simulated crash-restart: tear the journal mid-append (an in-flight
        # frame a kill-9 would leave), then recover in a fresh service and
        # verify the served results are bit-identical to the pre-crash ones.
        import os
        import struct as _struct

        probe_keys = np.arange(8) + 10_000_000
        probe_fields = rng.integers(
            0, user_lim, size=(8, cfg.n_user_fields)).astype(np.int32)
        want_ids, want_scores = svc.recommend(probe_keys, probe_fields)
        svc.lifecycle._wal.close()  # the "crash": no checkpoint, no goodbye
        jpath = os.path.join(args.snapshot_dir, "journal.bin")
        with open(jpath, "ab") as f:
            f.write(_struct.pack("<4sII", b"ADD\0", 1 << 20, 0))
            f.write(b"\x00" * 37)  # header promises 1 MiB; the crash hit here
        svc2 = TwoTowerRetrievalService(values, cfg, ServiceConfig(**defaults))
        t0 = time.perf_counter()
        rec = svc2.recover_lifecycle()
        got_ids, got_scores = svc2.recommend(probe_keys, probe_fields)
        identical = (np.array_equal(want_ids, got_ids)
                     and np.array_equal(want_scores, got_scores))
        print(f"[serve] crash-restart: recovered in "
              f"{time.perf_counter() - t0:.2f}s — {rec.tail_records} acked "
              f"tail record(s) replayed, {rec.torn_bytes} torn in-flight "
              f"byte(s) dropped; post-recovery results "
              f"{'bit-identical' if identical else 'DIVERGED'}")
        if not identical:
            raise SystemExit("recovered service diverged from pre-crash")
        svc2.lifecycle.close()
    # A proc fleet's workers are real OS processes: drain and reap them.
    svc.shutdown_shards()


if __name__ == "__main__":
    main()
