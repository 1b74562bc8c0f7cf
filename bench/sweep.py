#!/usr/bin/env python3
"""Find the knee of an open-loop serving cell: one index, then the open loop
at each offered rate, for each engine ``max_batch``.

    python bench/sweep.py --workload clustered-1m-ivfpq.poisson --seed 5 \
        --max-batch 16 64 --rates 100 200 400 --seconds 8

Prints one JSON line per (max_batch, rate): p50 and p99 latency from the due
time, the mean flush size, how late the generator ran, and how long the
queue took to drain after the last request was due.  A rate is sustained
when the drain stays short and p99 does not grow with the window.  Run once
when a cell is defined; the cell's mix then fixes the rate and max_batch.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]

import numpy as np  # noqa: E402

from bench import run as R  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--max-batch", type=int, nargs="+", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    args = ap.parse_args(argv)
    spec = R.load_json(os.path.join(R.ROOT, "BENCHMARK.json"))
    cell = R.find(spec["workloads"], args.workload, "workload")
    cfg = R.load_json(os.path.join(
        R.ROOT, R.find(spec["configs"], cell["config"], "config")["file"]))
    mix = R.load_json(os.path.join(R.BENCH, "mixes",
                                   cell["traffic"] + ".json"))
    try:
        R.require_devices(int(cell["chips"]))
    except R.NoChip as e:
        R.log(str(e))
        return 1
    R.configure_cache()
    import jax

    from repro.serving import EngineConfig, QueryEngine

    system = R.load_system(cfg)
    loop = R.load_loop(mix)
    first = dict(mix, max_batch=mix["min_batch"], warm_max=mix["min_batch"])
    st = system.setup(cfg, first, args.seed, 1, [int(mix["min_batch"])])
    R.log(f"set-up: {st.setup_log}")
    for mb in args.max_batch:
        m = dict(mix, max_batch=mb, warm_max=mb)
        eng = QueryEngine(st.index, EngineConfig(
            k=int(m["k"]), min_batch=int(m["min_batch"]), max_batch=mb))
        for b in loop.warm_sizes(m):
            jax.block_until_ready(eng.search(st.queries[:b]).ids)
        st.engine = eng
        for rate in args.rates:
            m["rate_per_s"] = rate
            out = loop.run(system, st, m, args.seconds, args.seed)
            lat = out["latency_s"]
            print(json.dumps({
                "max_batch": mb, "rate_per_s": rate, "sent": out["sent"],
                "p50_ms": float(np.percentile(lat, 50) * 1e3),
                "p99_ms": float(np.percentile(lat, 99) * 1e3),
                "mean_flush": float(np.mean(out["flush_sizes"])),
                "max_flush": int(np.max(out["flush_sizes"])),
                "flush_ms_p50": float(np.median(out["flush_s"]) * 1e3),
                "late_p99_ms": float(np.percentile(out["late_s"], 99) * 1e3),
                "drain_s": float(out["elapsed_s"] - args.seconds),
            }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
