"""Benchmark driver: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  Sizes are scaled to the CPU
container; EXPERIMENTS.md maps each section back to the paper's table.

  PYTHONPATH=src python -m benchmarks.run                    # everything
  PYTHONPATH=src python -m benchmarks.run table1             # one suite
  PYTHONPATH=src python -m benchmarks.run --smoke --json out.json serving

``--smoke`` shrinks every suite to CI-sized shapes (~seconds per suite);
``--json PATH`` additionally writes the collected rows as a BENCH json
artifact (the CI bench-smoke job uploads it so the perf trajectory
accumulates run over run).
"""
from __future__ import annotations

import argparse
import json
import sys
import time


SUITES = ("table1", "scaling", "kernels", "selection", "serving", "ivf",
          "pq", "snapshot", "shards", "faults", "rpc", "lifecycle",
          "filtered")


def run_suite(name: str, smoke: bool) -> None:
    if name == "table1":
        from benchmarks import table1
        if smoke:
            table1.main(sizes=(512,), d=64, k=20)
        else:
            table1.main(sizes=(1000, 2000, 4000), d=256, k=100)
    elif name == "scaling":
        from benchmarks import scaling
        if smoke:
            scaling.main(n=1024, d=32, k=16, devices=(1, 2))
        else:
            scaling.main(n=4096, d=128, k=64, devices=(1, 2, 4))
    elif name == "kernels":
        from benchmarks import kernels
        if smoke:
            kernels.main(m=256, n=512, d=64, k=16)
        else:
            kernels.main()
    elif name == "selection":
        from benchmarks import selection
        if smoke:
            selection.main(n=1024, d=64)
        else:
            selection.main()
    elif name == "serving":
        from benchmarks import serving
        if smoke:
            serving.main(corpus=2048, d=32, k=10, batch_sizes=(8, 64),
                         batches=4, churn=128)
        else:
            serving.main()
    elif name == "ivf":
        from benchmarks import serving
        if smoke:
            serving.ivf_sweep(corpus=2048, d=32, k=10, batch_sizes=(8, 64),
                              batches=4)
        else:
            serving.ivf_sweep()
    elif name == "pq":
        from benchmarks import serving
        if smoke:
            serving.pq_sweep(corpus=2048, d=32, k=10, batch_sizes=(8, 64),
                             batches=4, pq_ms=(8,), overfetches=(4,),
                             nprobes=(8,))
        else:
            serving.pq_sweep()
    elif name == "snapshot":
        from benchmarks import serving
        if smoke:
            serving.cold_start(corpus=2048, d=32, k=10, ncells=16, pq_m=8)
        else:
            serving.cold_start()
    elif name == "shards":
        from benchmarks import serving
        if smoke:
            serving.shards_sweep(corpus=2048, d=32, k=10,
                                 batch_sizes=(8, 64), batches=4, ncells=16,
                                 nprobe=8, shard_counts=(4,))
        else:
            serving.shards_sweep()
    elif name == "faults":
        from benchmarks import serving
        if smoke:
            serving.faults_sweep(corpus=2048, d=32, k=10, ncells=16,
                                 nprobe=8, n_shards=4,
                                 fault_rates=(0.0, 0.1), rounds=4)
        else:
            serving.faults_sweep()
    elif name == "rpc":
        from benchmarks import serving
        if smoke:
            serving.rpc_sweep(corpus=2048, d=32, k=10, batch_sizes=(8, 64),
                              batches=4, ncells=16, nprobe=8, n_shards=2)
        else:
            serving.rpc_sweep()
    elif name == "lifecycle":
        from benchmarks import serving
        if smoke:
            serving.lifecycle_sweep(corpus=2048, d=32, k=10, ncells=16,
                                    nprobe=8, churn=128, iters=12,
                                    wal_batches=8)
        else:
            serving.lifecycle_sweep()
    elif name == "filtered":
        from benchmarks import serving
        if smoke:
            serving.filtered_sweep(corpus=2048, d=32, k=10, batches=4,
                                   ncells=16, selectivities=(0.5, 0.1),
                                   nprobes=(8, None), overfetches=(4,),
                                   n_shards=4)
        else:
            serving.filtered_sweep()
    else:
        raise SystemExit(f"unknown suite {name!r}; have {SUITES}")


def _derived_value(row: dict, key: str) -> float | None:
    """Parse ``key=<float>`` out of a row's ``derived`` field, else None."""
    for part in row.get("derived", "").split(";"):
        if part.startswith(key + "="):
            try:
                return float(part.split("=", 1)[1])
            except ValueError:
                return None
    return None


def compare_rows(rows: list, baseline_rows: list, tolerance: float) -> list:
    """Perf regressions of ``rows`` vs a committed baseline (the CI gate).

    Gated metrics are the serving-level ones the stack optimizes for:
    ``qps`` (must not drop) and ``p99_ms`` (must not grow).  Two checks,
    both calibrated against measured same-machine run-over-run noise
    (single smoke rows move up to ~30%: p99 at CI sizes is a max over ~3
    steady-state samples):

    * **systemic** — the geometric-mean fresh/baseline ratio across ALL
      matched rows of a metric must stay within ``tolerance``.  A real
      regression in the shared scan/merge/select code moves every serving
      row together, which is exactly what a geomean detects and what
      single-row jitter cannot fake;
    * **catastrophic** — any single row beyond ``3 * tolerance`` fails on
      its own (a 75%+ move at the default is far outside noise even for a
      suite-local regression, e.g. one sweep recompiling per batch).

    Rows present on only one side are reported but never fail the run —
    suites grow, and a new sweep must not need a baseline to land in the
    same PR.  Raw ``us_per_call`` is NOT gated: kernel microbenches at CI
    sizes are noise-dominated.  The comparison is absolute, so the
    committed baseline must be refreshed when the runner class changes.
    """
    import math

    base = {r["name"]: r for r in baseline_rows}
    regressions = []
    fresh_names = {r["name"] for r in rows}
    rels: dict[str, list] = {"qps": [], "p99_ms": []}
    for row in rows:
        b = base.get(row["name"])
        if b is None:
            print(f"# compare: no baseline for {row['name']} (new row, "
                  f"skipped)", file=sys.stderr)
            continue
        for key, direction in (("qps", -1), ("p99_ms", +1)):
            bv, fv = _derived_value(b, key), _derived_value(row, key)
            if bv is None or fv is None or bv <= 0 or fv <= 0:
                continue
            rel = (fv - bv) / bv * direction  # oriented: > 0 means worse
            rels[key].append(rel)
            if rel > 3 * tolerance:
                regressions.append(
                    (row["name"], key, round(bv, 3), round(fv, 3),
                     f"{rel:+.0%}"))
    for key in rels:
        if not rels[key]:
            continue
        gm = math.exp(sum(math.log(max(1.0 + r, 1e-9)) for r in rels[key])
                      / len(rels[key]))
        print(f"# compare: {key} geomean drift {gm - 1:+.1%} over "
              f"{len(rels[key])} rows (gate {tolerance:+.0%})",
              file=sys.stderr)
        if gm - 1 > tolerance:
            regressions.append(
                (f"<geomean of {len(rels[key])} rows>", key, 1.0,
                 round(gm, 3), f"{gm - 1:+.0%}"))
    for name in sorted(set(base) - fresh_names):
        print(f"# compare: baseline row {name} missing from this run",
              file=sys.stderr)
    return regressions


def check_recall_floor(rows: list, floor: float) -> list:
    """Rows whose derived ``recall@K=`` value sits below ``floor``.

    The recall-carrying sweeps (serving precision, ivf, pq) run on fixed
    seeds, so their recall values are deterministic per commit — a drop
    below the floor is a real quality regression, not sampling noise, and
    the CI bench-smoke job turns it into a failing run (``--recall-floor``).
    """
    bad = []
    for row in rows:
        for part in row.get("derived", "").split(";"):
            if part.startswith("recall@") and "=" in part:
                val = float(part.split("=", 1)[1])
                if val < floor:
                    bad.append((row["name"], val))
    return bad


def main() -> None:
    ap = argparse.ArgumentParser(description="repro benchmark driver")
    ap.add_argument("suites", nargs="*", default=[], metavar="suite",
                    help=f"subset of {SUITES} (default: all)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized shapes: seconds per suite, same code paths")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write collected rows as a BENCH json artifact")
    ap.add_argument("--recall-floor", type=float, default=None,
                    metavar="FLOOR",
                    help="fail the run if any swept recall@k lands below "
                         "FLOOR (the CI bench-smoke quality gate)")
    ap.add_argument("--compare", default=None, metavar="BASELINE_JSON",
                    help="diff this run against a committed BENCH json and "
                         "fail on qps/p99 regressions beyond --tolerance "
                         "(the CI bench regression gate)")
    ap.add_argument("--tolerance", type=float, default=0.25,
                    help="relative qps/p99 slack for --compare "
                         "(default 0.25)")
    args = ap.parse_args()
    from repro.compile_cache import configure

    configure()
    which = args.suites or list(SUITES)
    print("name,us_per_call,derived")
    t0 = time.time()
    for name in which:
        run_suite(name, args.smoke)
    wall = time.time() - t0
    print(f"# total_wall_s,{wall:.1f},")
    from benchmarks import common
    if args.json:
        payload = {
            "meta": _run_metadata(),
            "suites": which,
            "smoke": bool(args.smoke),
            "total_wall_s": round(wall, 1),
            "rows": common.ROWS,
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=1)
        print(f"# wrote {args.json} ({len(common.ROWS)} rows)", file=sys.stderr)
    if args.recall_floor is not None:
        bad = check_recall_floor(common.ROWS, args.recall_floor)
        if bad:
            raise SystemExit(
                f"recall@k below the {args.recall_floor} floor: {bad}")
    if args.compare is not None:
        with open(args.compare) as f:
            baseline = json.load(f)
        regressions = compare_rows(common.ROWS, baseline["rows"],
                                   args.tolerance)
        if regressions:
            lines = "\n".join(
                f"  {name}: {key} {bv} -> {fv} ({rel} worse)"
                for name, key, bv, fv, rel in regressions)
            raise SystemExit(
                f"perf regressions beyond ±{args.tolerance:.0%} vs "
                f"{args.compare} (baseline {baseline['meta'].get('git_sha', '?')[:8]}):\n{lines}")
        print(f"# compare: no qps/p99 regressions beyond "
              f"±{args.tolerance:.0%} vs {args.compare}", file=sys.stderr)


def _run_metadata() -> dict:
    """Provenance stamp for the BENCH artifact.

    The CI bench-smoke job uploads one json per run; without the commit /
    timestamp / backend the accumulating perf-trajectory points are not
    attributable to anything (EXPERIMENTS.md).  Git lookups are best-effort:
    an exported tarball still produces a valid artifact.
    """
    import datetime
    import subprocess

    from benchmarks.common import REPO

    def git(*args: str) -> str | None:
        try:
            out = subprocess.run(["git", "-C", REPO, *args],
                                 capture_output=True, text=True, timeout=10)
            return out.stdout.strip() or None if out.returncode == 0 else None
        except (OSError, subprocess.SubprocessError):
            return None

    import jax

    return {
        "git_sha": git("rev-parse", "HEAD"),
        "git_branch": git("rev-parse", "--abbrev-ref", "HEAD"),
        "timestamp_utc": datetime.datetime.now(
            datetime.timezone.utc).isoformat(timespec="seconds"),
        "backend": jax.default_backend(),
        "jax_version": jax.__version__,
    }


if __name__ == '__main__':
    main()
