"""Snapshot round-trip check: save -> restore in a FRESH process -> compare.

The CI ``snapshot-roundtrip`` job runs this driver.  For each serving
configuration (flat fp32, int8 two-stage, IVF, IVF-PQ) it:

  1. builds a RetrievalIndex and churns it (deletes + delta upserts), so the
     snapshot exercises tombstones and a non-empty journal;
  2. searches a fixed query set and records the exact (distances, ids);
  3. snapshots the index under ``--out/<config>`` plus the queries and
     expected results (``expected.npz``, outside the snapshot dir);
  4. spawns a FRESH Python subprocess that restores the snapshot — with
     ``core.kmeans.lloyd`` replaced by a tripwire, so any k-means/PQ training
     on the restore path fails the run — and asserts the restored ``search``
     is BIT-identical (values and ids) to the recorded results.

A fresh process is the point: it proves the snapshot carries everything
(restore shares no interpreter state with the builder), which is exactly the
serving-restart scenario DESIGN.md §Persistence exists for.  Exit code is
nonzero on any mismatch; the snapshot directories remain on disk so CI can
upload them as a workflow artifact.

  PYTHONPATH=src python -m repro.launch.snapshot_check --out snapshots
"""
from __future__ import annotations

import argparse
import os
import subprocess
import sys

CONFIGS = {
    "flat": {},
    "int8": {"scan_dtype": "int8"},
    "ivf": {"ivf_cells": 16, "nprobe": 4},
    "ivfpq": {"ivf_cells": 16, "nprobe": 8, "pq_m": 8},
}

_BUILD_SNIPPET = """
import sys
from repro.launch.snapshot_check import CONFIGS, build_and_snapshot
build_and_snapshot(sys.argv[1], CONFIGS[sys.argv[1]], sys.argv[2])
"""

_RESTORE_SNIPPET = """
import sys
import numpy as np
import repro.core.kmeans as KM

def _tripwire(*a, **kw):
    raise AssertionError("kmeans.lloyd entered on the restore path")
KM.lloyd = _tripwire

from repro.serving import RetrievalIndex

snap, expected_path = sys.argv[1], sys.argv[2]
with np.load(expected_path) as z:
    q, want_v, want_i, k = z["q"], z["v"], z["i"], int(z["k"])
idx = RetrievalIndex.restore(snap)
res = idx.search(q, k)
got_v, got_i = np.asarray(res.distances), np.asarray(res.ids)
if not np.array_equal(got_i, want_i):
    sys.exit(f"restored ids differ from source index ({snap})")
if not np.array_equal(got_v, want_v):
    sys.exit(f"restored distances differ bitwise from source index ({snap})")
print(f"restore OK: {len(idx)} live rows, bit-identical search")
"""


def build_and_snapshot(name: str, kw: dict, out: str, *, n: int = 2048,
                       d: int = 32, k: int = 10, seed: int = 0) -> str:
    import numpy as np

    from repro.serving import RetrievalIndex

    rng = np.random.default_rng(seed)
    vecs = rng.normal(size=(n, d)).astype(np.float32)
    idx = RetrievalIndex.build(np.arange(n), vecs, **kw)
    # Churn: main tombstones + delta inserts + an id re-upserted inside the
    # delta (a dead and a live row under one id — the journal's hard case).
    idx.delete(np.arange(0, n, 17))
    idx.upsert(np.arange(n, n + 96),
               rng.normal(size=(96, d)).astype(np.float32))
    idx.upsert(np.arange(n, n + 8),
               rng.normal(size=(8, d)).astype(np.float32))
    idx.delete([n + 3])

    q = rng.normal(size=(32, d)).astype(np.float32)
    res = idx.search(q, k)
    snap = os.path.join(out, name)
    idx.save(snap)
    expected = os.path.join(out, f"{name}.expected.npz")
    np.savez(expected, q=q, v=np.asarray(res.distances),
             i=np.asarray(res.ids), k=k)
    return snap


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default="snapshots",
                    help="directory for the snapshot artifacts")
    ap.add_argument("--configs", nargs="*", default=list(CONFIGS),
                    metavar="NAME", help=f"subset of {list(CONFIGS)}")
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)

    repo_src = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env = dict(os.environ)
    env["PYTHONPATH"] = repo_src + os.pathsep + env.get("PYTHONPATH", "")
    failures = []
    for name in args.configs:
        kw = CONFIGS[name]
        print(f"[snapshot-check] {name}: build + churn + save ({kw})")
        # The builder is a child process too: this parent never touches JAX,
        # so on a TPU host each child in turn is the one process on the chip.
        subprocess.run([sys.executable, "-c", _BUILD_SNIPPET, name, args.out],
                       check=True, env=env, timeout=600)
        snap = os.path.join(args.out, name)
        proc = subprocess.run(
            [sys.executable, "-c", _RESTORE_SNIPPET, snap,
             os.path.join(args.out, f"{name}.expected.npz")],
            capture_output=True, text=True, env=env, timeout=600)
        tag = "PASS" if proc.returncode == 0 else "FAIL"
        print(f"[snapshot-check] {name}: {tag}  "
              f"{proc.stdout.strip() or proc.stderr.strip()}")
        if proc.returncode != 0:
            failures.append((name, proc.stderr[-2000:]))
    if failures:
        raise SystemExit(f"snapshot round-trip failed: {failures}")
    print(f"[snapshot-check] all {len(args.configs)} configs round-trip "
          f"bit-identically in fresh processes")


if __name__ == "__main__":
    main()
