"""Lifecycle crash-replay check: journal -> crash -> recover in a FRESH process.

The CI ``lifecycle-crash`` job runs this driver.  For each serving
configuration (flat fp32, int8 two-stage, IVF, IVF-PQ) it:

  1. builds a RetrievalIndex, arms the crash-safe lifecycle
     (``serving.lifecycle.LifecycleIndex.attach`` — full WAL image +
     fsync-acked journaling), and acks a batch of inserts/upserts/deletes;
  2. searches a fixed query set and records the exact (distances, ids);
  3. simulates a crash mid-append: the process state is discarded and a torn
     half-frame is left at the journal tail, exactly what a SIGKILL between
     ``write`` and ``fsync`` strands on disk;
  4. spawns a FRESH Python subprocess that recovers the snapshot + WAL —
     with ``core.kmeans.lloyd`` replaced by a tripwire, so any k-means/PQ
     training on the recovery path fails the run — and asserts that every
     acked record was replayed, the torn bytes were dropped, and the
     recovered ``search`` is BIT-identical (values and ids) to the recorded
     results.

A fresh process is the point: it proves the journal + image carry everything
(recovery shares no interpreter state with the writer), which is exactly the
crash-restart scenario DESIGN.md §16 exists for.  Exit code is nonzero on any
mismatch; the snapshot directories remain on disk so CI can upload them as a
workflow artifact.

  PYTHONPATH=src python -m repro.launch.lifecycle_check --out wal_snapshots
"""
from __future__ import annotations

import argparse
import os
import struct
import subprocess
import sys

CONFIGS = {
    "flat": {},
    "int8": {"scan_dtype": "int8"},
    "ivf": {"ivf_cells": 16, "nprobe": 4},
    "ivfpq": {"ivf_cells": 16, "nprobe": 8, "pq_m": 8},
}

_WRITE_SNIPPET = """
import sys
from repro.launch.lifecycle_check import CONFIGS, journal_and_crash
journal_and_crash(sys.argv[1], CONFIGS[sys.argv[1]], sys.argv[2])
"""

_RECOVER_SNIPPET = """
import sys
import numpy as np
import repro.core.kmeans as KM

def _tripwire(*a, **kw):
    raise AssertionError("kmeans.lloyd entered on the recovery path")
KM.lloyd = _tripwire

from repro.serving import LifecycleConfig, LifecycleIndex

snap, expected_path = sys.argv[1], sys.argv[2]
with np.load(expected_path) as z:
    q, want_v, want_i = z["q"], z["v"], z["i"]
    k, acked, torn = int(z["k"]), int(z["acked"]), int(z["torn"])
lc, rec = LifecycleIndex.recover(LifecycleConfig(snapshot_dir=snap))
if rec.tail_records != acked:
    sys.exit(f"replayed {rec.tail_records} acked records, wanted {acked} "
             f"({snap})")
if rec.torn_bytes != torn:
    sys.exit(f"dropped {rec.torn_bytes} torn bytes, wanted {torn} ({snap})")
res = lc.search(q, k)
got_v, got_i = np.asarray(res.distances), np.asarray(res.ids)
if not np.array_equal(got_i, want_i):
    sys.exit(f"recovered ids differ from the pre-crash writer ({snap})")
if not np.array_equal(got_v, want_v):
    sys.exit(f"recovered distances differ bitwise from the writer ({snap})")
lc.close()
print(f"recover OK: {rec.tail_records} acked records replayed, "
      f"{rec.torn_bytes} torn bytes dropped, bit-identical search")
"""


def journal_and_crash(name: str, kw: dict, out: str, *, n: int = 1024,
                      d: int = 32, k: int = 10, seed: int = 0) -> str:
    """Build + arm + ack mutations, then strand a torn frame at the tail."""
    import numpy as np

    from repro.serving import LifecycleConfig, LifecycleIndex, RetrievalIndex
    from repro.serving.snapshot import _JOURNAL

    rng = np.random.default_rng(seed)
    vecs = rng.normal(size=(n, d)).astype(np.float32)
    idx = RetrievalIndex.build(np.arange(n), vecs, **kw)
    snap = os.path.join(out, name)
    lc = LifecycleIndex.attach(idx, LifecycleConfig(snapshot_dir=snap))
    # Acked churn: every record below is fsynced before the call returns.
    lc.insert(np.arange(n, n + 64),
              rng.normal(size=(64, d)).astype(np.float32))
    lc.upsert(np.arange(n + 60, n + 72),
              rng.normal(size=(12, d)).astype(np.float32))
    lc.delete(np.arange(0, n, 17))
    acked = 3

    q = rng.normal(size=(32, d)).astype(np.float32)
    res = lc.search(q, k)
    lc.close()
    # The crash: a half-written frame (header promises 1 MiB, 40 bytes
    # landed) at the tail — never acked, so recovery must drop exactly it.
    torn = struct.pack("<4sII", b"ADD\0", 1 << 20, 0) + b"\0" * 40
    with open(os.path.join(snap, _JOURNAL), "ab") as f:
        f.write(torn)
    expected = os.path.join(out, f"{name}.expected.npz")
    np.savez(expected, q=q, v=np.asarray(res.distances),
             i=np.asarray(res.ids), k=k, acked=acked, torn=len(torn))
    return snap


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default="wal_snapshots",
                    help="directory for the crashed-snapshot artifacts")
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
        print(f"[lifecycle-check] {name}: journal + crash mid-append ({kw})")
        # The writer is a child process too: this parent never touches JAX,
        # so on a TPU host each child in turn is the one process on the chip.
        subprocess.run([sys.executable, "-c", _WRITE_SNIPPET, name, args.out],
                       check=True, env=env, timeout=600)
        snap = os.path.join(args.out, name)
        proc = subprocess.run(
            [sys.executable, "-c", _RECOVER_SNIPPET, snap,
             os.path.join(args.out, f"{name}.expected.npz")],
            capture_output=True, text=True, env=env, timeout=600)
        tag = "PASS" if proc.returncode == 0 else "FAIL"
        print(f"[lifecycle-check] {name}: {tag}  "
              f"{proc.stdout.strip() or proc.stderr.strip()}")
        if proc.returncode != 0:
            failures.append((name, proc.stderr[-2000:]))
    if failures:
        raise SystemExit(f"lifecycle crash-replay failed: {failures}")


if __name__ == "__main__":
    main()
