"""Time the ``pq_scan`` kernel alone at the IVF-PQ batch cell's shapes.

    PYTHONPATH=src python benchmarks/pq_scan_alone.py [--small] [FILE ...]

Each FILE is a copy of ``src/repro/kernels/pq_scan.py`` to time in place of
the repository's own (none given: the repository's), e.g. an older commit's
module, or one whose ``fold_tile`` call is replaced by a plain running
minimum to time the scan without the K-wide fold.  A module with
``split_lut`` takes the bf16 LUT pieces, one without it the float32 LUT.

Shapes (``clustered-1m-ivfpq.batch``): bm 64, cell_cap 1024, K 1024 (k 640),
m 16, 256 codes, 2048 cells, residual codes; each of 256 queries probes 64
random cells and a tile's list is the union of its queries' cells
(``tile_probe_lists``, W 2048).  ``--small`` runs 8 cells in the Pallas
interpreter, to rehearse on a CPU.  Prints one JSON line per module: median
seconds per call of five, microseconds per grid step, and the largest
difference of its finite values from the first module's.
"""
import importlib.util
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.ivf import tile_probe_lists


def load(path):
    spec = importlib.util.spec_from_file_location(
        "pq_scan_" + os.path.basename(path).removesuffix(".py"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def main(argv):
    small = "--small" in argv
    paths = [a for a in argv if not a.startswith("--")] or [
        os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "src",
                     "repro", "kernels", "pq_scan.py")]
    bm, cap, k, m, ncodes = 64, 1024, 640, 16, 256
    ncells, nprobe, nq = (8, 4, 64) if small else (2048, 64, 256)
    S = ncells * cap

    rng = np.random.default_rng(0)
    lut = jnp.asarray(rng.standard_normal((nq, m * ncodes)).astype(np.float32))
    codes_t = jnp.asarray(rng.integers(0, ncodes, (m, S)).astype(np.uint8))
    hx = jnp.asarray(rng.standard_normal((nq, 1)).astype(np.float32))
    hy = jnp.asarray((rng.random((1, S)) * 10).astype(np.float32))
    qc = jnp.asarray(rng.standard_normal((nq, ncells)).astype(np.float32))
    cells = jnp.asarray(np.stack([rng.choice(ncells, nprobe, replace=False)
                                  for _ in range(nq)]).astype(np.int32))
    probes = tile_probe_lists(cells, ncells, bm)
    steps = (nq // bm) * probes.shape[1]
    print(json.dumps({"device": jax.devices()[0].device_kind,
                      "W": probes.shape[1], "steps": steps}), flush=True)

    first = None
    for path in paths:
        mod = load(path)
        lut_op = (jax.jit(mod.split_lut)(lut) if hasattr(mod, "split_lut")
                  else lut)
        fn = jax.jit(lambda *a, mod=mod: mod.pq_scan_pallas(
            *a[:5], k, cells=a[6], cell_cap=cap, ncodes=ncodes, qc=a[5],
            bm=bm, interpret=small))
        args = (probes, lut_op, codes_t, hx, hy, qc, cells)
        t0 = time.perf_counter()
        vals = np.asarray(jax.block_until_ready(fn(*args))[0])
        compile_s = time.perf_counter() - t0
        ts = []
        for _ in range(1 if small else 5):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            ts.append(time.perf_counter() - t0)
        t = float(np.median(ts))
        first = vals if first is None else first
        fin = np.isfinite(first) & np.isfinite(vals)
        print(json.dumps({
            "module": path, "compile_s": compile_s, "call_s": t,
            "all_s": ts, "us_per_step": t / steps * 1e6,
            "max_abs_diff_vs_first": float(np.abs(vals[fin] - first[fin]).max()),
        }), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
