#!/usr/bin/env python3
"""Compare the optimized programs of two checkouts, compiled for a TPU v5e
that is described, not attached (no chip needed):

    JAX_PLATFORMS=cpu python bench/hlo_diff.py CHECKOUT_A CHECKOUT_B

The programs are the batch cell's IVF-PQ segment search
(``serving.index._segment_candidates_ivfpq`` at 1,024 x 128 queries,
k_out 16, 2,048 cells of 1,024 slots, nprobe 64, pq_m 16, overfetch 64,
2^20 rows, ``impl="fused"``) and the all-pairs solve
(``knn_allpairs(x, 100, impl="fused")`` at 160,000 x 256).  Each text is
stripped of what is debug information only: ``metadata={...}``, the
tables of source files, functions and stack frames it points into, and
the source locations inside each Mosaic kernel's serialized payload (taken
out before it is serialized, on both sides alike).  Two
programs whose stripped texts differ only in instruction numbering (which
XLA draws from source locations) are also reported, renumbered, as equal.
Each checkout compiles in a child process of its own, with the kernels
lowered for Mosaic as on the chip (not the interpreter this CPU would pick).
"""
from __future__ import annotations

import os
import re
import subprocess
import sys
import tempfile

TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")

CHILD = r'''
import os, sys
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.join(sys.argv[1], "src"))
import jax, jax.numpy as jnp
jax.config.update("jax_enable_compilation_cache", False)
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
from repro.core import knn_allpairs
from repro.core.ivf import IVFCells
from repro.core.pq import PQCodebook, PQCodes
from repro.kernels import _backend
from repro.serving import index as I
_backend.on_tpu = lambda: True  # Mosaic kernels, as on the chip
from jax._src import tpu_custom_call as tcc
from jaxlib.mlir.passmanager import PassManager
_to_asm = tcc._lower_mosaic_module_to_asm


def _without_locations(module, **kw):  # a kernel's source locations
    with module.context:
        PassManager.parse("builtin.module(strip-debuginfo)").run(
            module.operation)
    return _to_asm(module, **kw)


tcc._lower_mosaic_module_to_asm = _without_locations
topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
sh = SingleDeviceSharding(topo.devices[0])
S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sh)
n, d, cells, cap, m, pqm = 1 << 20, 128, 2048, 1024, 1024, 16
ivf = IVFCells(S((cells, d), jnp.float32), S((cells * cap, d), jnp.float32),
               S((cells * cap,), jnp.int32), S((n,), jnp.int32),
               S((cells,), jnp.int32))
ivfpq = I._segment_candidates_ivfpq.lower(
    S((m, d), jnp.float32), S((n, d), jnp.float32), ivf,
    PQCodebook(S((pqm, 256, d // pqm), jnp.float32)),
    PQCodes(S((cells * cap, pqm), jnp.uint8), S((cells * cap,), jnp.float32)),
    S((n,), jnp.bool_), S((n,), jnp.int32), k_out=16, nprobe=64,
    overfetch=64, distance="sqeuclidean", impl="fused").compile()
allpairs = jax.jit(lambda x: knn_allpairs(x, 100, impl="fused")).lower(
    S((160000, 256), jnp.float32)).compile()
for name, c in (("ivfpq", ivfpq), ("allpairs", allpairs)):
    assert "tpu_custom_call" in c.as_text(), name
    open(os.path.join(sys.argv[2], name + ".hlo"), "w").write(c.as_text())
'''


def strip(text: str) -> str:
    """The program without its debug information."""
    out, skip = [], False
    for line in text.splitlines():
        if line in TABLES:
            skip = True
            continue
        if skip and line.startswith(("%", "ENTRY", "HloModule")):
            skip = False
        if not skip:
            out.append(re.sub(r",? metadata=\{[^}]*\}", "", line))
    return "\n".join(out)


def renumber(text: str) -> str:
    """Instruction names replaced by their order of first appearance."""
    ids = {}
    return re.sub(r"%([A-Za-z0-9_\-.]+)",
                  lambda m: "%" + ids.setdefault(m.group(1), f"v{len(ids)}"),
                  text)


def compile_in(checkout: str, out: str) -> None:
    subprocess.run([sys.executable, "-c", CHILD, os.path.abspath(checkout),
                    out], check=True, env=dict(os.environ,
                                               JAX_PLATFORMS="cpu"))


def main(argv=None) -> int:
    a, b = (argv or sys.argv[1:])[:2]
    with tempfile.TemporaryDirectory() as ta, \
            tempfile.TemporaryDirectory() as tb:
        compile_in(a, ta)
        compile_in(b, tb)
        same = True
        for name in ("ivfpq", "allpairs"):
            x, y = (strip(open(os.path.join(t, name + ".hlo")).read())
                    for t in (ta, tb))
            if x == y:
                verdict = "identical"
            elif renumber(x) == renumber(y):
                n = sum(p != q for p, q in zip(x.splitlines(),
                                               y.splitlines()))
                verdict = (f"identical but for instruction numbering "
                           f"({n} lines)")
            else:
                verdict, same = "DIFFERENT", False
            print(f"{name}: {len(x.splitlines())} lines stripped: {verdict}")
    return 0 if same else 1


if __name__ == "__main__":
    sys.exit(main())
