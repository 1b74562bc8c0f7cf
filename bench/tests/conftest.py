"""CPU tests of the benchmark (``python -m pytest bench/tests``).

They run the harness off the chip at tiny sizes: a test passes the devices
and the peaks that ``bench/run.py`` would otherwise look up, so nothing here
reads as a device number.
"""
from __future__ import annotations

import copy
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

os.environ.setdefault("JAX_PLATFORMS", "cpu")


def tiny(cell_name: str, chips=None, traffic=None):
    """(spec, cell, cfg, mix) of a cell, cut to the ``tiny`` sizes of its
    configuration and mix files, which the CPU runs in seconds (the Pallas
    kernels run in the interpreter); ``chips`` and
    ``traffic`` run the cell's configuration on that many devices or under
    another mix of ``bench/mixes`` instead."""
    from bench import run as R

    spec = R.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = R.find(spec["workloads"], cell_name, "workload")
    if chips is not None:
        cell = dict(cell, chips=chips)
    if traffic is not None:
        cell = dict(cell, traffic=traffic,
                    name=f"{cell['config']}.{traffic}")
    cfg = copy.deepcopy(R.load_json(os.path.join(
        ROOT, R.find(spec["configs"], cell["config"], "config")["file"])))
    mix = copy.deepcopy(R.load_json(os.path.join(BENCH, "mixes",
                                                 cell["traffic"] + ".json")))
    merge(cfg, cfg.get("tiny", {}))
    merge(mix, mix.get("tiny", {}))
    return spec, cell, cfg, mix


def merge(into: dict, over: dict) -> None:
    """Apply a file's ``tiny`` sizes, group by group."""
    for key, v in over.items():
        if isinstance(v, dict):
            merge(into[key], v)
        else:
            into[key] = v


def run_tiny(cell_name: str, *, seconds=1.0, seed=2**33 + 7, system=None,
             trace=False, mutate=None, chips=None, traffic=None):
    """One harness run of a tiny cell on the CPU; returns the result."""
    import jax

    from bench import run as R
    from bench import work

    spec, cell, cfg, mix = tiny(cell_name, chips, traffic)
    if mutate is not None:
        mutate(cfg, mix)
    if system is None:
        system = R.load_system(cfg)
    return R.run(spec, cell, cfg, mix, seed=seed, seconds=seconds,
                 trace=trace, devices=jax.devices()[:cell["chips"]],
                 peaks=work.load_peaks("TPU v5 lite"), system=system)
