#!/usr/bin/env python3
"""The control of a cell's checks: the float32 reference put in the
program's place and computed one precision below (bfloat16 operands,
float32 accumulation), read by the same comparison as a benchmark run.

    python bench/control.py --workload <cell> --seeds 1 2 3

Prints one JSON line per seed with its readings and whether they fail the
cell's limits (they must: a check that the control passes catches
nothing).  At the cell's own size; the benchmark's runs never run it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]

from bench import run as R  # noqa: E402


def fails(readings: dict, limits: dict) -> bool:
    return any(readings[c] < lim["min"] if "min" in lim else
               readings[c] > lim["max"]
               for c, lim in limits.items() if c in readings)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
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
    system = R.load_system(cfg)
    for seed in args.seeds:
        r = system.control(cfg, mix, seed, int(cell["chips"]))
        print(json.dumps({"workload": cell["name"], "seed": seed,
                          "control": r,
                          "fails": fails(r, cfg["checks"])}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
