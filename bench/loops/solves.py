"""Whole solves back to back, each blocked on its result, until the elapsed
time passes the window; the rate is elapsed time over the count of whole
solves (no solve dropped or pro-rated)."""
from __future__ import annotations

import time

import jax

ann = jax.profiler.TraceAnnotation


def warm_sizes(mix: dict) -> list[int]:
    """A solve has one shape, the configuration's: nothing beyond it."""
    return []


def run(system, state, mix: dict, seconds: float, seed: int,
        clock=time.perf_counter, sleep=time.sleep) -> dict:
    n = 0
    t0 = clock()
    while True:
        with ann("solve"):
            res = system.solve(state)
        with ann("block"):
            jax.block_until_ready(res)
        system.keep(state, res)
        n += 1
        elapsed = clock() - t0
        if elapsed >= seconds:
            break
    return {"steps": n, "elapsed_s": elapsed}
