"""Open loop: requests of one query each, due at the mix's arrival times
(``bench/traffic.py`` ``due_times``).  Each request is submitted once it is
due and the loop flushes whatever is pending; after the last due time it
flushes until nothing is pending.  A request's latency runs from its due
time to the moment its answer is on the host."""
from __future__ import annotations

import time

import jax
import numpy as np

from bench import traffic

ann = jax.profiler.TraceAnnotation


def warm_sizes(mix: dict) -> list[int]:
    """Every flush size up to ``warm_max``: the engine compiles a program
    for each padded bucket, slices each flush's rows off it and
    concatenates the chunks of a flush larger than ``max_batch``, so every
    size is a shape, and a burst behind a stalled flush passes
    ``max_batch``."""
    return list(range(1, int(mix["warm_max"]) + 1))


def run(system, state, mix: dict, seconds: float, seed: int,
        clock=time.perf_counter, sleep=time.sleep) -> dict:
    engine, pool = state.engine, state.queries
    k = int(mix["k"])
    due = traffic.due_times(mix, seconds, seed)
    n = len(due)
    rec = traffic.Flushes()
    late = np.zeros(n)
    i = 0
    t0 = clock()
    while i < n or engine.pending:
        now = clock() - t0
        if i < n and due[i] <= now:
            with ann("submit"):
                while i < n and due[i] <= now:
                    engine.submit(i, pool[i % len(pool)])
                    late[i] = now - due[i]
                    i += 1
        if engine.pending:
            m = engine.pending
            f0 = clock()
            with ann("flush"):
                out = engine.flush(k)
            rec.add(out, m, f0 - t0, clock() - t0)
        elif i < n:
            wait = due[i] - (clock() - t0)
            if wait > 0:
                with ann("generate"):  # the generator waits for a due time
                    sleep(wait)
    elapsed = clock() - t0
    lat = np.array([rec.done_t[r] - due[r] if r in rec.done_t else np.inf
                    for r in range(n)])
    return rec.result(sent=n, elapsed_s=elapsed, latency_s=lat, late_s=late,
                      query_of=lambda rid: rid % len(pool))
