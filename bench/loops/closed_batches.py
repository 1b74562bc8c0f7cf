"""Closed loop, one client: it submits ``batch`` queries to the system's
engine, flushes, and sends the next batch once the answers are back, until
the elapsed time passes the window."""
from __future__ import annotations

import time

import jax

from bench import traffic

ann = jax.profiler.TraceAnnotation


def warm_sizes(mix: dict) -> list[int]:
    """The one flush size the loop sends."""
    return [int(mix["batch"])]


def run(system, state, mix: dict, seconds: float, seed: int,
        clock=time.perf_counter, sleep=time.sleep) -> dict:
    engine, pool = state.engine, state.queries
    b, k = int(mix["batch"]), int(mix["k"])
    rec = traffic.Flushes()
    sent = 0
    t0 = clock()
    while True:
        with ann("submit"):
            for j in range(b):
                engine.submit(sent + j, pool[(sent + j) % len(pool)])
        f0 = clock()
        with ann("flush"):
            out = engine.flush(k)
        t = clock()
        rec.add(out, b, f0 - t0, t - t0)
        sent += b
        if t - t0 >= seconds:
            break
    return rec.result(sent=sent, elapsed_s=t - t0,
                      query_of=lambda rid: rid % len(pool))
