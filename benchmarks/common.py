"""Shared benchmark helpers: timing, CSV emission, subprocess device sweeps."""
from __future__ import annotations

import os
import subprocess
import sys
import time

import jax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def timeit(fn, *args, warmup: int = 1, iters: int = 3) -> float:
    """Median wall seconds per call (blocking on device results)."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2]


# Every emitted row is also collected here so drivers (benchmarks.run) can
# write a machine-readable BENCH json next to the human CSV stream.
ROWS: list[dict] = []


def emit(name: str, seconds: float, derived: str = ""):
    print(f"{name},{seconds * 1e6:.1f},{derived}")
    ROWS.append({"name": name, "us_per_call": round(seconds * 1e6, 1),
                 "derived": derived})


def run_with_devices(code: str, n_devices: int, timeout: int = 1200) -> str:
    env = dict(os.environ)
    # Virtual host devices: the child runs on the CPU, never on the chip
    # this parent may hold.
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=timeout, env=env)
    if proc.returncode != 0:
        raise RuntimeError(f"bench subprocess failed:\n{proc.stderr[-2000:]}")
    return proc.stdout
