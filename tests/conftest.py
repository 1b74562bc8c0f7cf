"""Shared test helpers.

NOTE: no XLA_FLAGS here — smoke tests and benches must see ONE device.
Multi-device tests run in subprocesses via ``run_with_devices``.
"""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Run from a plain checkout without installing: src/ on the path.
sys.path.insert(0, os.path.join(REPO, "src"))

import _hypothesis_fallback  # noqa: E402

_hypothesis_fallback.install()


def run_with_devices(code: str, n_devices: int = 8, timeout: int = 600) -> str:
    """Run ``code`` in a subprocess with N forced host devices; assert rc 0."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    if proc.returncode != 0:
        raise AssertionError(
            f"subprocess failed (rc={proc.returncode})\n"
            f"--- stdout ---\n{proc.stdout}\n--- stderr ---\n{proc.stderr[-4000:]}"
        )
    return proc.stdout


@pytest.fixture(scope="session")
def single_mesh():
    import jax
    from jax.sharding import AxisType

    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


@pytest.fixture(scope="session")
def rules(single_mesh):
    from repro.distributed.sharding import make_rules

    return make_rules(single_mesh)
