"""Whole runs of the harness at tiny sizes on the CPU: a sound run is
correct, and a run whose timed path is broken underneath is not."""
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from conftest import BENCH, ROOT, run_tiny


def _system(name):
    from bench import run as R

    return R.load_module(os.path.join(BENCH, "systems", name + ".py"),
                         f"bench_test_{name}_{np.random.randint(1 << 30)}")


class Proxy:
    """The real system module with some of its functions replaced."""

    def __init__(self, real, **over):
        self._real, self._over = real, over

    def __getattr__(self, name):
        return self._over.get(name) or getattr(self._real, name)


def test_allpairs_sound_run_is_correct():
    res = run_tiny("allpairs-160k.solve")
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "solve_s"}
    assert list(res)[-1] == "checks"


def test_allpairs_answer_altered_is_caught():
    real = _system("allpairs")

    def solve(st):
        r = real.solve(st)
        row = int(st.rows[0])
        wrong = (r.indices[row, 0] + 1) % st.x.shape[0]
        return r._replace(indices=r.indices.at[row, 0].set(wrong))

    res = run_tiny("allpairs-160k.solve", system=Proxy(real, solve=solve))
    assert not res["correct"]
    assert res["failed"] == res["attempted"]


@pytest.mark.parametrize("traffic", ["batch", "poisson"])
def test_serving_sound_runs_are_correct(traffic):
    res = run_tiny("clustered-1m-ivfpq.batch", seconds=1.5, traffic=traffic)
    assert res["correct"], (traffic, res["checks"])
    if traffic == "batch":
        assert set(res["metrics"]) == {"setup_s", "qps", "recall_at_10"}


class _Engine:
    def __init__(self, eng, fault):
        self._eng, self._fault = eng, fault

    def __getattr__(self, name):
        return getattr(self._eng, name)

    def flush(self, k=None):
        return self._fault(self._eng.flush(k))


def _broken_serving(fault):
    real = _system("ivfpq_engine")

    def setup(*a, **kw):
        st = real.setup(*a, **kw)
        st.engine = _Engine(st.engine, fault)
        return st

    return Proxy(real, setup=setup)


def _half(out):
    keep = sorted(out)[::2]
    return {r: out[r] for r in keep}


def _alter(out):
    r = min(out)
    v, i = out[r]
    i = i.copy()
    i[0] = (i[0] + 1) % 4096
    out = dict(out)
    out[r] = (v, i)
    return out


@pytest.mark.parametrize("traffic", ["poisson", "batch"])
@pytest.mark.parametrize("fault", [_half, _alter], ids=["half", "alter"])
def test_serving_faults_are_caught(traffic, fault):
    res = run_tiny("clustered-1m-ivfpq.batch", seconds=1.0, traffic=traffic,
                   system=_broken_serving(fault))
    assert not res["correct"], res["checks"]
    assert res["failed"] >= 1


RING = textwrap.dedent("""
    import os, sys
    sys.path[:0] = [{root!r}, {src!r}, {tests!r}]
    import jax, jax.numpy as jnp
    from conftest import run_tiny, BENCH
    from bench import run as R
    real = R.load_module(os.path.join(BENCH, "systems", "allpairs.py"), "s")
    broken = {broken}
    if broken:
        from jax.sharding import NamedSharding, PartitionSpec as P
        from bench import data
        from repro.core import knn_query
        orig = real.make_solver

        def make_solver(cfg, seed, chips):
            # The ring with its exchange left out: each chip ranks only its
            # own rows.
            mesh = jax.make_mesh((chips,), ("ring",),
                                 axis_types=(jax.sharding.AxisType.Auto,))
            x = data.random_vectors(int(cfg["n"]), int(cfg["d"]), seed,
                                    sharding=NamedSharding(mesh, P("ring")))
            n_loc = int(cfg["n"]) // chips
            k = int(cfg["k"])

            @jax.jit
            @jax.shard_map(mesh=mesh, in_specs=P("ring"),
                           out_specs=(P("ring"), P("ring")), check_vma=False)
            def local(xl):
                r = knn_query(xl, xl, k, exclude_self=True)
                off = jax.lax.axis_index("ring") * n_loc
                return r.distances, r.indices + off

            def solve(x):
                v, i = local(x)
                from repro.core.knn import KNNResult
                return KNNResult(v, i)
            return x, solve
        real.make_solver = make_solver
    res = run_tiny("allpairs-160k.solve", system=real, chips=4)
    print("CORRECT", res["correct"], res["checks"])
""")


@pytest.mark.parametrize("broken", [False, True],
                         ids=["ring", "exchange_left_out"])
def test_ring_on_four_devices(broken):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = RING.format(root=ROOT, src=os.path.join(ROOT, "src"),
                       tests=os.path.dirname(__file__), broken=broken)
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    assert f"CORRECT {not broken}" in p.stdout, p.stdout[-2000:]
