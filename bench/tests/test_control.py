"""The control (the reference one precision below float32, in the
program's place) fails each configuration's limits; the reference against
itself reads nothing."""
import os

import pytest

from conftest import BENCH, tiny


def _system(name):
    from bench import run as R

    return R.load_module(os.path.join(BENCH, "systems", name + ".py"),
                         "bench_ctl_" + name)


def _fails(readings, limits):
    from bench.control import fails

    return fails(readings, limits)


@pytest.mark.parametrize("seed", [1, 2**33 + 9, 77])
def test_allpairs_control_fails(seed):
    _, cell, cfg, mix = tiny("allpairs-160k.solve")
    r = _system("allpairs").control(cfg, mix, seed, 1)
    assert _fails(r, cfg["checks"]), r
    assert r["value_err"] > 10 * cfg["checks"]["value_err"]["max"]


@pytest.mark.parametrize("seed", [1, 2**33 + 9, 77])
def test_serving_control_fails(seed):
    _, cell, cfg, mix = tiny("clustered-1m-ivfpq.batch")
    r = _system("ivfpq_engine").control(cfg, mix, seed, 1)
    assert _fails(r, cfg["checks"]), r
    assert r["value_err"] > 10 * cfg["checks"]["value_err"]["max"]


def test_reference_against_itself_reads_zero():
    import jax.numpy as jnp
    import numpy as np

    from bench import data, reference

    x = data.random_vectors(512, 32, 3)
    q = x[:16]
    v, i = reference.brute_topk(q, x, 8, exclude=np.arange(16))
    r = reference.compare(q, x, v, i, 8, exclude=np.arange(16))
    assert r["bad_ids"] == 0 and r["recall"] == 1.0
    assert r["topk_err"] < 1e-6 and r["value_err"] < 1e-6
    # The ids compared as a set: a reordering reads as before on topk_err.
    sw = i.copy()
    sw[:, [1, 2]] = sw[:, [2, 1]]
    assert reference.compare(q, x, v, sw, 8,
                             exclude=np.arange(16))["topk_err"] < 1e-6
    bad = i.copy()
    bad[0, 0] = bad[0, -1] + 0  # a repeated id
    assert reference.compare(q, x, v, bad, 8)["bad_ids"] >= 1
    del jnp
