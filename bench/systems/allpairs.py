"""All-pairs kNN (the paper's problem): back-to-back solves of one array.

One chip runs ``repro.core.knn_allpairs(x, k, impl=...)``; a mesh of several
chips runs the ring, ``repro.core.distributed.make_ring_allpairs``, over
rows sharded across the chips.  The configuration's ``entry`` names which,
by the cell's chip count.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import data, reference, work

CHECKS = ("bad_ids", "topk_err", "value_err")


@dataclasses.dataclass
class State:
    cfg: dict
    x: object
    solve_fn: object = None
    rows: np.ndarray = None  # sampled rows whose answers are compared
    rows_dev: object = None
    take: object = None
    kept: list = dataclasses.field(default_factory=list)
    setup_log: dict = dataclasses.field(default_factory=dict)


def make_solver(cfg: dict, seed: int, chips: int):
    """(x, solve(x)) for the configuration's entry at ``chips`` chips."""
    n, d, k = int(cfg["n"]), int(cfg["d"]), int(cfg["k"])
    entry = cfg["entry"][str(chips)]
    if entry == "knn_allpairs":
        from repro.core import knn_allpairs

        impl = cfg["impl"]
        return (data.random_vectors(n, d, seed),
                lambda x: knn_allpairs(x, k, impl=impl))
    if entry == "ring_allpairs":
        from jax.sharding import NamedSharding, PartitionSpec

        from repro.core.distributed import make_ring_allpairs

        mesh = jax.make_mesh((chips,), ("ring",), devices=jax.devices()[:chips],
                             axis_types=(jax.sharding.AxisType.Auto,))
        x = data.random_vectors(
            n, d, seed, sharding=NamedSharding(mesh, PartitionSpec("ring")))
        ring = make_ring_allpairs(mesh, k=k, impl=cfg["impl"])
        return x, lambda x: ring(x, n)
    raise ValueError(f"unknown entry {entry!r}")


def sample_rows(cfg: dict, seed: int) -> np.ndarray:
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 11]))
    return np.sort(rng.choice(int(cfg["n"]), int(cfg["check_rows"]),
                              replace=False))


def setup(cfg: dict, mix: dict, seed: int, chips: int,
          warm: list[int]) -> State:
    """The rows made and one solve run (it compiles, or loads the cache);
    a solve has one shape, so ``warm`` adds nothing."""
    log = {}
    t = time.perf_counter()
    x, solve_fn = make_solver(cfg, seed, chips)
    jax.block_until_ready(x)
    log["data_s"] = time.perf_counter() - t
    rows = sample_rows(cfg, seed)
    st = State(cfg, x, solve_fn, rows, jnp.asarray(rows),
               jax.jit(lambda v, i, r: (v[r], i[r])))
    t = time.perf_counter()
    res = jax.block_until_ready(solve(st))  # compiles, or loads the cache
    keep(st, res)
    jax.block_until_ready(st.kept)
    st.kept.clear()
    log["warm_solve_s"] = time.perf_counter() - t
    st.setup_log = log
    return st


def solve(st: State):
    return st.solve_fn(st.x)


def keep(st: State, res) -> None:
    """Keep the sampled rows of a solve's answer, on the device."""
    st.kept.append(st.take(res.distances, res.indices, st.rows_dev))


def check(st: State, out: dict, limits: dict) -> tuple[dict, int]:
    """Readings of the sampled rows of every solve in the window (the worst
    over the solves), and the count of solves that fail a limit."""
    got = [(np.asarray(v), np.asarray(i)) for v, i in st.kept]
    st.kept.clear()
    st.solve_fn = None  # the program's state goes before the reference runs
    k = int(st.cfg["k"])
    q = st.x[st.rows_dev]
    worst = {name: 0 for name in CHECKS}
    failed = 0
    for v, i in got:
        r = reference.compare(q, st.x, v, i, k, exclude=st.rows)
        failed += any(r[c] > limits[c]["max"] for c in CHECKS)
        for c in CHECKS:
            worst[c] = max(worst[c], r[c])
    worst["missing"] = int(out["steps"]) - len(got)
    return worst, failed + worst["missing"]


def control(cfg: dict, mix: dict, seed: int, chips: int) -> dict:
    """The control's readings: the reference computed in bfloat16 in the
    program's place, on the sampled rows of the cell's own array."""
    x, _ = make_solver(cfg, seed, chips)
    rows = sample_rows(cfg, seed)
    q = x[jnp.asarray(rows)]
    v, i = reference.control_topk(q, x, int(cfg["k"]), exclude=rows)
    r = reference.compare(q, x, v, i, int(cfg["k"]), exclude=rows)
    return {c: r[c] for c in CHECKS}


def window_work(st: State, out: dict) -> dict:
    n, d, k = int(st.cfg["n"]), int(st.cfg["d"]), int(st.cfg["k"])
    return {"solve": work.allpairs(n, d, k).scaled(out["steps"])}


def attempted(out: dict) -> int:
    return int(out["steps"])
