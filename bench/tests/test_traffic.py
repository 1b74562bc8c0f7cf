"""The traffic generator and the window loops, on a fake clock."""
import types

import numpy as np
import pytest

from bench import run as R
from bench import traffic


def _loop(name):
    return R.load_loop({"loop": name})


def _state(engine, pool):
    return types.SimpleNamespace(engine=engine, queries=pool)


def test_poisson_due_times_come_from_the_seed():
    a = traffic.poisson_due(200, 10, gap_seed=0, seed=5)
    b = traffic.poisson_due(200, 10, gap_seed=0, seed=5)
    c = traffic.poisson_due(200, 10, gap_seed=0, seed=2**33 + 1)
    assert np.array_equal(a, b)
    assert len(a) == 2000 and not np.array_equal(a, c)
    # Every seed offers the same gaps in another order.
    assert np.allclose(np.sort(np.diff(a, prepend=0)),
                       np.sort(np.diff(c, prepend=0)))
    assert a[-1] == pytest.approx(c[-1])
    gaps = np.diff(a, prepend=0)
    assert gaps.mean() == pytest.approx(1 / 200, rel=0.1)


def test_bursts_keep_the_rate_and_arrive_together():
    one = traffic.poisson_due(200, 10, gap_seed=0, seed=5)
    b = traffic.poisson_due(200, 10, gap_seed=0, seed=5, burst=4)
    assert len(b) == 2000 and len(np.unique(b)) == 500
    assert b[-1] == pytest.approx(one[-1], rel=0.15)
    # burst 1 is the plain Poisson process, draw for draw.
    assert np.array_equal(
        traffic.poisson_due(200, 10, gap_seed=0, seed=5, burst=1), one)


def test_queries_come_from_the_seed_and_skew_weights_the_clusters():
    import jax.numpy as jnp

    cfg = {"d": 8, "corpus_seed": 0, "clusters": 8, "spread": 0.01}
    mix = {"query_pool": 4000}
    a = np.asarray(traffic.queries(cfg, mix, 2**33 + 5))
    assert np.array_equal(a, np.asarray(traffic.queries(cfg, mix, 2**33 + 5)))
    assert not np.array_equal(a, np.asarray(traffic.queries(cfg, mix, 6)))
    import jax

    from bench import data

    # The mixture's centres, as bench/data.py draws them.
    centres = np.asarray(jax.random.normal(data.key(0, 0), (8, 8),
                                           jnp.float32))

    def shares(q):
        d = ((q[:, None, :] - centres[None]) ** 2).sum(-1)
        return np.bincount(d.argmin(1), minlength=8) / len(q)

    assert shares(a).max() < 0.2  # uniform: 1/8 each
    skewed = np.asarray(traffic.queries(cfg, dict(mix, cluster_skew=2.0), 1))
    # Zipf(2) over 8 clusters puts 0.65 of the queries on the first.
    assert shares(skewed)[0] == pytest.approx(0.65, abs=0.05)


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def sleep(self, dt):
        self.t += dt


class FakeEngine:
    """Answers every pending query after ``cost(m)`` seconds of the clock."""

    def __init__(self, clock, cost):
        self.clock, self.cost, self.q = clock, cost, {}
        self.flushes = []

    def submit(self, rid, v):
        self.q[rid] = v

    @property
    def pending(self):
        return len(self.q)

    def flush(self, k=None):
        out = {r: (np.zeros(k), np.full(k, r)) for r in self.q}
        self.flushes.append(len(self.q))
        self.clock.t += self.cost(len(self.q))
        self.q = {}
        return out


def test_open_loop_times_requests_from_their_due_time():
    clock = FakeClock()
    eng = FakeEngine(clock, lambda m: 0.010)
    mix = {"k": 2, "rate_per_s": 50, "gap_seed": 0}
    out = _loop("open_loop").run(None, _state(eng, np.zeros((64, 4))), mix,
                                 2.0, seed=3, clock=clock, sleep=clock.sleep)
    due = traffic.poisson_due(50, 2.0, 0, 3)
    assert out["sent"] == len(due) == len(out["answers"])
    # A request arriving at an idle engine waits exactly one flush; one
    # arriving during a flush also waits for the flush ahead of it.
    lat = out["latency_s"]
    assert lat.min() == pytest.approx(0.010)
    assert (lat >= 0.010 - 1e-12).all() and (lat <= 0.020 + 1e-9).all()
    assert sum(out["flush_sizes"]) == out["sent"]


def test_open_loop_p99_covers_every_request_and_a_stall_shows():
    clock = FakeClock()
    calls = []

    def cost(m):
        calls.append(m)
        return 0.5 if len(calls) == 3 else 0.001  # one stalled flush

    eng = FakeEngine(clock, cost)
    mix = {"k": 1, "rate_per_s": 100, "gap_seed": 1}
    out = _loop("open_loop").run(None, _state(eng, np.zeros((8, 2))), mix,
                                 5.0, seed=0, clock=clock, sleep=clock.sleep)
    lat = out["latency_s"]
    assert len(lat) == 500
    # The ~50 requests due during the stall wait up to 0.5 s: they are over
    # 1 % of all requests, so p99 shows the stall.
    assert traffic.percentile(lat, 99) > 0.1
    assert traffic.percentile(lat, 50) < 0.01


def test_solves_rate_is_whole_steps_over_elapsed():
    clock = FakeClock()

    class Sys:
        n = 0

        def solve(self, st):
            clock.t += 0.3
            self.n += 1
            return np.zeros(1)

        def keep(self, st, r):
            pass

    s = Sys()
    out = _loop("solves").run(s, None, {}, 1.0, 0, clock=clock)
    # Whole solves until the elapsed time passes the window: 4 x 0.3 s.
    assert out["steps"] == 4 == s.n
    assert out["elapsed_s"] == pytest.approx(1.2)


def test_closed_batches_send_whole_batches_until_the_window_passes():
    clock = FakeClock()
    eng = FakeEngine(clock, lambda m: 0.25)
    mix = {"k": 3, "batch": 16}
    out = _loop("closed_batches").run(None, _state(eng, np.zeros((20, 4))),
                                      mix, 0.6, 0, clock=clock)
    assert out["sent"] == 48 == len(out["answers"])
    assert eng.flushes == [16, 16, 16]
    assert out["elapsed_s"] == pytest.approx(0.75)
    assert out["query_of"](21) == 1
    assert out["flush_start_s"] == pytest.approx([0.0, 0.25, 0.5])


def test_pad_rows_and_warm_sizes_follow_the_engine_buckets():
    mix = {"loop": "open_loop", "min_batch": 8, "max_batch": 64,
           "warm_max": 128}
    assert traffic.pad_rows(mix, [1, 8, 9, 64, 70]) == (
        7 + 0 + 7 + 0 + (0 + 2), 8 + 8 + 16 + 64 + 64 + 8)
    assert _loop("open_loop").warm_sizes(mix) == list(range(1, 129))
    closed = {"loop": "closed_batches", "batch": 1024, "min_batch": 8,
              "max_batch": 1024}
    assert _loop("closed_batches").warm_sizes(closed) == [1024]
    assert _loop("solves").warm_sizes({}) == []
    assert traffic.pad_rows(closed, [1024, 1024]) == (0, 2048)
