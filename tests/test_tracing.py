"""The serving path's profiler spans and device stage scopes, and the
engine's meter that shares their intervals (CPU).

The spans (``repro.accounting.span``) are host ``TraceAnnotation``s written
only while a profiler session is open; the scopes are ``jax.named_scope``
metadata on the IVF-PQ search's ops.  Neither may change a result.
"""
import glob
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.accounting import ServingMeter
from repro.serving import EngineConfig, QueryEngine, RetrievalIndex
from repro.serving import index as I

N, D = 1024, 16
STAGES = ("knn.probe", "knn.live_mask", "knn.probe_union", "knn.lut",
          "knn.scan", "knn.rescore", "knn.merge")


def _index(impl="jnp"):
    x = np.random.default_rng(0).standard_normal((N, D)).astype(np.float32)
    return RetrievalIndex.build(np.arange(N), x, impl=impl, ivf_cells=16,
                                nprobe=4, pq_m=4, pq_nbits=8, overfetch=4)


def _queries(m, seed=1):
    return np.random.default_rng(seed).standard_normal((m, D)).astype(
        np.float32)


def _knn_spans(trace_dir):
    """[(name, start_ns, end_ns, args)] of every ``knn.*`` host event."""
    (path,) = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("knn."):
                    out.append((ev.name, int(ev.start_ns), int(ev.end_ns),
                                dict(ev.stats)))
    return sorted(out, key=lambda s: (s[1], -s[2]))


def _children(spans, parent):
    """The spans directly inside ``parent``, in order."""
    inside = [s for s in spans if s is not parent and parent[1] <= s[1]
              and s[2] <= parent[2]]
    return [s for s in inside
            if not any(o is not s and o[1] <= s[1] and s[2] <= o[2]
                       for o in inside)]


def test_flush_writes_the_span_tree_with_its_args(tmp_path):
    """flush ⊃ chunk ⊃ {pad, state, dispatch, wait, fetch}, then the
    chunks' concatenate and the answers' fetch; ``flush_id`` per flush,
    ``rows``/``padded`` the bucket, ``cold`` 1 then 0 for a repeated
    shape, ``rebuilt`` 1 only where a segment was uploaded."""
    idx = _index()
    idx._device_state()  # trained before the trace
    eng = QueryEngine(idx, EngineConfig(k=5, min_batch=8, max_batch=16))
    q = _queries(40)
    with jax.profiler.trace(str(tmp_path)):
        for i, m in enumerate((5, 5, 20, 5)):  # 20: chunks of 16 and 4
            if i == 3:  # a delta segment to upload
                idx.insert(np.arange(N, N + 3), _queries(3, seed=2))
            for j in range(m):
                eng.submit(j, q[j])
            eng.flush()
    spans = _knn_spans(tmp_path)
    flushes = [s for s in spans if s[0] == "knn.flush"]
    assert [f[3] for f in flushes] == [{"flush_id": 1, "rows": 5},
                                       {"flush_id": 2, "rows": 5},
                                       {"flush_id": 3, "rows": 20},
                                       {"flush_id": 4, "rows": 5}]
    chunks = []
    for f in flushes:
        kids = _children(spans, f)
        assert [c[0] for c in kids][-2:] == ["knn.fetch", "knn.fetch"]
        assert {c[0] for c in kids[:-2]} == {"knn.chunk"}
        for c in kids[:-2]:
            assert [g[0] for g in _children(spans, c)] == [
                "knn.pad", "knn.state", "knn.dispatch", "knn.wait",
                "knn.fetch"]
            chunks.append(c)
    assert [c[3] for c in chunks] == [
        {"rows": 5, "padded": 8, "cold": 1},
        {"rows": 5, "padded": 8, "cold": 0},
        {"rows": 16, "padded": 16, "cold": 1},
        {"rows": 4, "padded": 8, "cold": 0},
        {"rows": 5, "padded": 8, "cold": 1}]  # the delta is a new shape
    rebuilt = [s[3]["rebuilt"] for s in spans if s[0] == "knn.state"]
    assert rebuilt == [0, 0, 0, 0, 1]


def test_the_meter_times_the_chunk_span(tmp_path):
    """The meter's interval is the ``knn.chunk`` span, pad and fetch
    included: a slow shape-key lookup shows in both."""

    class SlowSignature:
        def __init__(self, index):
            self._index = index
            self.dim = index.dim

        def shape_signature(self, k):
            time.sleep(0.03)
            return self._index.shape_signature(k)

        def search(self, q, k):
            return self._index.search(q, k)

    eng = QueryEngine(SlowSignature(_index()), EngineConfig(k=5))
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(3):
            eng.search(_queries(8))
    chunks = [s for s in _knn_spans(tmp_path) if s[0] == "knn.chunk"]
    metered = eng.meter._compile_spans + eng.meter._spans  # compile first
    assert len(chunks) == len(metered) == 3
    for c, (s, e) in zip(chunks, metered):
        assert e - s >= 0.03
        assert e - s == pytest.approx((c[2] - c[1]) * 1e-9, abs=2e-3)


def test_qps_divides_by_wall_time_without_compile_batches():
    """Host time between metered batches counts; a compile batch inside
    that time does not."""
    m = ServingMeter()
    m.record(10, 1.0, end=1.0)  # [0, 1]
    m.record(7, 0.5, compile_batch=True, end=2.0)  # [1.5, 2], excluded
    m.record(10, 1.0, end=4.0)  # [3, 4]
    assert m.qps() == pytest.approx(20 / 3.5)
    assert m.summary()["compile_batches"] == 1
    assert np.isnan(ServingMeter().qps())


@pytest.mark.parametrize("impl", ["jnp", "fused"])
def test_the_ivfpq_search_program_names_each_stage(impl):
    """Each stage scope is in the lowered program's op locations (the fused
    scan's probe-union list exists only on ``impl="fused"``)."""
    idx = _index(impl)
    dev = idx._device_state()
    vecs, live, ids = dev["main"]
    cb, codes = dev["main_pq"]
    low = I._segment_candidates_ivfpq.lower(
        jnp.asarray(_queries(8)), vecs, dev["main_ivf"], cb, codes, live,
        ids, k_out=8, nprobe=4, overfetch=4, distance="sqeuclidean",
        impl=impl)
    found = set(re.findall(r"knn\.[a-z_]+", low.as_text(debug_info=True)))
    want = set(STAGES) - ({"knn.probe_union"} if impl == "jnp" else set())
    assert want <= found, want - found


def test_results_are_bit_identical_with_a_profiler_session_open(tmp_path):
    q = _queries(24, seed=3)
    plain = QueryEngine(_index(), EngineConfig(k=5, max_batch=16)).search(q)
    with jax.profiler.trace(str(tmp_path)):
        traced = QueryEngine(_index(),
                             EngineConfig(k=5, max_batch=16)).search(q)
    np.testing.assert_array_equal(np.asarray(plain.ids),
                                  np.asarray(traced.ids))
    np.testing.assert_array_equal(np.asarray(plain.distances),
                                  np.asarray(traced.distances))
