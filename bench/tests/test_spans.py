"""The program's spans and stage scopes as ``bench/spans.py`` reads them:
interval arithmetic, a hand-built trace with known answers, and small
traces recorded on the chip (``data/``)."""
import gzip
import os
import types

import pytest

from bench import spans
from bench import trace as tr

US = 1_000_000  # picoseconds in a microsecond
DATA = os.path.join(os.path.dirname(__file__), "data")
ENGINE = os.path.join(DATA, "ivfpq_engine_1chip.xplane.pb.gz")
ALLPAIRS = os.path.join(DATA, "allpairs_4096_1chip.xplane.pb")


def S(name, start, end, **args):
    return spans.Span(name, start, end, args)


def test_innermost_labels_each_piece_with_the_deepest_open_span():
    a = S("knn.flush", 0, 100)
    b = S("knn.chunk", 10, 90)
    c = S("knn.wait", 20, 50)
    d = S("knn.flush", 120, 130)
    got = [(s, e, sp.name) for s, e, sp in spans.innermost([d, c, a, b])]
    assert got == [(0, 10, "knn.flush"), (10, 20, "knn.chunk"),
                   (20, 50, "knn.wait"), (50, 90, "knn.chunk"),
                   (90, 100, "knn.flush"), (120, 130, "knn.flush")]


def test_intersect_keeps_the_labels_of_the_second_list():
    idle = [(0, 15), (45, 60), (95, 125)]
    pieces = [(0, 10, "x"), (10, 50, "y"), (90, 100, "z"), (120, 130, "w")]
    assert spans.intersect(idle, pieces) == [
        (0, 10, "x"), (10, 15, "y"), (45, 50, "y"), (95, 100, "z"),
        (120, 125, "w")]
    assert spans.intersect([], pieces) == []


def test_stage_is_the_innermost_knn_component():
    assert spans.stage("jit(f)/knn.scan/jit(g)/knn.lut/dot:") == "knn.lut"
    assert spans.stage("jit(f)/jit(fused_knn)/reduce_sum:") == spans.NO_STAGE
    assert spans.stage(None) == spans.NO_STAGE


def _xspace():
    """Text proto of a one-chip XSpace: two flushes of the engine's spans
    and their device ops (times in us)."""
    host = [("window", 0, 1000, {}),
            ("knn.flush", 100, 400, {"flush_id": 1, "rows": 64}),
            ("knn.chunk", 110, 370, {"rows": 64, "padded": 64, "cold": 0}),
            ("knn.pad", 110, 20, {}), ("knn.state", 130, 10, {"rebuilt": 0}),
            ("knn.dispatch", 140, 60, {}), ("knn.wait", 200, 250, {}),
            ("knn.fetch", 450, 30, {}), ("knn.fetch", 480, 20, {}),
            ("knn.flush", 600, 300, {"flush_id": 2, "rows": 64}),
            ("knn.chunk", 600, 280, {"rows": 64, "padded": 64, "cold": 0}),
            ("knn.pad", 600, 20, {}), ("knn.dispatch", 620, 40, {}),
            ("knn.wait", 660, 190, {}), ("knn.fetch", 850, 30, {}),
            ("$engine.py:1 flush", 100, 400, {})]
    dev = [("%fusion.1 = pred[8] fusion()", 150, 60,
            "jit(s)/knn.live_mask/gather:"),
           ("%pq_scan.1 = f32[8] custom-call()", 210, 230,
            "jit(s)/jit(pq_scan)/knn.scan/pallas_call:"),
           ("%fusion.3 = f32[8] fusion()", 440, 5, None),
           ("%fusion.1 = pred[8] fusion()", 640, 60,
            "jit(s)/knn.live_mask/gather:"),
           ("%pq_scan.1 = f32[8] custom-call()", 700, 140,
            "jit(s)/jit(pq_scan)/knn.scan/pallas_call:")]
    stat_names = ["tf_op", "flush_id", "rows", "padded", "cold", "rebuilt",
                  "jit(s)/knn.live_mask/gather:"]

    def plane(pid, name, line, events, meta):
        sm = "".join(f'stat_metadata {{ key: {i} value {{ id: {i} '
                     f'name: "{n}" }} }}\n'
                     for i, n in enumerate(stat_names, 1))
        return (f'planes {{ id: {pid} name: "{name}"\n'
                f'lines {{ id: 1 name: "{line}" timestamp_ns: 0\n{events}}}'
                f"\n{meta}{sm}}}\n")

    names = sorted({h[0] for h in host})
    hev = "".join(
        f"events {{ metadata_id: {names.index(n) + 1} offset_ps: {s * US} "
        f"duration_ps: {d * US} "
        + "".join(f"stats {{ metadata_id: {stat_names.index(k) + 1} "
                  f"int64_value: {v} }} " for k, v in a.items()) + "}\n"
        for n, s, d, a in host)
    hmeta = "".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                    f'name: "{n}" }} }}\n' for i, n in enumerate(names, 1))
    ops = sorted({(o[0], o[3]) for o in dev}, key=str)
    dev_ev = "".join(
        f"events {{ metadata_id: {ops.index((n, p)) + 1} offset_ps: "
        f"{s * US} duration_ps: {d * US} }}\n" for n, s, d, p in dev)

    def op_stats(p):
        if p is None:
            return ""
        if p in stat_names:  # kept as a reference to a stat's name
            return (f"stats {{ metadata_id: 1 ref_value: "
                    f"{stat_names.index(p) + 1} }}")
        return f'stats {{ metadata_id: 1 str_value: "{p}" }}'

    dmeta = "".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                    f'name: "{n}" {op_stats(p)} }} }}\n'
                    for i, (n, p) in enumerate(ops, 1))
    return (plane(1, "/device:TPU:0", "XLA Ops", dev_ev, dmeta)
            + plane(2, "/host:CPU", "python", hev, hmeta))


@pytest.fixture
def synthetic(tmp_path):
    from jax.profiler import ProfileData

    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(_xspace()))
    return str(path)


def test_the_wire_reader_finds_each_ops_tf_op(synthetic):
    with open(synthetic, "rb") as f:
        got = spans.tf_ops(f.read())
    assert got == {"/device:TPU:0": {
        "%fusion.1 = pred[8] fusion()": "jit(s)/knn.live_mask/gather:",
        "%pq_scan.1 = f32[8] custom-call()":
            "jit(s)/jit(pq_scan)/knn.scan/pallas_call:"}}


def test_readers_on_a_hand_built_trace(synthetic):
    """Busy [150, 445] and [640, 840] us.  Idle under knn spans: [100, 150]
    (flush 10, pad 20, state 10, dispatch 10), [445, 500] (wait 5, fetch 30
    + 20), [600, 640] (pad 20, dispatch 20), [840, 900] (wait 10, fetch 30,
    flush 20): 205 of 1000 us.  Flush self time 400 - 250 and 300 - 190 us.
    Live mask 120 of 495 us busy."""
    sp = spans.load(synthetic)
    assert [s.args for s in sp.spans("knn.flush")] == [
        {"flush_id": 1, "rows": 64}, {"flush_id": 2, "rows": 64}]
    assert [o.stage for o in sp.ops] == [
        "knn.live_mask", "knn.scan", spans.NO_STAGE, "knn.live_mask",
        "knn.scan"]
    assert [o.name for o in sp.ops][:2] == ["fusion.1", "pq_scan.1"]
    assert spans.engine_idle_share(sp) == pytest.approx(20.5)
    assert spans.flush_self_ms(sp) == pytest.approx(0.13)
    assert spans.live_mask_share(sp) == pytest.approx(100 * 120 / 495)
    assert spans.stage_s(sp)["knn.scan"] == pytest.approx(370e-6)
    # The harness's own idle share holds the engine's.
    assert 100 * (1 - tr.mean_busy_s(tr.load(synthetic)) / 1e-3) == \
        pytest.approx(50.5)


def _ctx(path):
    return types.SimpleNamespace(trace=tr.load(path), xplane=path)


def _read(name, ctx):
    from bench import run as R

    return R.load_module(os.path.join(R.BENCH, "metrics", name + ".py"),
                         "t_" + name.replace(".", "_")).read(ctx)


def test_readers_find_nothing_in_the_allpairs_trace():
    """The all-pairs solve has no engine spans and no knn scopes."""
    ctx = _ctx(ALLPAIRS)
    for name in ("engine_idle_share", "flush_self_ms", "live_mask_share"):
        for cell in ("batch", "poisson"):
            assert _read(f"{name}.{cell}", ctx) is None


def test_readers_return_none_in_an_untraced_run():
    ctx = types.SimpleNamespace(trace=None)
    assert spans.of(ctx) is None
    assert _read("engine_idle_share.batch", ctx) is None


@pytest.fixture(scope="module")
def engine(tmp_path_factory):
    """Four flushes (64, 64, 40 and 100 queries; max_batch 64, so five
    chunks) of a warm 2^16-row, 256-cell IVF-PQ index, recorded on a TPU v5
    lite by ``python bench/spans.py record`` and kept gzipped."""
    path = tmp_path_factory.mktemp("engine") / "engine.xplane.pb"
    with gzip.open(ENGINE, "rb") as f:
        path.write_bytes(f.read())
    return str(path)


def test_readers_on_the_recorded_engine_trace(engine):
    """Checked by hand on a 100 ns grid of the same trace: idle under a
    knn span 12.7197 % of the window; flush self times 4.180, 3.843,
    4.928 and 8.019 ms; live-mask ops 17.5811 % of busy time."""
    ctx = _ctx(engine)
    sp = spans.of(ctx)
    assert [f.args for f in sp.spans("knn.flush")] == [
        {"flush_id": 1, "rows": 64}, {"flush_id": 2, "rows": 64},
        {"flush_id": 3, "rows": 40}, {"flush_id": 4, "rows": 100}]
    assert [c.args["rows"] for c in sp.spans("knn.chunk")] == [
        64, 64, 40, 64, 36]
    assert {c.args["padded"] for c in sp.spans("knn.chunk")} == {64}
    engine_idle = _read("engine_idle_share.batch", ctx)
    live = _read("live_mask_share.batch", ctx)
    assert engine_idle == pytest.approx(12.7197, abs=2e-3)
    assert _read("flush_self_ms.batch", ctx) == pytest.approx(
        (4.18022 + 4.92847) / 2, abs=1e-4)
    assert live == pytest.approx(17.5811, abs=2e-3)
    assert engine_idle <= _read("idle_share.batch", ctx)
    assert live <= _read("glue_share.batch", ctx)
    by = spans.stage_s(sp)
    assert sum(v for k, v in by.items() if k != spans.NO_STAGE) >= \
        0.99 * sum(by.values())


def test_host_spans_and_device_ops_share_one_clock(engine):
    """Each chunk's scan kernel starts after its ``knn.dispatch`` starts and
    runs inside its ``knn.wait``, which ends after the last op."""
    sp = spans.load(engine)
    scans = [o for o in sp.ops if o.stage == "knn.scan"
             and o.name.startswith("pq_scan")]
    chunks = sp.spans("knn.chunk")
    assert len(scans) == len(chunks) == 5
    for c, op in zip(chunks, scans):
        inside = [s for s in sp.host if c.start <= s.start and s.end <= c.end]
        dispatch = next(s for s in inside if s.name == "knn.dispatch")
        wait = next(s for s in inside if s.name == "knn.wait")
        assert dispatch.start < op.start < op.end < wait.end
        assert op.end > wait.start
