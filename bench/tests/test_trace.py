"""The trace reduction: hand-built traces with known answers, and a small
trace recorded on the chip (``data/``)."""
import glob
import os

import pytest

from bench import trace as tr

US = 1_000_000  # picoseconds in a microsecond


def _xspace(devices, host):
    """Text proto of an XSpace: ``devices`` {plane: [(name, start_us,
    dur_us)]}, ``host`` [(name, start_us, dur_us)]."""
    planes = []
    for pid, (plane, evs) in enumerate(list(devices.items()) +
                                       [("/host:CPU", host)], 1):
        names = sorted({e[0] for e in evs})
        meta = "".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                       f'name: "{n}" }} }}\n' for i, n in enumerate(names, 1))
        events = "".join(
            f"events {{ metadata_id: {names.index(n) + 1} "
            f"offset_ps: {s * US} duration_ps: {d * US} }}\n"
            for n, s, d in evs)
        line = "XLA Ops" if plane != "/host:CPU" else "python"
        planes.append(f'planes {{ id: {pid} name: "{plane}"\n'
                      f'lines {{ id: 1 name: "{line}" timestamp_ns: 0\n'
                      f"{events}}}\n{meta}}}\n")
    return "".join(planes)


@pytest.fixture
def synthetic(tmp_path):
    from jax.profiler import ProfileData

    dev0 = [("fused_knn.1", 0, 400), ("fusion.2", 400, 100),
            ("collective-permute-start", 500, 50),
            ("fused_knn.1", 520, 200),  # overlaps the collective by 30
            ("collective-permute-done", 800, 100), ("fusion.2", 950, 50)]
    dev1 = [("fused_knn.1", 100, 600), ("all-reduce.7", 900, 100)]
    host = [("window", 0, 1000), ("solve", 0, 40), ("block", 40, 700),
            ("flush", 720, 80), ("generate", 5000, 10)]
    txt = _xspace({"/device:TPU:0": dev0, "/device:TPU:1": dev1}, host)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(txt))
    return tr.load(str(path))


def test_busy_kernels_and_window(synthetic):
    r = synthetic
    assert r.window_s == pytest.approx(1000e-6)
    busy = tr.busy_s(r)
    # dev0: [0,720) and [800,900) and [950,1000) -> 870 us; dev1: 700 us
    assert busy["/device:TPU:0"] == pytest.approx(870e-6)
    assert busy["/device:TPU:1"] == pytest.approx(700e-6)
    assert tr.mean_busy_s(r) == pytest.approx(785e-6)
    assert tr.kernel_s(r, "fused_knn") == pytest.approx((400 + 200 + 600)
                                                        * 1e-6)
    assert tr.named_kernel_s(r, ("fused_knn", "pq_scan")) == pytest.approx(
        1200e-6)


def test_collective_time_that_no_compute_hides(synthetic):
    # dev0: start [500,550) has [520,550) under the kernel -> 20 exposed,
    # done [800,900) -> 100; dev1: all-reduce 100.  Mean 110 us.
    assert tr.collective_exposed_s(synthetic) == pytest.approx(110e-6)


def test_idle_gaps_are_labelled_by_the_open_host_span(synthetic):
    gaps = tr.idle_gaps(synthetic)
    # dev0 idles in [720,800) under "flush" and [900,950) under no span.
    assert gaps == [["flush", pytest.approx(80e-6)],
                    ["host", pytest.approx(50e-6)]]


def test_device_ops_are_the_largest_mean_over_devices(synthetic):
    ops = dict(tr.device_ops(synthetic))
    assert ops["fused_knn.1"] == pytest.approx(600e-6)
    assert list(dict(tr.device_ops(synthetic, top=1))) == ["fused_knn.1"]


def test_a_trace_without_the_window_span_is_refused(tmp_path):
    from jax.profiler import ProfileData

    txt = _xspace({"/device:TPU:0": [("x", 0, 1)]}, [("solve", 0, 1)])
    p = tmp_path / "n.xplane.pb"
    p.write_bytes(ProfileData.text_proto_to_serialized_xspace(txt))
    with pytest.raises(ValueError):
        tr.load(str(p))


CHIP = sorted(glob.glob(os.path.join(os.path.dirname(__file__), "data",
                                     "*.xplane.pb")))


@pytest.mark.parametrize("path", CHIP, ids=os.path.basename)
def test_recorded_chip_trace(path):
    """Two all-pairs solves at n = 4096 inside the harness's spans, and on
    four chips one ring solve, recorded on TPU v5 lite chips
    (``bench/probe.py trace-fixture``)."""
    r = tr.load(path)
    assert r.devices and all(k.startswith("/device:TPU:") for k in r.devices)
    assert 0 < tr.mean_busy_s(r) < r.window_s
    k = tr.kernel_s(r, "fused_knn")
    assert k > 0
    # The kernel is most of the busy time of a solve at this size.
    assert k <= sum(tr.busy_s(r).values()) + 1e-12
    gaps = dict(tr.idle_gaps(r))
    assert "flush" in gaps  # the 50 ms sleep under the flush span
    assert gaps["flush"] == pytest.approx(0.05, abs=0.02)
    if len(r.devices) > 1:  # the ring exchanges blocks between the chips
        assert any(tr.is_collective(e) for evs in r.devices.values()
                   for e in evs)
        assert 0 < tr.collective_exposed_s(r) < r.window_s


def test_allpairs_readers_on_the_recorded_chip_trace():
    """The solve's share of the peak over all busy time is above 0 and no
    higher than the kernel's share of its roofline, which stays under 100 %:
    the trace holds two solves at n = 4096, d = 256, k = 100."""
    import types

    from bench import run as R
    from bench import work

    path = [p for p in CHIP if "1chip" in p][0]
    ctx = types.SimpleNamespace(
        trace=tr.load(path), peaks=work.load_peaks("TPU v5 lite"),
        work={"solve": work.allpairs(4096, 256, 100).scaled(2)})

    def read(name):
        return R.load_module(os.path.join(R.BENCH, "metrics", name + ".py"),
                             "t_" + name.replace(".", "_")).read(ctx)

    mfu, roof = read("mfu.allpairs"), read("fused_knn_roofline.allpairs")
    assert 0 < mfu <= roof < 100
