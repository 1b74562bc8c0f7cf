"""repro.serving.lifecycle: WAL durability, torn-tail recovery, epoch handoff.

The contract under test (DESIGN.md §16):

* every mutation ack implies durability — the record is fsynced into the
  snapshot's ``journal.bin`` before ``insert``/``upsert``/``delete`` returns,
  and ``recover()`` replays every acked record after ANY crash point,
  including a SIGKILL mid-append (the torn in-flight frame is dropped at the
  last valid boundary; it was never acked);
* any byte-length crash prefix of the journal restores to EXACTLY the state
  after the last fully-acked record (the hypothesis property below);
* mid-file corruption is still refused — leniency applies only to the
  genuinely in-flight tail;
* ``compact()`` trains epoch N+1 in a background worker and the handed-off
  index is BIT-identical to a synchronous compact; no search ever enters
  ``core.kmeans.lloyd`` on the serving thread (tripwire-enforced);
* a mutation past ``delta_budget`` raises ``BackpressureError`` before
  anything is applied or logged.
"""
import os
import shutil
import signal
import struct
import subprocess
import sys
import tempfile
import threading
import time

import hypothesis
import hypothesis.strategies as st
import numpy as np
import pytest

from repro.serving import (
    BackpressureError,
    EngineConfig,
    LifecycleConfig,
    LifecycleIndex,
    QueryEngine,
    RetrievalIndex,
    SnapshotError,
    WalWriter,
)
from repro.serving.snapshot import _JOURNAL, _JOURNAL_MAGIC_V1, read_manifest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CONFIGS = {
    "flat": {},
    "int8": {"scan_dtype": "int8"},
    "ivf": {"ivf_cells": 16, "nprobe": 4},
    "ivfpq": {"ivf_cells": 16, "nprobe": 8, "pq_m": 8},
}


def _base_index(kw, n=512, d=32, seed=0):
    rng = np.random.default_rng(seed)
    vecs = rng.standard_normal((n, d)).astype(np.float32)
    idx = RetrievalIndex.build(np.arange(n), vecs, **kw)
    q = rng.standard_normal((16, d)).astype(np.float32)
    return idx, q


def _churn(lc, n=512, d=32, seed=1):
    """Three acked batches: bulk insert, overlapping upsert, delete."""
    rng = np.random.default_rng(seed)
    lc.insert(np.arange(n, n + 32),
              rng.standard_normal((32, d)).astype(np.float32))
    # Overlap re-upserts inside the delta: dead + live rows under one id.
    lc.upsert(np.arange(n + 28, n + 40),
              rng.standard_normal((12, d)).astype(np.float32))
    lc.delete(np.arange(0, n, 19))


def _assert_bit_identical(a, b):
    np.testing.assert_array_equal(np.asarray(a.ids), np.asarray(b.ids))
    np.testing.assert_array_equal(np.asarray(a.distances),
                                  np.asarray(b.distances))


# -- WAL durability round-trip ------------------------------------------------


@pytest.mark.parametrize("name", list(CONFIGS))
def test_wal_recover_bit_identical(name, tmp_path):
    idx, q = _base_index(CONFIGS[name])
    snap = str(tmp_path / name)
    lc = LifecycleIndex.attach(idx, LifecycleConfig(snapshot_dir=snap))
    _churn(lc)
    want = lc.search(q, 10)
    want_delta = (int(idx._delta_n), idx._delta_live[: idx._delta_n].copy())
    lc.close()

    lc2, rec = LifecycleIndex.recover(LifecycleConfig(snapshot_dir=snap))
    assert rec.wal and rec.torn_bytes == 0
    assert rec.tail_records == 3  # every acked batch survived, none stamped
    got = lc2.index
    assert int(got._delta_n) == want_delta[0]
    np.testing.assert_array_equal(got._delta_live[: got._delta_n],
                                  want_delta[1])
    _assert_bit_identical(want, lc2.search(q, 10))
    lc2.close()


def test_vectorized_replay_rebuilds_exact_delta_state(tmp_path):
    """Bulk ADD replays as ONE vectorized append with identical internals."""
    idx, q = _base_index(CONFIGS["flat"])
    # Dead rows inside the saved delta journal: live-mask bits in the record.
    rng = np.random.default_rng(7)
    idx.upsert(np.arange(512, 512 + 48),
               rng.standard_normal((48, 32)).astype(np.float32))
    idx.upsert(np.arange(512, 512 + 6),
               rng.standard_normal((6, 32)).astype(np.float32))
    idx.delete([512 + 2, 512 + 40])
    snap = str(tmp_path / "snap")
    idx.save(snap, wal=True)
    got = RetrievalIndex.restore(snap)
    assert int(got._delta_n) == int(idx._delta_n)
    np.testing.assert_array_equal(got._delta_live[: got._delta_n],
                                  idx._delta_live[: idx._delta_n])
    assert got._loc == idx._loc
    _assert_bit_identical(idx.search(q, 10), got.search(q, 10))


# -- torn tail vs corruption --------------------------------------------------


def test_torn_tail_truncated_and_replay_resumes(tmp_path):
    idx, q = _base_index(CONFIGS["flat"])
    snap = str(tmp_path / "snap")
    lc = LifecycleIndex.attach(idx, LifecycleConfig(snapshot_dir=snap))
    _churn(lc)
    want = lc.search(q, 10)
    lc.close()
    journal = os.path.join(snap, _JOURNAL)
    # Crash mid-append: a frame header claiming 1 MiB with 40 payload bytes.
    with open(journal, "ab") as f:
        f.write(struct.pack("<4sII", b"ADD\0", 1 << 20, 0) + b"\0" * 40)

    lc2, rec = LifecycleIndex.recover(LifecycleConfig(snapshot_dir=snap))
    assert rec.torn_bytes == 12 + 40
    assert rec.tail_records == 3  # all acked records replayed
    # The torn frame is physically gone: the journal is back to a verified
    # frame boundary and appending resumes from there.
    assert os.path.getsize(journal) == rec.valid_bytes
    _assert_bit_identical(want, lc2.search(q, 10))
    lc2.insert([9000], np.ones((1, 32), np.float32))
    lc2.close()
    lc3, rec3 = LifecycleIndex.recover(LifecycleConfig(snapshot_dir=snap))
    assert rec3.torn_bytes == 0 and rec3.tail_records == 4
    assert 9000 in lc3
    lc3.close()


def test_corruption_inside_stamped_prefix_refused(tmp_path):
    idx, _ = _base_index(CONFIGS["flat"])
    rng = np.random.default_rng(2)
    idx.upsert(np.arange(512, 512 + 16),
               rng.standard_normal((16, 32)).astype(np.float32))
    snap = str(tmp_path / "snap")
    lc = LifecycleIndex.attach(idx, LifecycleConfig(snapshot_dir=snap))
    lc.close()
    journal = os.path.join(snap, _JOURNAL)
    stamp = read_manifest(snap, verify=False)["files"][_JOURNAL]["bytes"]
    assert stamp > 32  # the attach image journals the delta rows
    with open(journal, "r+b") as f:
        f.seek(stamp - 5)
        byte = f.read(1)
        f.seek(stamp - 5)
        f.write(bytes([byte[0] ^ 0xFF]))
    with pytest.raises(SnapshotError):
        LifecycleIndex.recover(LifecycleConfig(snapshot_dir=snap))


def test_corruption_mid_tail_refused_not_torn(tmp_path):
    """A CRC-failing tail frame WITH data after it is damage, not a crash."""
    idx, _ = _base_index(CONFIGS["flat"])
    snap = str(tmp_path / "snap")
    lc = LifecycleIndex.attach(idx, LifecycleConfig(snapshot_dir=snap))
    stamp = lc._wal.tell()
    lc.insert([600], np.ones((1, 32), np.float32))
    end1 = lc._wal.tell()
    lc.insert([601], np.ones((1, 32), np.float32))
    lc.close()
    journal = os.path.join(snap, _JOURNAL)
    with open(journal, "r+b") as f:
        f.seek(end1 - 3)  # inside frame 1's payload; frame 2 follows
        byte = f.read(1)
        f.seek(end1 - 3)
        f.write(bytes([byte[0] ^ 0xFF]))
    with pytest.raises(SnapshotError, match="CRC mismatch"):
        LifecycleIndex.recover(LifecycleConfig(snapshot_dir=snap))
    assert stamp < end1  # sanity: the flip landed past the stamp


def test_journal_shorter_than_stamp_refused(tmp_path):
    idx, _ = _base_index(CONFIGS["flat"])
    snap = str(tmp_path / "snap")
    lc = LifecycleIndex.attach(idx, LifecycleConfig(snapshot_dir=snap))
    lc.close()
    stamp = read_manifest(snap, verify=False)["files"][_JOURNAL]["bytes"]
    with open(os.path.join(snap, _JOURNAL), "r+b") as f:
        f.truncate(max(0, stamp - 1))
    with pytest.raises(SnapshotError):
        LifecycleIndex.recover(LifecycleConfig(snapshot_dir=snap))


# -- property: every crash prefix restores the acked prefix -------------------

_N_ACKS = 8


@pytest.fixture(scope="module")
def wal_history(tmp_path_factory):
    """One journaled run: WAL boundaries + expected state after each ack."""
    snap = str(tmp_path_factory.mktemp("walprop") / "snap")
    idx, q = _base_index(CONFIGS["flat"], n=256, d=16, seed=3)
    lc = LifecycleIndex.attach(idx, LifecycleConfig(snapshot_dir=snap))
    rng = np.random.default_rng(4)

    def state():
        r = lc.search(q, 8)
        return (int(lc.index._delta_n), np.asarray(r.distances).copy(),
                np.asarray(r.ids).copy())

    boundaries, states, nid = [lc._wal.tell()], [state()], 256
    for step in range(_N_ACKS):
        kind = step % 3
        if kind == 0:
            lc.insert(np.arange(nid, nid + 5),
                      rng.standard_normal((5, 16)).astype(np.float32))
            nid += 5
        elif kind == 1:
            lc.upsert(np.arange(nid - 3, nid + 2),
                      rng.standard_normal((5, 16)).astype(np.float32))
            nid += 2
        else:
            lc.delete(rng.integers(0, 256, size=4))
        boundaries.append(lc._wal.tell())
        states.append(state())
    lc.close()
    return snap, q, boundaries, states


@hypothesis.settings(max_examples=20, deadline=None)
@hypothesis.given(i=st.integers(0, _N_ACKS), extra=st.integers(0, 1 << 30))
def test_any_crash_prefix_restores_acked_prefix(wal_history, i, extra):
    """Truncating the journal anywhere in [ack_i, ack_{i+1}) recovers state i.

    At a frame boundary (extra lands on 0) that is the exact acked-prefix
    restore; strictly inside the next frame it is a genuine torn tail — a
    literal crash prefix of the real byte stream — and the in-flight record
    must vanish without disturbing the acked prefix.
    """
    snap, q, boundaries, states = wal_history
    if i == _N_ACKS:
        cut = boundaries[i]
    else:
        cut = boundaries[i] + extra % (boundaries[i + 1] - boundaries[i])
    work = tempfile.mkdtemp()
    try:
        dst = os.path.join(work, "snap")
        shutil.copytree(snap, dst)
        with open(os.path.join(dst, _JOURNAL), "r+b") as f:
            f.truncate(cut)
        lc, rec = LifecycleIndex.recover(LifecycleConfig(snapshot_dir=dst))
        try:
            assert rec.tail_records == i
            assert rec.torn_bytes == cut - boundaries[i]
            delta_n, want_v, want_i = states[i]
            assert int(lc.index._delta_n) == delta_n
            got = lc.search(q, 8)
            np.testing.assert_array_equal(np.asarray(got.ids), want_i)
            np.testing.assert_array_equal(np.asarray(got.distances), want_v)
        finally:
            lc.close()
    finally:
        shutil.rmtree(work, ignore_errors=True)


# -- kill -9 mid-ingest -------------------------------------------------------

_KILL9_CHILD = """
import sys
import numpy as np
from repro.serving import LifecycleConfig, LifecycleIndex, RetrievalIndex

snap = sys.argv[1]
rng = np.random.default_rng(0)
vecs = rng.standard_normal((256, 32)).astype(np.float32)
idx = RetrievalIndex.build(np.arange(256), vecs)
lc = LifecycleIndex.attach(idx, LifecycleConfig(snapshot_dir=snap))
nid = 256
for i in range(200):
    lc.insert(np.arange(nid, nid + 4),
              rng.standard_normal((4, 32)).astype(np.float32))
    nid += 4
    print(f"ACK {i}", flush=True)  # printed strictly AFTER the fsync ack
"""


def test_kill9_mid_ingest_loses_no_acked_write(tmp_path):
    """SIGKILL a journaling writer; recovery == a never-crashed twin.

    The child prints ``ACK i`` only after insert ``i``'s fsync returned, so
    every ack the parent observes MUST survive.  The recovered index must
    also be bit-identical to a twin that applied exactly the replayed prefix
    of the same deterministic schedule and never crashed.
    """
    snap = str(tmp_path / "snap")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.Popen(
        [sys.executable, "-c", _KILL9_CHILD, snap],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, env=env)
    acked = []
    try:
        deadline = time.monotonic() + 300
        while len(acked) < 3:
            line = proc.stdout.readline()
            if not line:
                break
            if line.startswith("ACK "):
                acked.append(int(line.split()[1]))
            assert time.monotonic() < deadline, "child produced no acks"
        proc.kill()  # SIGKILL: no atexit, no flush, no close
        proc.wait(timeout=60)
    finally:
        if proc.poll() is None:
            proc.kill()
        proc.stdout.close()
        proc.stderr.close()
    assert acked and acked == list(range(len(acked)))

    lc, rec = LifecycleIndex.recover(LifecycleConfig(snapshot_dir=snap))
    r = rec.tail_records
    assert r >= len(acked), (r, acked)  # no acked write lost

    # Never-crashed twin: replay the same deterministic schedule prefix.
    rng = np.random.default_rng(0)
    vecs = rng.standard_normal((256, 32)).astype(np.float32)
    twin = RetrievalIndex.build(np.arange(256), vecs)
    nid = 256
    for _ in range(r):
        twin.insert(np.arange(nid, nid + 4),
                    rng.standard_normal((4, 32)).astype(np.float32))
        nid += 4
    assert len(lc) == len(twin)
    q = np.random.default_rng(99).standard_normal((24, 32)).astype(np.float32)
    _assert_bit_identical(twin.search(q, 10), lc.search(q, 10))
    lc.close()


def test_kill9_crash_restart_with_sigkill_signal(tmp_path):
    """Same kill-9 recovery through the POSIX signal (not Popen.kill)."""
    snap = str(tmp_path / "snap")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.Popen(
        [sys.executable, "-c", _KILL9_CHILD, snap],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, env=env)
    try:
        line = proc.stdout.readline()
        while line and not line.startswith("ACK 1"):
            line = proc.stdout.readline()
        assert line, "child never acked"
        os.kill(proc.pid, signal.SIGKILL)
        proc.wait(timeout=60)
    finally:
        if proc.poll() is None:
            proc.kill()
        proc.stdout.close()
        proc.stderr.close()
    lc, rec = LifecycleIndex.recover(LifecycleConfig(snapshot_dir=snap))
    assert rec.tail_records >= 2  # acks 0 and 1 were both observed
    assert len(lc) == 256 + 4 * rec.tail_records
    lc.close()


# -- background retrain + epoch handoff ---------------------------------------


@pytest.mark.parametrize("name", ["ivf", "ivfpq"])
def test_background_handoff_bit_identical_to_sync_compact(name, tmp_path):
    idx, q = _base_index(CONFIGS[name])
    twin, _ = _base_index(CONFIGS[name])  # same seed: identical build
    snap = str(tmp_path / name)
    lc = LifecycleIndex.attach(idx, LifecycleConfig(snapshot_dir=snap))
    _churn(lc)
    rng = np.random.default_rng(1)
    twin.insert(np.arange(512, 512 + 32),
                rng.standard_normal((32, 32)).astype(np.float32))
    twin.upsert(np.arange(512 + 28, 512 + 40),
                rng.standard_normal((12, 32)).astype(np.float32))
    twin.delete(np.arange(0, 512, 19))

    twin.compact()  # blocking repack; first search trains synchronously
    want = twin.search(q, 10)
    lc.compact(wait=True)  # background worker trains, then swaps
    assert lc.stats()["epoch"] == twin._main_epoch
    assert lc.stats()["handoffs"] == 1
    _assert_bit_identical(want, lc.search(q, 10))
    lc.close()


def test_mutations_during_pending_window_survive_handoff(tmp_path):
    idx, q = _base_index(CONFIGS["ivf"])
    snap = str(tmp_path / "snap")
    lc = LifecycleIndex.attach(idx, LifecycleConfig(snapshot_dir=snap))
    _churn(lc)
    lc.compact()  # cut taken; worker training in the background
    # Post-cut mutations land on epoch N and the WAL...
    lc.insert([7001], np.full((1, 32), 0.5, np.float32))
    lc.delete([1])
    assert lc.finish_handoff(wait=True)
    # ...and must ride the handoff onto epoch N+1.
    assert 7001 in lc and 1 not in lc
    assert lc.stats()["delta_rows"] == 1  # just the post-cut insert
    want = lc.search(q, 10)
    lc.close()
    # Crash right after the swap: the new image + copied tail recover.
    lc2, rec = LifecycleIndex.recover(LifecycleConfig(snapshot_dir=snap))
    assert 7001 in lc2 and 1 not in lc2
    _assert_bit_identical(want, lc2.search(q, 10))
    lc2.close()


def test_serving_thread_never_trains(tmp_path, monkeypatch):
    """The Lloyd tripwire: handoff training happens OFF the serving thread."""
    import repro.core.kmeans as KM

    idx, q = _base_index(CONFIGS["ivf"])
    idx.search(q, 10)  # train the initial epoch before arming
    snap = str(tmp_path / "snap")
    lc = LifecycleIndex.attach(idx, LifecycleConfig(snapshot_dir=snap))

    real, calls = KM.lloyd, []

    def guard(*a, **kw):
        assert threading.current_thread() is not threading.main_thread(), (
            "kmeans.lloyd entered on the serving thread")
        calls.append(threading.current_thread().name)
        return real(*a, **kw)

    monkeypatch.setattr(KM, "lloyd", guard)
    # train_cells is jitted: a same-shape trace from an earlier test would
    # skip its Python body (and the guard) entirely — force a retrace.
    import jax

    jax.clear_caches()
    _churn(lc)
    lc.compact(wait=True)
    assert calls, "background worker never trained"
    lc.search(q, 10)  # steady-state serving after the swap
    lc.close()


def test_sync_train_tripwire_raises_instead_of_stalling(tmp_path):
    idx, q = _base_index(CONFIGS["ivf"])
    snap = str(tmp_path / "snap")
    lc = LifecycleIndex.attach(idx, LifecycleConfig(snapshot_dir=snap))
    # Bypass the lifecycle: a raw compact strands the epoch untrained, and
    # the next search would train synchronously — the tripwire fires.
    lc.index.compact()
    with pytest.raises(RuntimeError, match="tripwire"):
        lc.search(q, 10)
    lc.close()


def test_engine_swaps_ready_epoch_at_batch_boundary(tmp_path):
    idx, q = _base_index(CONFIGS["flat"])
    snap = str(tmp_path / "snap")
    lc = LifecycleIndex.attach(idx, LifecycleConfig(snapshot_dir=snap))
    eng = QueryEngine(lc, EngineConfig(k=8, min_batch=8, max_batch=64))
    eng.search(q, 8)
    _churn(lc)
    epoch0 = lc.stats()["epoch"]
    lc.compact()  # no wait: the swap must come from the engine hook
    deadline = time.monotonic() + 120
    while lc.stats()["state"] == "train":
        assert time.monotonic() < deadline, "worker never finished"
        time.sleep(0.01)
    assert lc.stats()["state"] == "handoff"
    assert lc.stats()["epoch"] == epoch0  # not swapped yet: no batch ran
    r = eng.search(q, 8)  # before_batch hook swaps, then the batch serves
    assert lc.stats()["state"] == "serve"
    assert lc.stats()["epoch"] == epoch0 + 1
    _assert_bit_identical(r, lc.search(q, 8))
    lc.close()


# -- admission control --------------------------------------------------------


def test_backpressure_applies_nothing_and_logs_nothing(tmp_path):
    idx, _ = _base_index(CONFIGS["flat"])
    snap = str(tmp_path / "snap")
    lc = LifecycleIndex.attach(
        idx, LifecycleConfig(snapshot_dir=snap, delta_budget=16))
    rng = np.random.default_rng(5)
    lc.insert(np.arange(512, 512 + 16),
              rng.standard_normal((16, 32)).astype(np.float32))
    tell0, delta0 = lc._wal.tell(), int(lc.index._delta_n)
    with pytest.raises(BackpressureError, match="budget"):
        lc.insert([9000], np.ones((1, 32), np.float32))
    assert lc._wal.tell() == tell0  # nothing logged
    assert int(lc.index._delta_n) == delta0  # nothing applied
    assert 9000 not in lc
    assert lc.stats()["rejected"] == 1
    lc.delete([512])  # deletes are always admitted: they free space
    lc.compact(wait=True)
    lc.insert([9000], np.ones((1, 32), np.float32))  # budget drained
    assert 9000 in lc
    lc.close()


# -- incremental checkpoint ---------------------------------------------------


def test_checkpoint_extends_stamp_without_rewriting_main(tmp_path):
    idx, q = _base_index(CONFIGS["flat"])
    snap = str(tmp_path / "snap")
    lc = LifecycleIndex.attach(idx, LifecycleConfig(snapshot_dir=snap))
    main = os.path.join(snap, "main.npz")
    st0 = os.stat(main)
    _churn(lc)
    lc.checkpoint()
    st1 = os.stat(main)
    assert (st0.st_mtime_ns, st0.st_size) == (st1.st_mtime_ns, st1.st_size)
    stamp = read_manifest(snap, verify=False)["files"][_JOURNAL]["bytes"]
    assert stamp == lc._wal.tell()  # the whole tail is now verified prefix
    want = lc.search(q, 10)
    lc.close()
    lc2, rec = LifecycleIndex.recover(LifecycleConfig(snapshot_dir=snap))
    assert rec.tail_records == 0 and rec.prefix_records >= 3
    _assert_bit_identical(want, lc2.search(q, 10))
    lc2.close()


def test_checkpoint_refuses_rebased_main(tmp_path):
    idx, _ = _base_index(CONFIGS["flat"])
    snap = str(tmp_path / "snap")
    lc = LifecycleIndex.attach(idx, LifecycleConfig(snapshot_dir=snap))
    lc._dirty_main = True  # the guard a sync compact arms mid-flight
    with pytest.raises(SnapshotError, match="full"):
        lc.checkpoint()
    lc.close()


# -- format upgrades ----------------------------------------------------------


def test_recover_upgrades_non_wal_snapshot(tmp_path):
    idx, q = _base_index(CONFIGS["flat"])
    snap = str(tmp_path / "snap")
    idx.save(snap)  # plain §Persistence image: no WAL marker
    assert not read_manifest(snap, verify=False).get("wal")
    lc, rec = LifecycleIndex.recover(LifecycleConfig(snapshot_dir=snap))
    assert not rec.wal  # forensics report what was found...
    assert read_manifest(snap, verify=False)["wal"]  # ...upgrade re-stamped
    lc.insert([9000], np.ones((1, 32), np.float32))
    want = lc.search(q, 10)
    lc.close()
    lc2, rec2 = LifecycleIndex.recover(LifecycleConfig(snapshot_dir=snap))
    assert rec2.wal and rec2.tail_records == 1
    _assert_bit_identical(want, lc2.search(q, 10))
    lc2.close()


def test_walwriter_refuses_v1_journal(tmp_path):
    path = str(tmp_path / "journal.bin")
    with open(path, "wb") as f:
        f.write(_JOURNAL_MAGIC_V1)
    with pytest.raises(SnapshotError, match="magic"):
        WalWriter(path)


# -- service integration ------------------------------------------------------


def test_service_lifecycle_end_to_end(tmp_path):
    import jax

    from repro.configs import registry as REG
    from repro.models.nn import split_params
    from repro.serving import ServiceConfig, TwoTowerRetrievalService

    arch = REG.get("two-tower-retrieval")
    cfg = arch.smoke_config()
    values, _ = split_params(arch.init_params(jax.random.PRNGKey(0), cfg))
    snap = str(tmp_path / "snap")
    sc = ServiceConfig(k=5, snapshot_dir=snap, wal=True, delta_budget=64)
    svc = TwoTowerRetrievalService(values, cfg, sc)

    rng = np.random.default_rng(1)
    n = 256
    fields = rng.integers(0, min(cfg.i_sizes()),
                          size=(n, cfg.n_item_fields)).astype(np.int32)
    svc.build_corpus(np.arange(n), fields)
    svc.enable_lifecycle()
    new_fields = rng.integers(0, min(cfg.i_sizes()),
                              size=(24, cfg.n_item_fields)).astype(np.int32)
    svc.ingest_items(np.arange(n, n + 24), new_fields)
    svc.delete_items(np.arange(0, n, 31))
    svc.compact(wait=True)
    assert svc.stats()["lifecycle"]["handoffs"] == 1
    ukeys = np.arange(7)
    ufields = rng.integers(0, min(cfg.u_sizes()),
                           size=(7, cfg.n_user_fields)).astype(np.int32)
    want_ids, want_scores = svc.recommend(ukeys, ufields)

    # Crash-restart: a fresh service recovers snapshot + WAL and serves
    # bit-identically.
    svc2 = TwoTowerRetrievalService(values, cfg, sc)
    rec = svc2.recover_lifecycle()
    assert rec.wal and rec.torn_bytes == 0
    got_ids, got_scores = svc2.recommend(ukeys, ufields)
    np.testing.assert_array_equal(want_ids, got_ids)
    np.testing.assert_array_equal(want_scores, got_scores)

    # Mismatched tower params must be refused, exactly as restore_index.
    values2, _ = split_params(arch.init_params(jax.random.PRNGKey(1), cfg))
    svc3 = TwoTowerRetrievalService(values2, cfg, sc)
    with pytest.raises(SnapshotError, match="different model"):
        svc3.recover_lifecycle()
