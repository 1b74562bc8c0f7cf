"""Process-isolated worker transport (DESIGN.md §15): wire + supervisor.

The contract under test:

* **no byte corruption survives the wire** — every malformed frame
  (truncated, torn, CRC-flipped, version-skewed, dtype-smuggling) raises
  ``WireError``, a subclass of ``TornResultError``, so a corrupt frame
  fails over exactly like a torn in-process reply and never reaches the
  merge (fuzz-pinned);
* **structured errors cross the process boundary as structure** — the
  serving exceptions round-trip with their cells/shard_ids/attempts
  context intact, and unknown types degrade to a tagged
  ``RemoteWorkerError`` instead of being misclassified;
* **the proc backend is bit-invisible** — ``workers="proc"`` serves bits
  identical to the in-process fleet (fp32 wire exact; bf16 wire idempotent
  with the bf16-wire merge);
* **real SIGKILL mid-batch is survivable at R=2** — one replica of every
  shard killed mid-stream yields bit-identical results and coverage 1.0,
  the corpses respawn from their snapshot images into PROBATION, and the
  respawned workers SERVE when traffic is forced onto them (the
  acceptance criterion);
* **deadlines bound real socket waits** — a slow worker's reply is
  abandoned at the socket deadline, its late reply is discarded by seq
  (never served), and the bounded in-flight queue refuses further calls
  with ``BackpressureError``;
* **liveness is supervised** — a wedged (SIGSTOPped) worker fails the
  heartbeat probe and is respawned; graceful drain exits every worker 0.
"""
import json
import os
import signal
import struct
import time
import zlib
from types import SimpleNamespace

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.topk import next_pow2
from repro.serving import (BackpressureError, CallPolicy, FaultPolicy,
                           FaultyWorker, HealthState, HealthTracker,
                           RemoteWorkerError, RetrievalIndex, ShardRouter,
                           ShardUnavailableError, SnapshotError,
                           TornResultError, WireError, WorkerCrashedError,
                           WorkerSupervisor, WorkerTimeoutError,
                           aggregate_topk, load_fleet, validate_run)
from repro.serving import transport as T
from repro.serving.health import Attempt
from repro.serving.shards import MissingShardError
from repro.serving.snapshot import save_shards
from repro.serving.supervisor import SupervisorConfig
from repro.data.synthetic import clustered_vectors

N, D, K, NCELLS, NSHARDS = 1024, 16, 10, 8, 2
CFG = dict(ivf_cells=NCELLS, nprobe=4, overfetch=8)


@pytest.fixture(scope="module")
def fleet(tmp_path_factory):
    """One IVF index, its R=2 shard fleet root, and the inproc baseline."""
    vecs = clustered_vectors(N, D, seed=5)
    idx = RetrievalIndex.build(np.arange(N), vecs, **CFG)
    q = clustered_vectors(24, D, seed=6)
    root = str(tmp_path_factory.mktemp("rpc") / "fleet")
    save_shards(idx, root, NSHARDS, replicas=2)
    base = load_fleet(root, replicas=1).search(q, K)
    return SimpleNamespace(q=q, root=root, base=base)


def _assert_bit_identical(a, b):
    np.testing.assert_array_equal(np.asarray(a.ids), np.asarray(b.ids))
    np.testing.assert_array_equal(np.asarray(a.distances),
                                  np.asarray(b.distances))


# -- framing -----------------------------------------------------------------


def test_frame_roundtrip():
    arrays = {
        "q": np.arange(12, dtype=np.float32).reshape(3, 4),
        "ids": np.array([[1, -1], [7, 8]], dtype=np.int32),
        "mask": np.array([True, False]),
        "scalar": np.array(3, dtype=np.int64),
    }
    meta = {"seq": 42, "k": 10, "note": "héllo"}
    buf = T.pack_frame(T.F_QUERY, meta, arrays)
    ftype, m, a, consumed = T.unpack_frame(buf)
    assert ftype == T.F_QUERY and consumed == len(buf)
    assert m == {"seq": 42, "k": 10, "note": "héllo"}
    assert sorted(a) == sorted(arrays)
    for name in arrays:
        np.testing.assert_array_equal(a[name], arrays[name])
        assert a[name].dtype == arrays[name].dtype
    # Two frames back to back: consumed delimits the first exactly.
    combo = T.pack_frame(T.F_PING) + buf
    ftype2, _, _, c2 = T.unpack_frame(combo)
    assert ftype2 == T.F_PING
    ftype3, m3, _, _ = T.unpack_frame(combo[c2:])
    assert ftype3 == T.F_QUERY and m3 == m


def test_pack_refuses_bad_inputs():
    with pytest.raises(WireError, match="unknown frame type"):
        T.pack_frame(99)
    # Send-side dtype whitelist: float16 and object never hit the wire.
    with pytest.raises(WireError, match="refusing to send"):
        T.pack_frame(T.F_RESULT, {}, {"v": np.zeros(2, np.float16)})


def _craft(ftype: int, payload: bytes) -> bytes:
    """A frame with a VALID checksum for an arbitrary type/payload — lets
    the tests reach parse errors deeper than the CRC gate."""
    crc = zlib.crc32(payload, zlib.crc32(
        struct.pack("<4sHH", T.WIRE_MAGIC, T.WIRE_VERSION, ftype)))
    return T._HEADER.pack(T.WIRE_MAGIC, T.WIRE_VERSION, ftype,
                          len(payload), crc) + payload


def test_unpack_rejects_each_malformation():
    frame = T.pack_frame(T.F_RESULT, {"seq": 1},
                         {"v": np.arange(8, dtype=np.float32)})
    with pytest.raises(WireError, match="truncated frame header"):
        T.unpack_frame(frame[: T.HEADER_BYTES - 1])
    with pytest.raises(WireError, match="bad frame magic"):
        T.unpack_frame(b"XXXX" + frame[4:])
    ver = T._HEADER.pack(T.WIRE_MAGIC, T.WIRE_VERSION + 1, T.F_RESULT, 0, 0)
    with pytest.raises(WireError, match="wire version"):
        T.unpack_frame(ver)
    with pytest.raises(WireError, match="truncated frame payload"):
        T.unpack_frame(frame[:-3])
    crc_flip = bytearray(frame)
    crc_flip[-1] ^= 0xFF  # payload tail: CRC must catch it
    with pytest.raises(WireError, match="CRC mismatch"):
        T.unpack_frame(bytes(crc_flip))
    # Unknown frame type with a valid checksum.
    with pytest.raises(WireError, match="unknown frame type"):
        T.unpack_frame(_craft(77, frame[T.HEADER_BYTES:]))
    # A flipped TYPE byte must fail the CRC, not relabel the message.
    relabel = bytearray(frame)
    relabel[6] ^= 1  # F_RESULT -> F_QUERY, payload untouched
    with pytest.raises(WireError, match="CRC mismatch"):
        T.unpack_frame(bytes(relabel))

    def crafted(payload: bytes) -> bytes:
        return _craft(T.F_RESULT, payload)

    with pytest.raises(WireError, match="not valid JSON"):
        T.unpack_frame(crafted(struct.pack("<I", 8) + b"not json"))
    with pytest.raises(WireError, match="arrays manifest"):
        T.unpack_frame(crafted(struct.pack("<I", 2) + b"{}"))
    # A spec naming a dtype off the whitelist cannot smuggle np.dtype(evil).
    meta = json.dumps({"arrays": [{"name": "v", "dtype": "object",
                                   "shape": [1]}]}).encode()
    with pytest.raises(WireError, match="not admitted"):
        T.unpack_frame(crafted(struct.pack("<I", len(meta)) + meta))
    meta = json.dumps({"arrays": [{"name": "v", "dtype": "float32",
                                   "shape": [-1]}]}).encode()
    with pytest.raises(WireError, match="negative array dim"):
        T.unpack_frame(crafted(struct.pack("<I", len(meta)) + meta))
    # Blob bytes disagreeing with the declared shape, both directions.
    meta = json.dumps({"arrays": [{"name": "v", "dtype": "float32",
                                   "shape": [4]}]}).encode()
    with pytest.raises(WireError, match="truncated"):
        T.unpack_frame(crafted(struct.pack("<I", len(meta)) + meta + b"\0" * 8))
    with pytest.raises(WireError, match="trailing bytes"):
        T.unpack_frame(crafted(struct.pack("<I", len(meta)) + meta
                               + b"\0" * 24))


def test_fuzz_byte_corruption_never_parses_wrong():
    """Satellite: fuzz contract — ANY single-byte flip or truncation either
    raises WireError or yields the original message, never a third thing."""
    frame = T.pack_frame(T.F_RESULT, {"seq": 7, "k": 10},
                         {"vals": np.linspace(0, 1, 24, dtype=np.float32)
                          .reshape(3, 8),
                          "ids": np.arange(24, dtype=np.int32).reshape(3, 8)})
    want = T.unpack_frame(frame)
    rng = np.random.default_rng(1234)
    for _ in range(300):
        buf = bytearray(frame)
        pos = int(rng.integers(len(buf)))
        flip = int(rng.integers(1, 256))
        buf[pos] ^= flip  # guaranteed to differ at pos
        try:
            got = T.unpack_frame(bytes(buf))
        except WireError:
            continue
        # The only acceptable parse of a corrupt buffer is the original.
        assert got[0] == want[0] and got[1] == want[1], (pos, flip)
        for name in want[2]:
            np.testing.assert_array_equal(got[2][name], want[2][name])
    for _ in range(100):  # torn frames: every truncation point fails loudly
        n = int(rng.integers(len(frame)))
        with pytest.raises(WireError):
            T.unpack_frame(frame[:n])


def test_wire_error_fails_over_like_torn_result():
    assert issubclass(WireError, TornResultError)
    # The failover wrapper counts it as a worker failure like any raise.
    from repro.serving import run_with_failover

    def corrupt():
        raise WireError("frame payload CRC mismatch")

    tracker = HealthTracker()
    out, attempts = run_with_failover(
        [("bad", corrupt), ("good", lambda: "served")],
        policy=CallPolicy(), tracker=tracker)
    assert out == "served"
    assert attempts[0].error and "CRC" in attempts[0].error
    assert tracker.state("bad") is HealthState.DEGRADED


def test_frame_overhead_model_tracks_framing():
    base = T.frame_overhead_bytes({"seq": 1}, n_arrays=0)
    assert base > T.HEADER_BYTES
    assert T.frame_overhead_bytes({"seq": 1}, n_arrays=2) > \
        T.frame_overhead_bytes({"seq": 1}, n_arrays=1) > base


# -- result wire -------------------------------------------------------------


def test_result_wire_fp32_is_bit_exact():
    rng = np.random.default_rng(3)
    vals = np.sort(rng.random((4, 16)).astype(np.float32), axis=-1)
    ids = rng.integers(0, 1 << 20, size=(4, 16)).astype(np.int64)
    _, _, arrays, _ = T.unpack_frame(
        T.pack_frame(T.F_RESULT, {"seq": 1}, T.encode_result(vals, ids)))
    got_v, got_i = T.decode_result(arrays)
    np.testing.assert_array_equal(got_v, vals)
    np.testing.assert_array_equal(got_i, ids.astype(np.int32))
    assert got_v.dtype == np.float32 and got_i.dtype == np.int32


def test_result_wire_bf16_idempotent_with_bf16_merge():
    """Shipping runs in bf16 changes ZERO bits of the bf16-wire merge:
    encode's cast is the same rounding aggregate_topk applies before its
    first merge round."""
    S, m, Kp = 3, 4, next_pow2(K)
    rng = np.random.default_rng(11)
    vals = np.sort(rng.random((S, m, Kp)).astype(np.float32), axis=-1)
    ids = rng.integers(0, N, size=(S, m, Kp)).astype(np.int32)
    want = aggregate_topk(jnp.asarray(vals), jnp.asarray(ids), K,
                          wire_dtype="bfloat16")
    shipped = []
    for s in range(S):
        _, _, arrays, _ = T.unpack_frame(T.pack_frame(
            T.F_RESULT, {},
            T.encode_result(vals[s], ids[s], wire_dtype="bfloat16")))
        v, i = T.decode_result(arrays)
        assert v.dtype == np.float32  # decode always hands back fp32
        shipped.append(v)
    got = aggregate_topk(jnp.asarray(np.stack(shipped)), jnp.asarray(ids), K,
                         wire_dtype="bfloat16")
    np.testing.assert_array_equal(np.asarray(want.distances),
                                  np.asarray(got.distances))
    np.testing.assert_array_equal(np.asarray(want.indices),
                                  np.asarray(got.indices))


def test_decode_result_validates():
    with pytest.raises(WireError, match="missing runs"):
        T.decode_result({"vals": np.zeros((1, 2), np.float32)})
    with pytest.raises(WireError, match="not integral"):
        T.decode_result({"vals": np.zeros((1, 2), np.float32),
                         "ids": np.zeros((1, 2), np.float32)})


# -- error wire (satellite: structured errors round-trip) --------------------


def test_error_roundtrip_preserves_context():
    attempts = (Attempt("s1r0", 0.012, "WorkerCrashedError: down"),
                Attempt("s1r1", 0.034, None))
    e = ShardUnavailableError("every replica of shard 1 failed",
                              cells=(3, 4), shard_ids=(1,), attempts=attempts)
    # Through a REAL frame, not just the codec: meta JSON-ifies the context.
    _, meta, _, _ = T.unpack_frame(
        T.pack_frame(T.F_ERROR, {"seq": 9, "error": T.encode_error(e)}))
    r = T.decode_error(meta["error"])
    assert type(r) is ShardUnavailableError
    assert isinstance(r, MissingShardError)  # callers still catch one type
    assert str(r) == str(e)
    assert r.cells == (3, 4) and r.shard_ids == (1,)
    assert r.attempts == attempts  # real Attempt records, None preserved
    assert all(isinstance(a, Attempt) for a in r.attempts)

    m = MissingShardError("cells owned by no loaded shard", cells=(7,))
    rm = T.roundtrip_error(m)
    assert type(rm) is MissingShardError and rm.cells == (7,)


def test_error_roundtrip_plain_and_unknown_types():
    for cls in (TornResultError, WireError, SnapshotError,
                WorkerCrashedError, WorkerTimeoutError, BackpressureError):
        r = T.roundtrip_error(cls("boom"))
        assert type(r) is cls and str(r) == "boom"
    # Unknown types degrade to a TAGGED RemoteWorkerError, never a guess.
    r = T.roundtrip_error(ValueError("k must be positive"))
    assert type(r) is RemoteWorkerError
    assert r.remote_type == "ValueError"
    assert "ValueError" in str(r) and "k must be positive" in str(r)


def test_attempts_from_wire():
    raw = [["w0", 0.5, "err"], ["w1", 1, None]]
    assert T.attempts_from_wire(raw) == (Attempt("w0", 0.5, "err"),
                                         Attempt("w1", 1.0, None))


# -- the analytic RPC traffic model ------------------------------------------


def test_rpc_bytes_model():
    from repro.accounting import rpc_bytes_per_batch

    m = rpc_bytes_per_batch(64, 128, k=K, shards_dispatched=3.0)
    Kp = next_pow2(K)
    assert m["request"] > 64 * 128 * 4  # query block + real frame overhead
    assert m["reply"] > 64 * Kp * 8
    # The architecture's point: requests are O(m·d), replies O(m·K) — the
    # aggregator stays thin because workers ship runs, not candidates.
    assert m["reply"] < m["request"]
    assert m["fleet_total"] == pytest.approx(3.0 * m["per_shard"])
    assert m["per_query"] == pytest.approx(m["fleet_total"] / 64)
    bf16 = rpc_bytes_per_batch(64, 128, k=K, wire_bytes_per_value=2)
    assert bf16["reply"] < m["reply"]
    assert bf16["request"] == m["request"]  # queries stay fp32


# -- the proc backend: real worker processes ---------------------------------


def test_proc_fleet_bit_identical_and_graceful_drain(fleet):
    """workers="proc" serves the same bits as inproc; deadlines bind the
    real socket timeout; a malformed QUERY comes back as a typed WireError
    without killing the worker; drain exits every worker 0."""
    router = load_fleet(fleet.root, workers="proc", replicas=2,
                        call_policy=CallPolicy(deadline_s=60.0))
    sup = router.supervisor
    try:
        # The router's deadline bounds REAL socket waits on every worker.
        assert sup.timeout_s == 60.0
        assert all(w._sock.gettimeout() == 60.0 for w in sup.workers)
        assert {w.key for w in sup.workers} == \
            {f"s{s}r{r}" for s in range(NSHARDS) for r in range(2)}
        assert all(w.alive and w.pid is not None for w in sup.workers)
        # HELLO-announced metadata matches the shard images: live rows are
        # counted once per range, replicas are restores of the same image.
        assert sum(w.n_live for w in sup.workers) == 2 * N
        assert all(w.dim == D for w in sup.workers)

        got = router.search(fleet.q, K)
        _assert_bit_identical(fleet.base, got)
        assert np.all(np.asarray(got.coverage) == 1.0)

        # A QUERY missing its q array: the worker answers with a typed
        # ERROR frame (WireError, with our seq) and keeps serving.
        w = sup.workers[0]
        w._seq += 1
        T.send_frame(w._sock, T.F_QUERY, {"seq": w._seq, "k": K})
        ftype, meta, _ = T.recv_frame(w._sock)
        assert ftype == T.F_ERROR and meta["seq"] == w._seq
        err = T.decode_error(meta["error"])
        assert type(err) is WireError and "q array" in str(err)
        _assert_bit_identical(fleet.base, router.search(fleet.q, K))

        assert sup.summary()["respawns"] == 0
        procs = [w._proc for w in sup.workers]
    finally:
        sup.shutdown(drain=True)
    # Graceful drain: DRAIN → BYE → exit 0, no worker terminated/killed.
    assert [p.wait(timeout=10) for p in procs] == [0] * len(procs)
    assert not any(w.alive for w in sup.workers)


def test_proc_bf16_wire_matches_inproc_bf16(fleet):
    """The bf16 value wire end to end: a proc fleet shipping bf16 runs is
    bit-identical to the inproc fleet merging with the bf16 wire."""
    inproc = load_fleet(fleet.root, replicas=1, wire_dtype="bfloat16")
    want = inproc.search(fleet.q, K)
    router = load_fleet(fleet.root, workers="proc", replicas=1,
                        wire_dtype="bfloat16")
    try:
        _assert_bit_identical(want, router.search(fleet.q, K))
    finally:
        router.supervisor.shutdown(drain=False)


def test_sigkill_one_replica_of_every_shard_mid_batch(fleet):
    """The acceptance criterion, on real processes: at R=2, SIGKILL one
    replica of every shard MID-BATCH → bit-identical results, coverage
    1.0; the corpses respawn from their snapshot images into PROBATION;
    then the surviving replicas are killed mid-batch too, forcing traffic
    onto the respawned workers — which serve, and graduate to HEALTHY."""
    router0 = load_fleet(fleet.root, workers="proc", replicas=2,
                         degraded="partial")
    sup = router0.supervisor
    try:
        kill0 = {f"s{s}r0" for s in range(NSHARDS)}
        kill1 = {f"s{s}r1" for s in range(NSHARDS)}
        pids = {w.key: w.pid for w in sup.workers}
        # The kill fault schedule (satellite: chaos suites get a "kill"
        # kind): replica 0 dies at its first consult — batch 1, because
        # the round-robin rotation starts every group at replica 0; the
        # survivor dies at its call 2 — batch 3, after serving batches
        # 1 (failover) and 2.
        wrapped = [FaultyWorker(w, FaultPolicy.kill_at(0)) if w.key in kill0
                   else FaultyWorker(w, FaultPolicy.kill_at(2))
                   for w in router0.workers]
        router = ShardRouter(wrapped, degraded="partial",
                             call_policy=CallPolicy(), supervisor=sup)

        # Batch 1: every shard's replica 0 is SIGKILLed mid-batch; the
        # broken pipe is discovered in-flight and failover eats it whole.
        got = router.search(fleet.q, K)
        _assert_bit_identical(fleet.base, got)
        assert np.all(np.asarray(got.coverage) == 1.0)
        assert all(st == "ok" for _, st in got.shard_status)
        assert all(router.health.state(k) is HealthState.DEGRADED
                   for k in kill0)
        assert all(not w.alive for w in sup.workers if w.key in kill0)

        # Batch 2: the supervisor's pre-dispatch poll respawns the corpses
        # from their shard images; they re-enter routing as PROBATION
        # while the healthy survivors carry the batch.
        _assert_bit_identical(fleet.base, router.search(fleet.q, K))
        assert sup.respawns == NSHARDS
        assert all(router.health.state(k) is HealthState.PROBATION
                   for k in kill0)
        for w in sup.workers:
            if w.key in kill0:
                assert w.alive and w.respawns == 1 and w.pid != pids[w.key]

        # Batch 3: now the SURVIVORS are killed mid-batch — traffic is
        # forced onto the respawned workers, which must actually serve
        # (respawn-to-serving, not just respawn-to-alive).
        got = router.search(fleet.q, K)
        _assert_bit_identical(fleet.base, got)
        assert np.all(np.asarray(got.coverage) == 1.0)
        assert all(router.health.state(k) is HealthState.HEALTHY
                   for k in kill0)  # probation trial served and passed
        assert all(router.health.state(k) is HealthState.DEGRADED
                   for k in kill1)

        # Batch 4: the second wave respawns too; the whole fleet is live
        # again and every worker has a fresh pid.
        _assert_bit_identical(fleet.base, router.search(fleet.q, K))
        assert sup.respawns == 2 * NSHARDS
        assert all(w.alive and w.pid != pids[w.key] for w in sup.workers)
        assert all(f.faults_injected == 1 for f in wrapped)
    finally:
        sup.shutdown(drain=False)


def test_deadline_abandons_slow_reply_then_discards_it_stale(fleet):
    """A worker answering past the socket deadline: the call times out
    (worker NOT marked dead — slow is not crashed), the in-flight budget
    refuses further calls (backpressure), and the late reply is retired
    by its stale seq — discarded, never served."""
    sup = WorkerSupervisor(SupervisorConfig(heartbeat_s=60.0))
    try:
        sup.spawn_fleet(fleet.root, replicas=1)
        w = next(x for x in sup.workers if x.key == "s0r0")
        warm = w.topk(fleet.q, K)  # compiles the worker-side scan
        validate_run(warm, len(fleet.q), next_pow2(K))

        w.test_delay_s = 0.6
        w._sock.settimeout(0.15)  # what CallPolicy.deadline_s binds
        with pytest.raises(WorkerTimeoutError):
            w.topk(fleet.q, K)
        assert w.alive and w._pending == 1  # abandoned, not crashed

        # Bounded in-flight queue: at the budget, calls are refused
        # loudly instead of piling onto a struggling worker.
        w.queue_depth = 1
        with pytest.raises(BackpressureError):
            w.topk(fleet.q, K)
        w.queue_depth = sup.cfg.queue_depth

        # The worker eventually answers the abandoned request; the next
        # call reads that stale reply first, retires it by seq, and
        # serves only its own — bit-identical to the warm result.
        w.test_delay_s = 0.0
        w._sock.settimeout(30.0)
        got = w.topk(fleet.q, K)
        np.testing.assert_array_equal(np.asarray(got.distances),
                                      np.asarray(warm.distances))
        np.testing.assert_array_equal(np.asarray(got.indices),
                                      np.asarray(warm.indices))
        assert w._pending == 0  # the stale reply was retired, not leaked
        w.ping()
    finally:
        sup.shutdown(drain=False)


def test_heartbeat_detects_wedged_worker_and_respawns(fleet):
    """SIGSTOP leaves a process alive-but-wedged — exit-code polling can't
    see it; the idle heartbeat PING times out, the worker is declared
    dead, respawned from its image, and re-admitted as PROBATION."""
    cfg = SupervisorConfig(heartbeat_s=0.05, heartbeat_timeout_s=0.3)
    sup = WorkerSupervisor(cfg)
    try:
        sup.spawn_fleet(fleet.root, replicas=1)
        w = next(x for x in sup.workers if x.key == "s0r0")
        old_pid = w.pid
        os.kill(w.pid, signal.SIGSTOP)
        assert w.alive  # the lie the heartbeat exists to catch
        with pytest.raises(WorkerTimeoutError):
            w.ping(timeout_s=0.2)
        time.sleep(0.06)  # past heartbeat_s: poll must probe idle workers
        tracker = HealthTracker()
        respawned = sup.poll(tracker)
        assert "s0r0" in respawned
        assert tracker.state("s0r0") is HealthState.PROBATION
        assert w.alive and w.pid != old_pid and w.respawns == 1
        validate_run(w.topk(fleet.q, K), len(fleet.q), next_pow2(K))
    finally:
        sup.shutdown(drain=False)


def test_restore_failure_ships_as_typed_error(fleet, tmp_path):
    """A worker that cannot restore its image reports a structured
    SnapshotError over the wire — the parent raises the same typed error
    an in-process restore would have, and no process leaks."""
    import shutil

    root = str(tmp_path / "corrupt")
    shutil.copytree(fleet.root, root)
    mpath = os.path.join(root, "shard-000", "manifest.json")
    with open(mpath) as f:
        manifest = json.load(f)
    manifest["files"]["shard.npz"]["crc32"] ^= 1
    with open(mpath, "w") as f:
        json.dump(manifest, f)
    # Parent-side manifest reads skip verification (the child re-verifies
    # hard), so the failure surfaces through the child's ERROR frame.
    with pytest.raises(SnapshotError, match="corrupted/truncated"):
        load_fleet(root, workers="proc", replicas=1)


def test_load_fleet_rejects_unknown_backend(fleet):
    with pytest.raises(ValueError, match="workers"):
        load_fleet(fleet.root, workers="threads")


def test_proc_workers_refuse_a_process_holding_the_tpu(fleet, monkeypatch):
    """A chip belongs to one process at a time: with a TPU backend the
    supervisor refuses at once instead of waiting out the spawn timeout."""
    from repro.kernels import _backend

    monkeypatch.setattr(_backend, "on_tpu", lambda: True)
    with pytest.raises(RuntimeError, match="one chip per process"):
        load_fleet(fleet.root, workers="proc", replicas=1)
