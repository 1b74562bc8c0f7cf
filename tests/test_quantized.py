"""Quantized two-stage retrieval (DESIGN.md §Quantized).

The contract under test: a bf16/int8 scan replica plus exact fp32 rescore
returns the true top-k with recall above the configured floor (and exactly,
for a float32 replica); the serving index's ``scan_dtype`` knob preserves
bit-exactness at "float32"; the compressed collective wires (_rotate_bits
ring payload, butterfly ``wire_dtype``) change bytes, not answers.
"""
import hypothesis
import hypothesis.strategies as st
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_with_devices
from repro.core.distances import (
    QUANTIZABLE,
    dequantize_rows,
    quantize_rows,
)
from repro.core.knn import knn_query, rescore, scan_width, two_stage_query
from repro.serving import EngineConfig, QueryEngine, RetrievalIndex

SETTINGS = dict(max_examples=15, deadline=None)

# Recall floor for the property test: int8 per-row quantization at 4x
# overfetch sits at ~1.0 on gaussian/clustered data (EXPERIMENTS.md
# §Quantized); 0.9 leaves slack for adversarial hypothesis draws.
RECALL_FLOOR = 0.9


def _recall(got_idx, want_idx):
    m, k = want_idx.shape
    hits = sum(
        len(set(map(int, g)) & set(map(int, w)))
        for g, w in zip(np.asarray(got_idx), np.asarray(want_idx))
    )
    return hits / float(m * k)


# ---------------------------------------------------------------------------
# quantize_rows / dequantize_rows
# ---------------------------------------------------------------------------


def test_int8_roundtrip_error_bounded_by_half_scale():
    rng = np.random.default_rng(0)
    y = jnp.asarray(rng.standard_normal((100, 32)).astype(np.float32))
    qr = quantize_rows(y, "int8")
    err = np.abs(np.asarray(dequantize_rows(qr)) - np.asarray(y))
    bound = np.asarray(qr.scale)[:, None] * 0.5 + 1e-7
    assert (err <= bound).all()
    assert qr.data.dtype == jnp.int8 and qr.hy.shape == (100,)


def test_bf16_replica_has_no_scale_and_fp32_is_identity():
    rng = np.random.default_rng(1)
    y = jnp.asarray(rng.standard_normal((64, 16)).astype(np.float32))
    qb = quantize_rows(y, "bf16")  # alias spelling
    assert qb.data.dtype == jnp.bfloat16 and qb.scale is None
    qf = quantize_rows(y, "float32")
    np.testing.assert_array_equal(np.asarray(qf.data), np.asarray(y))
    np.testing.assert_allclose(
        np.asarray(qf.hy), np.sum(np.asarray(y) ** 2, -1), rtol=1e-6)


def test_unquantizable_distance_raises():
    y = jnp.ones((8, 8), jnp.float32) / 8.0
    with pytest.raises(ValueError):
        quantize_rows(y, "int8", distance="kl")
    with pytest.raises(ValueError):
        quantize_rows(y, "float16")  # not a scan dtype


@hypothesis.settings(**SETTINGS)
@hypothesis.given(seed=st.integers(0, 10_000),
                  mode=st.sampled_from(["zero", "constant", "ragged"]),
                  scan_dtype=st.sampled_from(["float32", "bfloat16", "int8"]))
def test_quantize_rows_degenerate_inputs_finite(seed, mode, scan_dtype):
    """All-zero rows, constant rows, and non-tile-multiple corpus sizes
    quantize/dequantize without NaN/Inf, and the two-stage pipeline over
    them returns finite distances (satellite contract next to the PQ edge
    cases in tests/test_pq.py — int8's zero-row scale floors at eps/127
    rather than dividing by zero)."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(3, 200))
    d = int(rng.integers(2, 40))
    if mode == "zero":
        y = np.zeros((n, d), np.float32)
    elif mode == "constant":
        y = np.full((n, d), float(rng.choice([-4.0, 1e-7, 2.5])), np.float32)
    else:
        y = rng.standard_normal((n, d)).astype(np.float32)
    qr = quantize_rows(jnp.asarray(y), scan_dtype)
    assert np.isfinite(np.asarray(qr.data, np.float32)).all()
    assert np.isfinite(np.asarray(qr.hy)).all()
    if qr.scale is not None:
        s = np.asarray(qr.scale)
        assert np.isfinite(s).all() and (s > 0).all()
    deq = np.asarray(dequantize_rows(qr))
    assert np.isfinite(deq).all()
    if mode == "zero":
        np.testing.assert_array_equal(deq, y)
    q = jnp.asarray(rng.standard_normal((4, d)).astype(np.float32))
    res = two_stage_query(q, jnp.asarray(y), qr, min(5, n))
    assert np.isfinite(np.asarray(res.distances)).all()
    assert (np.asarray(res.indices) >= 0).all()


# ---------------------------------------------------------------------------
# rescore + two_stage_query
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("impl", ["jnp", "fused"])
def test_rescore_of_true_candidates_reproduces_exact_knn(impl):
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.standard_normal((20, 24)).astype(np.float32))
    db = jnp.asarray(rng.standard_normal((300, 24)).astype(np.float32))
    exact = knn_query(q, db, 6)
    # over-fetch 16 true candidates, rescore down to 6: must match exactly
    cand = knn_query(q, db, 16).indices
    res = rescore(q, db, cand, 6, impl=impl)
    np.testing.assert_array_equal(np.asarray(res.indices),
                                  np.asarray(exact.indices))
    np.testing.assert_allclose(np.asarray(res.distances),
                               np.asarray(exact.distances), rtol=1e-5, atol=1e-5)


def test_rescore_handles_empty_slots_and_k_wider_than_candidates():
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.standard_normal((4, 8)).astype(np.float32))
    db = jnp.asarray(rng.standard_normal((50, 8)).astype(np.float32))
    cand = jnp.asarray([[0, 1, -1, -1]] * 4, jnp.int32)
    res = rescore(q, db, cand, 4)
    ids = np.asarray(res.indices)
    assert set(ids[:, :2].ravel()) <= {0, 1}
    assert (ids[:, 2:] == -1).all()
    assert np.isposinf(np.asarray(res.distances)[:, 2:]).all()


def test_scan_width_overfetch_math():
    assert scan_width(1000, 10, 4) == 64  # 4 * next_pow2(10)
    assert scan_width(40, 10, 4) == 40  # clamped at n: exhaustive => exact
    assert scan_width(1000, 10, 1) == 16


@pytest.mark.parametrize("impl", ["jnp", "fused"])
def test_two_stage_float32_replica_matches_exact(impl):
    """K' = overfetch*K fp32 scan candidates provably contain the top-k."""
    rng = np.random.default_rng(4)
    q = jnp.asarray(rng.standard_normal((13, 16)).astype(np.float32))
    db = jnp.asarray(rng.standard_normal((200, 16)).astype(np.float32))
    qr = quantize_rows(db, "float32")
    exact = knn_query(q, db, 7)
    res = two_stage_query(q, db, qr, 7, impl=impl)
    np.testing.assert_array_equal(np.asarray(res.indices),
                                  np.asarray(exact.indices))


@hypothesis.settings(**SETTINGS)
@hypothesis.given(seed=st.integers(0, 10_000), k=st.integers(1, 17),
                  scan_dtype=st.sampled_from(["bfloat16", "int8"]),
                  impl=st.sampled_from(["jnp", "fused"]),
                  distance=st.sampled_from(QUANTIZABLE))
def test_two_stage_recall_above_floor(seed, k, scan_dtype, impl, distance):
    """recall@k of quantized scan + exact rescore >= the configured floor."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(50, 400))
    d = int(rng.integers(4, 48))
    db = jnp.asarray(rng.standard_normal((n, d)).astype(np.float32))
    q = jnp.asarray(rng.standard_normal((8, d)).astype(np.float32))
    exact = knn_query(q, db, k, distance=distance)
    qr = quantize_rows(db, scan_dtype, distance=distance)
    res = two_stage_query(q, db, qr, k, distance=distance, impl=impl)
    rec = _recall(res.indices, exact.indices)
    assert rec >= RECALL_FLOOR, (rec, scan_dtype, impl, distance)
    # rescored distances are EXACT for every correctly-recalled id
    hit = np.asarray(res.indices) == np.asarray(exact.indices)
    np.testing.assert_allclose(np.asarray(res.distances)[hit],
                               np.asarray(exact.distances)[hit],
                               rtol=1e-4, atol=1e-4)


def _iter_eqns(jaxpr):
    """All equations of a jaxpr, recursing into call/scan/cond sub-jaxprs."""
    from jax.extend.core import ClosedJaxpr, Jaxpr

    def subs(v):
        if isinstance(v, ClosedJaxpr):
            return [v.jaxpr]
        if isinstance(v, Jaxpr):
            return [v]
        if isinstance(v, (list, tuple)):
            return [s for x in v for s in subs(x)]
        return []

    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in subs(v):
                yield from _iter_eqns(sub)


def test_two_stage_jnp_never_materializes_dequantized_corpus():
    """Peak-memory-shape assertion: the jnp scan scores the stored int8
    rows directly (per-tile upcast, scale in the epilogue) — no
    intermediate may be a corpus-sized fp32 array.  The original
    implementation dequantized the whole replica up front, which made the
    compressed replica's memory win a fiction on the jnp path."""
    n, d, m, k = 4096, 32, 8, 10  # n >> tile_n so tiles are visibly smaller
    rng = np.random.default_rng(13)
    db = jnp.asarray(rng.standard_normal((n, d)).astype(np.float32))
    q = jnp.asarray(rng.standard_normal((m, d)).astype(np.float32))
    db_q = quantize_rows(db, "int8")
    import jax

    jaxpr = jax.make_jaxpr(
        lambda q_, db_, dq: two_stage_query(q_, db_, dq, k, impl="jnp")
    )(q, db, db_q)
    offenders = [
        (eqn.primitive.name, ov.aval.shape)
        for eqn in _iter_eqns(jaxpr.jaxpr)
        for ov in eqn.outvars
        if (getattr(ov.aval, "ndim", 0) == 2 and ov.aval.shape[0] >= n
            and ov.aval.dtype == jnp.float32)
    ]
    assert not offenders, (
        f"corpus-sized fp32 intermediates on the jnp scan path: {offenders}")


# ---------------------------------------------------------------------------
# Serving index: scan_dtype knob
# ---------------------------------------------------------------------------


def test_index_float32_scan_dtype_is_bit_exact():
    rng = np.random.default_rng(5)
    vecs = rng.standard_normal((300, 24)).astype(np.float32)
    ids = np.arange(300)
    q = rng.standard_normal((9, 24)).astype(np.float32)
    a = RetrievalIndex.build(ids, vecs).search(q, 11)
    b = RetrievalIndex.build(ids, vecs, scan_dtype="float32").search(q, 11)
    np.testing.assert_array_equal(np.asarray(a.ids), np.asarray(b.ids))
    np.testing.assert_array_equal(np.asarray(a.distances),
                                  np.asarray(b.distances))


@pytest.mark.parametrize("scan_dtype", ["bfloat16", "int8"])
@pytest.mark.parametrize("impl", ["jnp", "fused"])
def test_index_quantized_lifecycle_recall(scan_dtype, impl):
    """Insert/delete/compact with a quantized main: delta stays fp32-exact,
    overall recall stays above the floor, and the replica follows compact."""
    rng = np.random.default_rng(6)
    d, k = 16, 8
    vecs = rng.standard_normal((256, d)).astype(np.float32)
    idx = RetrievalIndex.build(np.arange(256), vecs, scan_dtype=scan_dtype,
                               impl=impl)
    ref = RetrievalIndex.build(np.arange(256), vecs, impl=impl)
    fresh = rng.standard_normal((30, d)).astype(np.float32)
    for i in (idx, ref):
        i.delete(np.arange(0, 256, 5))
        i.insert(np.arange(1000, 1030), fresh)
    q = rng.standard_normal((12, d)).astype(np.float32)
    r, e = idx.search(q, k), ref.search(q, k)
    assert _recall(r.ids, e.ids) >= RECALL_FLOOR
    epoch_before = idx._main_epoch
    idx.compact()
    ref.compact()
    assert idx._main_epoch == epoch_before + 1  # replica rebuild point
    r, e = idx.search(q, k), ref.search(q, k)
    assert _recall(r.ids, e.ids) >= RECALL_FLOOR


def test_index_quantized_rejects_unquantizable_distance():
    with pytest.raises(ValueError):
        RetrievalIndex(8, distance="kl", scan_dtype="int8")


def test_tombstone_does_not_rebuild_replica_but_compact_does():
    rng = np.random.default_rng(7)
    vecs = rng.standard_normal((64, 8)).astype(np.float32)
    idx = RetrievalIndex.build(np.arange(64), vecs, scan_dtype="int8")
    q = rng.standard_normal((3, 8)).astype(np.float32)
    idx.search(q, 3)
    replica = idx._dev["main_q"]
    idx.delete([0, 1, 2])
    idx.search(q, 3)
    assert idx._dev["main_q"] is replica  # mask flip, same replica
    idx.compact()
    idx.search(q, 3)
    assert idx._dev["main_q"] is not replica


# ---------------------------------------------------------------------------
# Engine: stale shape-signature eviction
# ---------------------------------------------------------------------------


def test_engine_evicts_stale_shape_signatures():
    """Growth-churn (main size moves at each compact) stays bounded."""
    rng = np.random.default_rng(8)
    d = 8
    idx = RetrievalIndex.build(
        np.arange(32), rng.standard_normal((32, d)).astype(np.float32))
    eng = QueryEngine(idx, EngineConfig(k=3, min_batch=8, max_batch=64))
    q = rng.standard_normal((5, d)).astype(np.float32)
    for epoch in range(4):  # each compact grows main => signature moves on
        eng.search(q)
        eng.search(rng.standard_normal((40, d)).astype(np.float32))
        assert len(eng._seen_shapes) <= 2  # live main-epoch's keys only
        idx.insert(np.arange(100 + 10 * epoch, 110 + 10 * epoch),
                   rng.standard_normal((10, d)).astype(np.float32))
        idx.compact()
    eng.search(q)  # eviction is lazy: first search at the new signature
    sig = idx.shape_signature(3)
    assert all(s[2] == sig for s in eng._seen_shapes)
    assert len(eng._seen_shapes) == 1


def test_engine_recurring_signature_not_retagged_as_compile():
    """Upsert-replace churn: compact keeps the main row count, so the
    (main, delta-cap) signatures RECUR — returning batches must stay
    steady-state, not be re-tagged compile batches (and re-evicted)."""
    rng = np.random.default_rng(12)
    d, n = 8, 32
    idx = RetrievalIndex.build(
        np.arange(n), rng.standard_normal((n, d)).astype(np.float32))
    eng = QueryEngine(idx, EngineConfig(k=3, min_batch=8, max_batch=64))
    q = rng.standard_normal((5, d)).astype(np.float32)
    for cycle in range(3):
        eng.search(q)  # sig (n, 0)
        idx.upsert(np.arange(10),  # replaces: row count preserved at compact
                   rng.standard_normal((10, d)).astype(np.float32))
        eng.search(q)  # sig (n, delta_cap)
        idx.compact()
    s = eng.meter.summary()
    # cycle 0 compiles both signatures; cycles 1-2 are pure recurrence
    assert s["compile_batches"] == 2
    assert s["batches"] == 4


# ---------------------------------------------------------------------------
# Compressed collective wires (8 forced host devices, subprocess)
# ---------------------------------------------------------------------------


def test_ring_wire_bf16_matches_fp32_8dev():
    """wire_dtype=bf16 boomerang heap vs the fp32 wire: the traveling heap is
    rounded at every hop, so the contract is bf16-NEAR-OPTIMALITY — every
    returned neighbor's TRUE distance is within bf16 tolerance of the exact
    k-th distance — not index identity (boundary pairs inside one bf16 ulp
    may swap; DESIGN.md §Quantized)."""
    run_with_devices("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import distributed as D
        from repro.kernels import ref as kref
        np.random.seed(9)
        n, d, k = 512, 32, 9
        x = jnp.asarray(np.random.randn(n, d).astype(np.float32))
        mesh = jax.make_mesh((8,), ("ring",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        ref = D.make_ring_allpairs(mesh, k=k)(x, n)
        got = D.make_ring_allpairs(mesh, k=k, wire_dtype=jnp.bfloat16)(x, n)
        rv, gv = np.asarray(ref.distances), np.asarray(got.distances)
        np.testing.assert_allclose(gv, rv, rtol=1e-2, atol=1e-2)
        # each returned index is a real near-optimal neighbor: its exact
        # distance matches the exact k-th distances to bf16 precision
        Dm = np.array(kref.pairwise_distance_ref(x, x))
        np.fill_diagonal(Dm, np.inf)
        true_of_got = np.take_along_axis(Dm, np.asarray(got.indices), 1)
        np.testing.assert_allclose(true_of_got, rv, rtol=1e-2, atol=1e-2)
        # and most slots agree exactly (sanity: the wire is lossy, not wrong)
        agree = (np.asarray(ref.indices) == np.asarray(got.indices)).mean()
        assert agree > 0.9, agree
        print("OK")
    """)


def test_query_sharded_quantized_scan_8dev():
    """Per-shard bf16/int8 scan + rescore + bf16 butterfly wire vs exact."""
    run_with_devices("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import distributed as D
        from repro.core.distances import quantize_rows
        from repro.core.knn import knn_query
        np.random.seed(10)
        d, k, n = 32, 7, 512
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        db = jnp.asarray(np.random.randn(n, d).astype(np.float32))
        q = jnp.asarray(np.random.randn(16, d).astype(np.float32))
        exact = knn_query(q, db, k)
        for sd in ("bfloat16", "int8"):
            fn = D.make_query_sharded(mesh, query_axis="data", db_axis="model",
                                      k=k, scan_dtype=sd,
                                      wire_dtype=jnp.bfloat16)
            for db_q in (None, quantize_rows(db, sd)):
                v, i = fn(q, db, n, None, db_q)
                hits = sum(len(set(map(int, a)) & set(map(int, b)))
                           for a, b in zip(np.asarray(i),
                                           np.asarray(exact.indices)))
                rec = hits / float(16 * k)
                assert rec >= 0.95, (sd, db_q is None, rec)
        print("OK")
    """)


def test_index_sharded_quantized_main_8dev():
    """Mesh-sharded main with scan_dtype=int8: recall vs the local fp32 path."""
    run_with_devices("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.serving import RetrievalIndex
        rng = np.random.default_rng(11)
        d, k = 16, 9
        vecs = rng.standard_normal((512, d)).astype(np.float32)
        ids = np.arange(512)
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        sharded = RetrievalIndex.build(ids, vecs, mesh=mesh, scan_dtype="int8")
        local = RetrievalIndex.build(ids, vecs)
        for idx in (sharded, local):
            idx.delete(np.arange(0, 512, 7))
        q = rng.standard_normal((10, d)).astype(np.float32)
        rs = sharded.search(jnp.asarray(q), k)
        rl = local.search(jnp.asarray(q), k)
        hits = sum(len(set(map(int, a)) & set(map(int, b)))
                   for a, b in zip(np.asarray(rs.ids), np.asarray(rl.ids)))
        assert hits / float(10 * k) >= 0.95
        print("OK")
    """)
