"""Selection-network primitives (paper Sect. 6 TPU adaptation) vs oracles."""
import hypothesis
import hypothesis.strategies as st
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import topk as T

SETTINGS = dict(max_examples=40, deadline=None)


@hypothesis.settings(**SETTINGS)
@hypothesis.given(
    rows=st.integers(1, 8),
    logl=st.integers(0, 9),
    seed=st.integers(0, 100_000),
    ascending=st.booleans(),
)
def test_bitonic_sort_matches_jnp_sort(rows, logl, seed, ascending):
    L = 2 ** logl
    g = np.random.default_rng(seed)
    vals = jnp.asarray(g.standard_normal((rows, L), dtype=np.float32))
    idx = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), (rows, L))
    sv, si = T.bitonic_sort_kv(vals, idx, ascending=ascending)
    ref = jnp.sort(vals, axis=-1)
    if not ascending:
        ref = ref[:, ::-1]
    np.testing.assert_array_equal(np.asarray(sv), np.asarray(ref))
    # index consistency: vals[row, si] == sv
    taken = np.take_along_axis(np.asarray(vals), np.asarray(si), axis=1)
    np.testing.assert_array_equal(taken, np.asarray(sv))


@hypothesis.settings(**SETTINGS)
@hypothesis.given(
    rows=st.integers(1, 6), logk=st.integers(0, 9), seed=st.integers(0, 100_000)
)
def test_merge_topk_sorted(rows, logk, seed):
    """min(a, reverse(b)) + bitonic merge == K smallest of the union."""
    K = 2 ** logk
    g = np.random.default_rng(seed)
    a = np.sort(g.standard_normal((rows, K), dtype=np.float32), axis=1)
    b = np.sort(g.standard_normal((rows, K), dtype=np.float32), axis=1)
    ai = np.arange(K, dtype=np.int32) * np.ones((rows, 1), np.int32)
    bi = ai + K
    mv, mi = T.merge_topk_sorted(jnp.asarray(a), jnp.asarray(ai),
                                 jnp.asarray(b), jnp.asarray(bi))
    ref = np.sort(np.concatenate([a, b], axis=1), axis=1)[:, :K]
    np.testing.assert_array_equal(np.asarray(mv), ref)


@hypothesis.settings(**SETTINGS)
@hypothesis.given(
    rows=st.integers(1, 4), logk=st.integers(1, 8), t=st.integers(0, 3),
    seed=st.integers(0, 100_000), descending=st.booleans(),
)
def test_reduce_topk_oracle(rows, logk, t, seed, descending):
    """Lanes [0, K) of an L = K * 2^t tile's reduction: its K smallest."""
    K = 2 ** logk
    L = K * 2 ** t
    hypothesis.assume(L <= 512)
    g = np.random.default_rng(seed)
    vals = g.standard_normal((rows, L), dtype=np.float32)
    idx = np.broadcast_to(np.arange(L, dtype=np.int32), (rows, L))
    rv, ri = T.reduce_topk(jnp.asarray(vals), jnp.asarray(idx), K,
                           descending=descending)
    assert rv.shape[-1] == T.reduce_width(L, K)
    rv, ri = np.asarray(rv)[:, :K], np.asarray(ri)[:, :K]
    ref = np.sort(vals, axis=1)[:, :K]
    np.testing.assert_array_equal(rv, ref[:, ::-1] if descending else ref)
    np.testing.assert_array_equal(np.take_along_axis(vals, ri, axis=1), rv)


@hypothesis.settings(**SETTINGS)
@hypothesis.given(
    rows=st.integers(1, 4), logk=st.integers(1, 9), groups=st.sampled_from(
        [1, 2, 4]), seed=st.integers(0, 100_000),
)
def test_merge_topk_bitonic_oracle(rows, logk, groups, seed):
    """Ascending a, descending b, K-lane groups side by side: each group's
    result is the K smallest of its two groups, ascending."""
    K = 2 ** logk
    hypothesis.assume(K * groups <= 512)
    g = np.random.default_rng(seed)
    a = np.sort(g.standard_normal((rows, groups, K), dtype=np.float32), -1)
    b = np.sort(g.standard_normal((rows, groups, K), dtype=np.float32),
                -1)[..., ::-1]
    ai = np.broadcast_to(np.arange(groups * K, dtype=np.int32),
                         (rows, groups * K))
    bi = ai + groups * K
    mv, mi = T.merge_topk_bitonic(
        jnp.asarray(a.reshape(rows, -1)), jnp.asarray(ai),
        jnp.asarray(b.reshape(rows, -1)), jnp.asarray(bi), K)
    ref = np.sort(np.concatenate([a, b], -1), -1)[..., :K]
    np.testing.assert_array_equal(np.asarray(mv), ref.reshape(rows, -1))
    both = np.concatenate([a.reshape(rows, -1), b.reshape(rows, -1)], 1)
    np.testing.assert_array_equal(
        np.take_along_axis(both, np.asarray(mi), axis=1), np.asarray(mv))


@hypothesis.settings(**SETTINGS)
@hypothesis.given(
    m=st.integers(1, 10), n=st.integers(1, 200), k=st.integers(1, 32),
    seed=st.integers(0, 100_000),
)
def test_topk_smallest_oracle(m, n, k, seed):
    k = min(k, n)
    g = np.random.default_rng(seed)
    x = jnp.asarray(g.standard_normal((m, n), dtype=np.float32))
    v, i = T.topk_smallest(x, k)
    ref = np.sort(np.asarray(x), axis=1)[:, :k]
    np.testing.assert_array_equal(np.asarray(v), ref)


@hypothesis.settings(**SETTINGS)
@hypothesis.given(
    m=st.integers(1, 6), k=st.integers(1, 16), tiles=st.integers(1, 5),
    bn=st.integers(1, 64), seed=st.integers(0, 100_000),
    skip=st.booleans(),
)
def test_update_running_streams_tiles(m, k, tiles, bn, seed, skip):
    """Streaming tile folds == one-shot top-k over the concatenation."""
    g = np.random.default_rng(seed)
    data = g.standard_normal((m, tiles * bn), dtype=np.float32)
    run = T.init_running(m, k)
    for t in range(tiles):
        tile = jnp.asarray(data[:, t * bn:(t + 1) * bn])
        run = T.update_running(*run, tile, t * bn, threshold_skip=skip)
    v, i = T.finalize_topk(*run, k)
    kk = min(k, tiles * bn)
    ref = np.sort(data, axis=1)[:, :kk]
    np.testing.assert_allclose(np.asarray(v)[:, :kk], ref, atol=1e-6)
    # indices point at the right values
    got = np.take_along_axis(data, np.asarray(i)[:, :kk], axis=1)
    np.testing.assert_allclose(got, ref, atol=1e-6)


def test_merge_many_sorted():
    g = np.random.default_rng(0)
    S, m, K = 5, 4, 8
    parts = np.sort(g.standard_normal((S, m, K), dtype=np.float32), axis=-1)
    idx = np.broadcast_to(np.arange(K, dtype=np.int32), (S, m, K)).copy()
    v, i = T.merge_many_sorted(jnp.asarray(parts), jnp.asarray(idx), K)
    ref = np.sort(parts.transpose(1, 0, 2).reshape(m, -1), axis=1)[:, :K]
    np.testing.assert_array_equal(np.asarray(v), ref)


def test_next_pow2():
    assert [T.next_pow2(i) for i in (1, 2, 3, 5, 8, 100)] == [1, 2, 4, 8, 8, 128]
