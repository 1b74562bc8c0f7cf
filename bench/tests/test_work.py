"""The lower-bound work functions, on hand-computed counts."""
import numpy as np
import pytest

from bench import work


def test_peaks_table_has_v5e_and_refuses_unknown_devices():
    p = work.load_peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.load_peaks("cpu")


def test_allpairs_counts_each_unordered_pair_once():
    w = work.allpairs(4, 3, 2)
    assert w.ops == 6 * 3 * 2  # 6 pairs, 3 multiply-adds each
    assert w.bytes == 4 * 3 * 4 + 4 * 2 * 8


@pytest.mark.parametrize("n,d", [(1024, 64), (160_000, 256)])
def test_allpairs_is_a_lower_bound_of_fused_and_symmetric(n, d):
    """The fused kernel scores the full square (n^2 pairs), the symmetric
    jnp path the upper triangle with its diagonal tiles whole; both do at
    least the work counted, and the count is the same whichever runs."""
    need = work.allpairs(n, d, 100)
    fused_ops = float(n) * n * d * 2
    g = 512
    tiles = -(-n // g)
    symmetric_ops = tiles * (tiles + 1) / 2 * g * g * d * 2
    assert need.ops <= fused_ops and need.ops <= symmetric_ops
    assert need == work.allpairs(n, d, 100)


def test_pq_scan_counts_probed_pairs_and_the_union_once():
    counts = np.array([5, 0, 3, 7])
    probes = np.array([[0, 2], [2, 3]])
    w = work.pq_scan(probes, counts, pq_m=16)
    assert w.ops == (5 + 3 + 3 + 7) * 16
    assert w.bytes == (5 + 3 + 7) * (16 + 4)


def test_pq_scan_union_is_below_per_query_reading():
    """A batch reads each probed cell once at least, never more than the
    queries one by one would: the count lies under both schemes."""
    rng = np.random.default_rng(0)
    counts = rng.integers(0, 600, 256)
    probes = np.stack([rng.choice(256, 16, replace=False) for _ in range(64)])
    batch = work.pq_scan(probes, counts, 16)
    one_by_one = sum((work.pq_scan(p[None], counts, 16) for p in probes),
                     work.ZERO)
    assert batch.ops == one_by_one.ops
    assert batch.bytes <= one_by_one.bytes
    padded_slots = 256 * 2048 * (16 + 4)  # every cell padded to 2048 slots
    assert batch.bytes <= padded_slots


def test_rescore_and_least_time():
    w = work.rescore(m=2, kp=1024, d=128)
    assert w.bytes == 2 * 1024 * 128 * 4
    p = work.load_peaks("TPU v5 lite")
    assert work.least_time(w, p) == pytest.approx(w.bytes / 819e9)
    big = work.allpairs(160_000, 256, 100)
    assert work.least_time(big, p) == pytest.approx(big.ops / 197e12)
    assert 0.03 < work.least_time(big, p) < 0.04  # ~33 ms
