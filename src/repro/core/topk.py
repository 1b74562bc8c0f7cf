"""Top-k selection primitives (paper Sect. 6, adapted to TPU).

The paper keeps, per row, a k-element max-heap in GPU memory and lets each
thread filter candidates against the heap top (the current k-th smallest)
before taking a lock and pushing.  TPUs have no per-thread scalar heaps and no
cheap fine-grained synchronization — the idiomatic equivalent is a *vectorized
selection network* with completely static dataflow:

* the running "heap" is an ascending-sorted length-K buffer per row
  (K = next_pow2(k)), the k-th smallest readable at position k-1 in O(1),
  exactly the property the paper wants from its descending heap;
* a candidate tile is reduced with a bitonic sorting network (log^2 K
  compare-exchange stages, each two lane rotations and a select — no
  gathers, no reshapes of the lane axis, no data-dependent control flow);
* two sorted K-buffers are merged with the classic bitonic *top-k merge*:
  elementwise min(a_i, b_rev_i) holds exactly the K smallest of the union and
  is bitonic, so one log-K merge network re-sorts it;
* the paper's "skip candidates that do not beat the heap top" trick becomes a
  per-tile ``lax.cond`` on ``any(tile < kth_best)`` — a whole-tile skip, the
  vector analogue of the thread-local buffer filter.

These primitives are shared by the pure-jnp reference implementation, the
Pallas kernels (repro.kernels.stream_topk / fused_knn) and the distributed
tree-merge (repro.core.distributed).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

Array = jnp.ndarray

NEG_INF = float("-inf")
POS_INF = float("inf")


def next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def resolve_threshold_skip(flag: bool | None, *, pallas: bool) -> bool:
    """One repo-wide default policy for the paper's heap-top filter.

    ``None`` (every public entry point's default) resolves per execution
    substrate: ON inside Pallas kernels, where ``pl.when`` predication is
    near-free, and OFF on the jnp/XLA paths, where the ``lax.cond`` guard
    measurably costs more than the merges it skips (EXPERIMENTS.md §Perf,
    refuted-hypothesis log; tradeoff documented in DESIGN.md §Quantized,
    "threshold-skip policy").  An explicit bool always wins — that is how
    ``benchmarks/selection.py`` A/Bs the two settings.
    """
    if flag is None:
        return pallas
    return bool(flag)


# ---------------------------------------------------------------------------
# Bitonic network on the lane (last) axis.  The Pallas TPU lowering refuses
# `rev` and any reshape that splits or merges the lane axis, so a partner
# exchange (lane i <-> lane i XOR j) is two lane rotations and a select, and
# the K-wide groups of a tile sit side by side on the lane axis.  The same
# code runs on the XLA paths and inside the kernels.
# ---------------------------------------------------------------------------

LANES = 128  # lane width of a TPU vector register


def _lane_pos(x: Array) -> Array:
    """Lane index, shaped to broadcast against ``x``."""
    shape = (1,) * (x.ndim - 1) + (x.shape[-1],)
    return jax.lax.broadcasted_iota(jnp.int32, shape, x.ndim - 1)


# ``pltpu.roll`` is XLA's roll off the TPU and a native lane rotation in a
# kernel (``jnp.roll`` on int32 lanes aborts the TPU compiler).  The
# primitive has no eager rule, hence the jit.
_roll = jax.jit(pltpu.roll, static_argnums=(1, 2))


def _partner(x: Array, j: int) -> Array:
    """``x`` at lane ``i XOR j``: one rotation by j, one by L - j, a select.

    Which of the two rotations brings lane ``i XOR j`` is read off a rotated
    lane iota, so the select holds whichever way the rotation turns.
    """
    L, ax = x.shape[-1], x.ndim - 1
    pos = _lane_pos(x)
    take_a = _roll(pos, j, ax) == (pos ^ j)
    return jnp.where(take_a, _roll(x, j, ax), _roll(x, L - j, ax))


def _stage(vals: Array, idx: Array, j: int, dir_bit: int, descending: bool):
    """One compare-exchange stage at distance ``j``.

    A block sorts ascending where ``lane & dir_bit == 0`` (everywhere when
    ``dir_bit == 0``), the other way where ``descending``.  Ties go to the
    lower lane, so value/index pairs stay consistent between the two halves.
    """
    pos = _lane_pos(vals)
    lower = (pos & j) == 0
    up = ((pos & dir_bit) == 0) != descending
    pvals = _partner(vals, j)
    pidx = _partner(idx, j)
    self_lt = (vals < pvals) | ((vals == pvals) & lower)
    take_self = (up == lower) == self_lt
    return jnp.where(take_self, vals, pvals), jnp.where(take_self, idx, pidx)


def _merge_groups(vals: Array, idx: Array, K: int, dir_bit: int,
                  descending: bool):
    """Sort every *bitonic* aligned K-lane group: the last log2 K stages."""
    j = K // 2
    while j >= 1:
        vals, idx = _stage(vals, idx, j, dir_bit, descending)
        j //= 2
    return vals, idx


def _sort_groups(vals: Array, idx: Array, K: int, dir_bit: int,
                 descending: bool):
    """Bitonic sort of every aligned K-lane group; direction as in ``_stage``."""
    size = 2
    while size <= K:
        vals, idx = _merge_groups(vals, idx, size,
                                  size if size < K else dir_bit, descending)
        size *= 2
    return vals, idx


def bitonic_sort_kv(vals: Array, idx: Array, ascending: bool = True):
    """Full bitonic sort of (vals, idx) along the last axis (length = 2^p).

    Static O(log^2 L) network of lane rotations and selects: no gathers and
    no data-dependent control flow.
    """
    L = vals.shape[-1]
    assert L & (L - 1) == 0, f"bitonic sort needs pow2 length, got {L}"
    return _sort_groups(vals, idx, L, 0, not ascending)


def _reverse(x: Array) -> Array:
    """Lane reversal, as the exchanges XOR 1, 2, ..., L/2 composed:
    ``i XOR (L - 1) == L - 1 - i``."""
    j = 1
    while j < x.shape[-1]:
        x = _partner(x, j)
        j *= 2
    return x


def merge_topk_bitonic(av: Array, ai: Array, bv: Array, bi: Array, K: int):
    """K smallest of ``a ∪ b`` in every aligned K-lane group, ascending.

    ``a``'s groups ascend and ``b``'s descend, so the lanewise min holds
    exactly the K smallest of the union and is bitonic; one log-K merge
    network sorts it.
    """
    a_wins = av <= bv
    return _merge_groups(jnp.where(a_wins, av, bv), jnp.where(a_wins, ai, bi),
                         K, 0, False)


def merge_topk_sorted(av: Array, ai: Array, bv: Array, bi: Array):
    """Merge two ascending length-K (value, index) sets, keep K smallest, sorted.

    Classic bitonic top-k merge: ``min(a_i, reverse(b)_i)`` contains exactly the
    K smallest of the union and is bitonic; one merge network sorts it.
    O(log K) stages vs O(K log K) for a full re-sort.
    """
    return merge_topk_bitonic(av, ai, _reverse(bv), _reverse(bi),
                              av.shape[-1])


def kernel_k(k: int) -> int:
    """Network width K inside a kernel: next_pow2(k), but at least 2 — a
    network with no compare-exchange stage (K = 1) aborts the TPU compiler
    (libtpu 0.0.34).  Kernels still return next_pow2(k) columns."""
    return max(next_pow2(k), 2)


def reduce_width(L: int, K: int) -> int:
    """Lane width of ``reduce_topk``'s result for an L-wide tile."""
    return max(K, min(L, LANES))


def reduce_topk(vals: Array, idx: Array, K: int, *, descending: bool = False):
    """K smallest of each row of an L-wide tile, L = K * 2^t.

    Returns ``reduce_width(L, K)``-wide arrays whose lanes [0, K) hold the
    winners sorted (ascending, or descending where ``descending``); the
    lanes after them are don't-care.  The K-lane groups sort in alternating
    directions and pair off at distances D = L/2, ..., K: the lower group
    keeps the lanewise min of an ascending and a descending group, which is
    the K smallest of both and bitonic, so one merge re-sorts it.  Pairs at
    D >= LANES are aligned halves, and the upper half is dropped; below that
    the partner arrives by lane rotations and the upper lanes go stale.
    """
    L = vals.shape[-1]
    assert L % K == 0 and (L // K) & (L // K - 1) == 0, (L, K)

    def direction(D):  # groups pairing at D sort in opposite directions
        return (D, False) if D >= K else (0, descending)

    D = L // 2
    vals, idx = _sort_groups(vals, idx, K, *direction(D))
    while D >= K:
        if D >= LANES:
            hi_v, hi_i = vals[..., D:], idx[..., D:]
            vals, idx = vals[..., :D], idx[..., :D]
        else:
            hi_v, hi_i = _partner(vals, D), _partner(idx, D)
        keep = vals <= hi_v
        vals, idx = jnp.where(keep, vals, hi_v), jnp.where(keep, idx, hi_i)
        D //= 2
        vals, idx = _merge_groups(vals, idx, K, *direction(D))
    return vals, idx


# ---------------------------------------------------------------------------
# Tile reduction + streaming scan (the pure-JAX reference used by core.knn).
# ---------------------------------------------------------------------------


def tile_topk(tile: Array, K: int, col_offset) -> tuple[Array, Array]:
    """Ascending top-K (smallest) of each row of ``tile`` [m, bn], global indices."""
    m, bn = tile.shape
    if bn < K:
        pad = jnp.full((m, K - bn), POS_INF, tile.dtype)
        tile = jnp.concatenate([tile, pad], axis=1)
    neg_vals, loc = jax.lax.top_k(-tile, K)  # descending of negated = ascending
    vals = -neg_vals
    idx = jnp.where(vals < POS_INF, loc + col_offset, jnp.int32(-1))
    return vals, idx.astype(jnp.int32)


def init_running(m: int, k: int, dtype=jnp.float32):
    K = next_pow2(k)
    return (
        jnp.full((m, K), POS_INF, dtype),
        jnp.full((m, K), -1, jnp.int32),
    )


def update_running(run_v, run_i, tile, col_offset, *, threshold_skip: bool = True):
    """Fold one distance tile into the running top-K state.

    ``threshold_skip``: vector analogue of the paper's heap-top filter — if no
    element of the tile beats the current k-th best of any row, skip the whole
    merge (a single cheap reduction guards the expensive selection network).
    """
    K = run_v.shape[-1]

    def do_merge(args):
        rv, ri = args
        tv, ti = tile_topk(tile, K, col_offset)
        return merge_topk_sorted(rv, ri, tv, ti)

    if not threshold_skip:
        return do_merge((run_v, run_i))

    kth = run_v[:, -1:]  # worst kept value per row (ascending buffer)
    any_better = jnp.any(tile < kth)
    return jax.lax.cond(any_better, do_merge, lambda args: args, (run_v, run_i))


def finalize_topk(run_v, run_i, k: int):
    return run_v[:, :k], run_i[:, :k]


@functools.partial(jax.jit, static_argnames=("k",))
def topk_smallest(x: Array, k: int):
    """Reference: ascending k smallest of each row + indices (lax.top_k based)."""
    neg_vals, idx = jax.lax.top_k(-x, k)
    return -neg_vals, idx.astype(jnp.int32)


def pad_topk(vals: Array, idx: Array, K: int):
    """Pad ascending top-k sets [..., k] out to width ``K`` (+inf values, -1 ids).

    The padded set is still ascending-sorted, so it composes directly with
    ``merge_topk_sorted`` — this is how the serving engine aligns candidate
    sets of different widths (main vs delta segment) before the bitonic merge.
    """
    k = vals.shape[-1]
    if k == K:
        return vals, idx
    assert K > k, (K, k)
    pv = jnp.full(vals.shape[:-1] + (K - k,), POS_INF, vals.dtype)
    pi = jnp.full(idx.shape[:-1] + (K - k,), -1, idx.dtype)
    return jnp.concatenate([vals, pv], axis=-1), jnp.concatenate([idx, pi], axis=-1)


def merge_many_sorted(vals: Array, idx: Array, k: int):
    """Merge ``[S, m, K]`` stacked ascending partial top-K sets → ``[m, K]``.

    Binary tree of pairwise bitonic merges — host/device final merge of the
    paper's per-GPU heaps, in log2(S) rounds.
    """
    S = vals.shape[0]
    while S > 1:
        half = S // 2
        mv, mi = merge_topk_sorted(
            vals[:half], idx[:half], vals[half : 2 * half], idx[half : 2 * half]
        )
        if S % 2:
            mv = jnp.concatenate([mv, vals[-1:]], axis=0)
            mi = jnp.concatenate([mi, idx[-1:]], axis=0)
        vals, idx = mv, mi
        S = vals.shape[0]
    return finalize_topk(vals[0], idx[0], k)
