"""Pallas TPU kernel: batched exact rescore of gathered candidate rows.

Stage 2 of the quantized two-stage retrieval (DESIGN.md §Quantized): the
bf16/int8 scan over-fetches K' = overfetch * K candidate rows per query; this
kernel re-scores those candidates against the fp32 corpus rows and re-ranks
them exactly.  The candidate GATHER itself (``db[cand_idx]``) stays in XLA —
arbitrary-row gathers are XLA's job; what the kernel fuses is everything
after the gather: per-pair exact distance + top-k selection, so the [m, K']
exact-distance matrix never exists in HBM (same fusion argument as
``fused_knn``).

Grid: (m/bm, d/bd).  Block operands: the query block's MXU-form rows
[bm, bd], the gathered candidate rows [bm, K', bd], and the rank-1 epilogue
terms; the inner product accumulates over d-chunks in a [bm, K'] VMEM
scratch (a batched row-vs-row dot — VPU multiply-reduce, no [bm, bn] tile
exists for the MXU here); the last chunk applies the epilogue, masks invalid
candidates (their ``hy`` is pre-set to +inf by the wrapper), and emits the
ascending top-K values plus each winner's POSITION in the candidate list —
the wrapper maps positions back to database rows via ``cand_idx``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import topk as T
from repro.kernels._backend import resolve_interpret
from repro.core.distances import get_distance, matmul_finalize


def _kernel(K, nk, alpha, finalize):
    def kernel(fx_ref, cand_ref, hx_ref, hyc_ref, out_v_ref, out_p_ref, acc):
        kd = pl.program_id(1)

        @pl.when(kd == 0)
        def _init():
            acc[...] = jnp.zeros_like(acc)

        # Batched per-row dot: acc[i, c] += <fx[i, :], cand[i, c, :]>.
        acc[...] += jnp.sum(
            fx_ref[...][:, None, :].astype(jnp.float32)
            * cand_ref[...].astype(jnp.float32),
            axis=-1,
        )

        @pl.when(kd == nk - 1)
        def _select():
            tile = finalize(alpha * acc[...] + hx_ref[...] + hyc_ref[...])
            pos = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 1)
            tv, tp = T.reduce_topk(tile, pos, K)
            out_v_ref[...] = tv
            out_p_ref[...] = tp

    return kernel


@functools.partial(
    jax.jit,
    static_argnames=("k", "distance", "bm", "bd", "interpret"),
)
def rescore_topk_pallas(
    fx: jnp.ndarray,
    cand: jnp.ndarray,
    hx: jnp.ndarray,
    hy_cand: jnp.ndarray,
    k: int,
    *,
    distance: str = "sqeuclidean",
    bm: int = 128,
    bd: int = 128,
    interpret: bool | None = None,
):
    """Exact top-k over per-row candidate sets (see ops.rescore_topk).

    ``fx`` [m, d] MXU-form queries, ``cand`` [m, Kp, d] gathered gy-form
    candidate rows, ``hx`` [m, 1] / ``hy_cand`` [m, Kp] rank-1 terms (+inf
    where the candidate slot is invalid).  Requires m % bm == 0,
    d % bd == 0, and Kp = K * 2^t for K = next_pow2(k).

    Returns (values [m, K], positions [m, K]): ascending exact distances and
    each winner's index INTO the candidate axis (not the database).
    """
    interpret = resolve_interpret(interpret)
    dist = get_distance(distance)
    assert dist.matmul_form is not None, f"{distance} has no MXU form"
    m, d = fx.shape
    Kp = cand.shape[1]
    K = T.kernel_k(k)
    Wr = T.reduce_width(Kp, K)
    assert cand.shape == (m, Kp, d), (cand.shape, fx.shape)
    assert m % bm == 0 and d % bd == 0, (fx.shape, bm, bd)
    assert Kp % K == 0 and (Kp // K) & (Kp // K - 1) == 0, (Kp, K)
    nk = d // bd
    grid = (m // bm, nk)
    vals, idx = pl.pallas_call(
        _kernel(K, nk, dist.matmul_form.alpha, matmul_finalize(dist)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bd), lambda i, kd: (i, kd)),
            pl.BlockSpec((bm, Kp, bd), lambda i, kd: (i, 0, kd)),
            pl.BlockSpec((bm, 1), lambda i, kd: (i, 0)),
            pl.BlockSpec((bm, Kp), lambda i, kd: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bm, Wr), lambda i, kd: (i, 0)),
            pl.BlockSpec((bm, Wr), lambda i, kd: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, Wr), jnp.float32),
            jax.ShapeDtypeStruct((m, Wr), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((bm, Kp), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="rescore_topk",
    )(fx, cand, hx, hy_cand)
    return vals[:, :T.next_pow2(k)], idx[:, :T.next_pow2(k)]
