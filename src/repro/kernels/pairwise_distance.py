"""Pallas TPU kernel: tiled pairwise-distance matrix (paper Sect. 5, phase 1).

Hardware adaptation (see DESIGN.md): the paper streams C2-sized coordinate
chunks of both operands through CUDA shared memory so that 16 consecutive
threads make coalesced 128-byte fetches.  The TPU analogue is BlockSpec VMEM
tiling: HBM->VMEM copies of (bm, bd) / (bn, bd) chunks are issued by the
Pallas pipeline (always "coalesced" — contiguous DMA), and the per-chunk
accumulation runs on the MXU as a (bm x bd) @ (bd x bn) matmul because every
registry distance admits the rewrite

    delta(x, y) = finalize( alpha * f(x) @ g(y)^T + hx(x) + hy(y) )

(squared-euclidean: f=g=id, alpha=-2, hx/hy = squared norms; KL / Hellinger /
cosine analogous — repro.core.distances.MatmulForm).  ``bd`` plays the role of
the paper's C2: it must be a multiple of the 128-lane register width just as
C2 had to be a multiple of 32 floats for coalescing.

A separate ``cumulative=True`` path evaluates the paper's generic dbar
coordinate-by-coordinate on the VPU for distances with no inner-product form.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.distances import EXACT
from repro.kernels._backend import resolve_interpret


def _matmul_kernel(finalize, alpha, n_dchunks):
    """Kernel body: acc over d-chunks, epilogue applies alpha/hx/hy/finalize."""

    def kernel(fx_ref, gy_ref, hx_ref, hy_ref, out_ref, acc_ref):
        kd = pl.program_id(2)

        @pl.when(kd == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += jax.lax.dot_general(
            fx_ref[...],
            gy_ref[...],
            (((1,), (1,)), ((), ())),
            precision=EXACT,
            preferred_element_type=jnp.float32,
        )

        @pl.when(kd == n_dchunks - 1)
        def _epilogue():
            tile = alpha * acc_ref[...] + hx_ref[...] + hy_ref[...]
            out_ref[...] = finalize(tile)

    return kernel


def _cumulative_kernel(accumulate, finalize, init, n_dchunks, bd):
    """Generic dbar path: per-coordinate VPU accumulation (paper's Fig. 7)."""

    def kernel(x_ref, y_ref, out_ref, acc_ref):
        kd = pl.program_id(2)

        @pl.when(kd == 0)
        def _init():
            acc_ref[...] = jnp.full_like(acc_ref, init)

        x = x_ref[...]  # (bm, bd)
        y = y_ref[...]  # (bn, bd)

        def body(c, acc):
            return accumulate(
                jax.lax.dynamic_slice_in_dim(x, c, 1, 1),
                jax.lax.dynamic_slice_in_dim(y, c, 1, 1),
                acc,
            )

        acc_ref[...] = jax.lax.fori_loop(0, bd, body, acc_ref[...])

        @pl.when(kd == n_dchunks - 1)
        def _epilogue():
            out_ref[...] = finalize(acc_ref[...])

    return kernel


def pairwise_distance_pallas(
    fx: jnp.ndarray,
    gy: jnp.ndarray,
    hx: jnp.ndarray,
    hy: jnp.ndarray,
    *,
    alpha: float,
    finalize,
    bm: int = 256,
    bn: int = 256,
    bd: int = 128,
    interpret: bool | None = None,
):
    """MXU-form distance tile matrix: [m, n] fp32.

    Inputs must be pre-padded: m % bm == n % bn == d % bd == 0.
    ``hx``: [m, 1] fp32, ``hy``: [1, n] fp32 rank-1 corrections.
    ``interpret=None`` resolves backend-aware (Mosaic only on a real TPU).
    """
    interpret = resolve_interpret(interpret)
    m, d = fx.shape
    n, d2 = gy.shape
    assert d == d2 and m % bm == 0 and n % bn == 0 and d % bd == 0, (
        fx.shape,
        gy.shape,
        (bm, bn, bd),
    )
    n_dchunks = d // bd
    grid = (m // bm, n // bn, n_dchunks)
    return pl.pallas_call(
        _matmul_kernel(finalize, alpha, n_dchunks),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bd), lambda i, j, kd: (i, kd)),
            pl.BlockSpec((bn, bd), lambda i, j, kd: (j, kd)),
            pl.BlockSpec((bm, 1), lambda i, j, kd: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, kd: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kd: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="pairwise_distance_mxu",
    )(fx, gy, hx, hy)


def pairwise_distance_cumulative_pallas(
    x: jnp.ndarray,
    y: jnp.ndarray,
    *,
    accumulate,
    finalize,
    init: float,
    bm: int = 256,
    bn: int = 256,
    bd: int = 128,
    interpret: bool | None = None,
):
    """Generic cumulative-dbar distance tile matrix (VPU path)."""
    interpret = resolve_interpret(interpret)
    m, d = x.shape
    n, d2 = y.shape
    assert d == d2 and m % bm == 0 and n % bn == 0 and d % bd == 0
    n_dchunks = d // bd
    grid = (m // bm, n // bn, n_dchunks)
    return pl.pallas_call(
        _cumulative_kernel(_coord_accumulate(accumulate), finalize, init, n_dchunks, bd),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bd), lambda i, j, kd: (i, kd)),
            pl.BlockSpec((bn, bd), lambda i, j, kd: (j, kd)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kd: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="pairwise_distance_vpu",
    )(x, y)


def _coord_accumulate(accumulate):
    """Adapt a chunked Distance.accumulate into a single-coordinate step.

    ``accumulate`` has signature (x[m,c], y[n,c], acc[m,n]); we call it with
    c = 1 slices, which broadcasts to the (bm, bn) tile on the VPU.
    """

    def step(xc, yc, acc):
        # xc: (bm, 1), yc: (bn, 1)
        return accumulate(xc, yc, acc)

    return step
