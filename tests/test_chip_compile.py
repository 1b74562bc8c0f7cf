"""The main-path kernels compile for a TPU v5e at real widths.

Compile-only: the TPU compiler is installed, and it compiles for a chip
that is described, not attached.  The interpret-mode tests elsewhere cannot
see what only Mosaic refuses (unsupported primitives, lane-axis shape casts,
VMEM overruns), so these cases do.  Nothing runs, so nothing is timed.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.pq import PQCodebook, PQCodes
from repro.kernels import ops
from repro.kernels.fused_knn import fused_knn_pallas
from repro.kernels.ivf_scan import ivf_scan_pallas
from repro.kernels.pq_scan import pq_scan_pallas
from repro.kernels.rescore import rescore_topk_pallas

M, N, D, CAP, CELLS = 256, 4096, 128, 256, 64
WIDE_CAP, NPROBE = 1024, 16  # wide scans go through the ops wrappers


@pytest.fixture(scope="module")
def one_chip():
    """devices[0] of a described v5e:2x2, with the compile cache off (a
    compile for a described chip cannot be read back without one)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *shapes):
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text


def _shapes(sharding, *specs):
    return [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in specs]


F32, BF16, I8, U8, I32, B = (jnp.float32, jnp.bfloat16, jnp.int8, jnp.uint8,
                             jnp.int32, jnp.bool_)


@pytest.mark.parametrize("k,d,gy", [(10, D, F32), (10, D, I8), (100, 256, F32)],
                         ids=["fp32_k10", "int8_k10", "paper_k100_d256"])
def test_fused_knn_compiles(one_chip, k, d, gy):
    if gy == I8:
        fn = lambda fx, g, hx, hy, s: fused_knn_pallas(  # noqa: E731
            fx, g, hx, hy, k, gy_scale=s, n_real=N, interpret=False)
        extra = [((1, N), F32)]
    else:
        fn = functools.partial(fused_knn_pallas, k=k, n_real=N,
                               interpret=False)
        extra = []
    _compile(fn, *_shapes(one_chip, ((M, d), F32), ((N, d), gy),
                          ((M, 1), F32), ((1, N), F32), *extra))


def test_ivf_scan_compiles(one_chip):
    fn = lambda p, fx, gy, hx, hy: ivf_scan_pallas(  # noqa: E731
        p, fx, gy, hx, hy, 10, cell_cap=CAP, interpret=False)
    _compile(fn, *_shapes(one_chip, ((1, CELLS), I32), ((M, D), F32),
                          ((CELLS * CAP, D), F32), ((M, 1), F32),
                          ((1, CELLS * CAP), F32)))


def test_pq_scan_compiles(one_chip):
    pq_m, ncodes = 16, 256
    fn = lambda p, lut, codes, hx, hy, qc, cells: pq_scan_pallas(  # noqa: E731
        p, lut, codes, hx, hy, 10, cell_cap=CAP, ncodes=ncodes, qc=qc,
        cells=cells, interpret=False)
    _compile(fn, *_shapes(one_chip, ((1, CELLS), I32),
                          ((3, M, pq_m * ncodes), BF16),
                          ((pq_m, CELLS * CAP), U8),
                          ((M, 1), F32), ((1, CELLS * CAP), F32),
                          ((M, CELLS), F32), ((M, 16), I32)))


def test_rescore_topk_compiles(one_chip):
    kp = 64  # overfetch 4 x K = 16
    fn = lambda fx, cand, hx, hy: rescore_topk_pallas(  # noqa: E731
        fx, cand, hx, hy, 10, interpret=False)
    _compile(fn, *_shapes(one_chip, ((128, D), F32), ((128, kp, D), F32),
                          ((128, 1), F32), ((128, kp), F32)))


# A fetch K wider than a lane tile: the wrappers' 64-row scan tiles, the
# scoped-VMEM limit and the network's halving steps past 128 lanes
# (K = 512 pairs off a 1024-lane cell block; K = 1024 sorts it whole).
@pytest.mark.parametrize("k", [320, 1000], ids=["K512", "K1024"])
def test_pq_scan_compiles_wide(one_chip, k):
    S = CELLS * WIDE_CAP
    fn = lambda q, cbk, codes, hy, cells, cent, live: ops.pq_scan(  # noqa: E731
        q, PQCodebook(cbk), PQCodes(codes, hy), cells, k,
        cell_cap=WIDE_CAP, centroids=cent, packed_live=live,
        interpret=False).indices
    _compile(fn, *_shapes(one_chip, ((M, D), F32), ((16, 256, D // 16), F32),
                          ((S, 16), U8), ((S,), F32), ((M, NPROBE), I32),
                          ((CELLS, D), F32), ((S,), B)))


def test_ivf_scan_compiles_wide(one_chip):
    S = CELLS * WIDE_CAP
    fn = lambda q, db, cells, live: ops.ivf_scan(  # noqa: E731
        q, db, cells, 320, cell_cap=WIDE_CAP, packed_live=live,
        interpret=False).indices
    _compile(fn, *_shapes(one_chip, ((M, D), F32), ((S, D), F32),
                          ((M, NPROBE), I32), ((S,), B)))


@pytest.mark.parametrize("kp", [512, 1024])
def test_rescore_topk_compiles_wide(one_chip, kp):
    # The wrapper shrinks the query block so [bm, Kp, d] fits in VMEM.
    fn = lambda q, db, cand: ops.rescore_topk(  # noqa: E731
        q, db, cand, 10, interpret=False).indices
    _compile(fn, *_shapes(one_chip, ((M, D), F32), ((N, D), F32),
                          ((M, kp), I32)))
