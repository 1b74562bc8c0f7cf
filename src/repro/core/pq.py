"""Product quantization: m-subspace codebooks + ADC lookup tables (DESIGN.md §PQ).

The scalar replica (§Quantized) compresses each row to d bytes (int8); the
IVF coarse quantizer (§IVF) prunes which rows stream at all.  The remaining
production move — Jégou et al.'s product quantization, composed with IVF into
Johnson et al.'s IVFADC — compresses each d-dim row to ``m`` uint8 codes:
split the (gy-mapped) row into ``m`` subspaces of d/m coordinates, train a
2^nbits-codeword k-means codebook per subspace, and store only the per-
subspace codeword ids.  At d = 128, m = 16 that is 32x under fp32 and 8x
under int8, and the scan becomes asymmetric distance computation (ADC):
per query a [m, 2^nbits] lookup table of subspace partial dots, per row a
sum of m table entries — no matmul against the database at all.

Contract (identical to ``QuantizedRows``): the scanned value is EXACTLY the
distance to the DECODED corpus.  ``PQCodes.hy`` is precomputed from the
decoded rows, so the only retrieval error is candidate ordering, which the
exact fp32 rescore stage repairs (``core.knn.ivfpq_query``).

Residual PQ (the IVFADC recipe proper): when an IVF coarse quantizer is
present, codes encode the residual ``gy(row) − centroid[cell]`` instead of
the row itself — the codebooks then only have to cover the within-cell
spread, which is where almost all of the quantization error budget goes.
The cross term ``alpha · fx · centroid[cell]`` is per (query, cell) and rides
into the scan as a rank-1 bias (one scalar per probed cell block —
``pq_cell_bias``), never a second pass over the database.

Training reuses the shared Lloyd loop (``core.kmeans.lloyd``) — the same
implementation that trains the IVF coarse quantizer, pointed at per-subspace
row slices.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.distances import EXACT, get_distance, gy_rows
from repro.core.kmeans import lloyd

Array = jnp.ndarray


class PQCodebook(NamedTuple):
    """Per-subspace codeword tables, in the (residual) MXU ``gy`` space.

    codebooks: [m, ncodes, dsub] fp32 — subspace j's codeword c is
               ``codebooks[j, c]``; d = m * dsub, ncodes = 2^nbits.
    All geometry is derivable from the shape (jit-friendly pytree).
    """

    codebooks: Array

    @property
    def m(self) -> int:
        return self.codebooks.shape[0]

    @property
    def ncodes(self) -> int:
        return self.codebooks.shape[1]

    @property
    def dsub(self) -> int:
        return self.codebooks.shape[2]


class PQCodes(NamedTuple):
    """The PQ scan replica of a database (analogue of ``QuantizedRows``).

    codes: [n, m] uint8 — per-row subspace codeword ids.
    hy:    [n] fp32 rank-1 term of the DECODED rows (residual base included),
           so the ADC-scanned value is exactly the distance to the decoded
           corpus; dead rows are masked to +inf through this term at query
           time, exactly like the scalar replica.
    """

    codes: Array
    hy: Array


def _check_pq_geometry(d: int, m: int, nbits: int) -> int:
    if d % m != 0:
        raise ValueError(f"pq_m={m} must divide d={d}")
    if not 1 <= nbits <= 8:
        raise ValueError(f"pq_nbits={nbits} must be in [1, 8] (uint8 codes)")
    return 2 ** nbits


def train_pq(
    rows: Array,
    m: int,
    *,
    nbits: int = 8,
    iters: int = 10,
    seed: int = 0,
    impl: str = "jnp",
) -> PQCodebook:
    """Train m subspace codebooks over pre-mapped rows [n, d] (gy/residual
    space — callers map first; ``build_pq``/``build_ivfpq`` do).

    Each subspace runs the shared Lloyd loop independently with a
    subspace-salted seed (deterministic per (seed, subspace), decorrelated
    across subspaces).  Needs n >= 2^nbits distinct init rows.
    """
    n, d = rows.shape
    ncodes = _check_pq_geometry(d, m, nbits)
    assert n >= ncodes, (
        f"PQ training needs >= 2^nbits = {ncodes} rows, got {n}")
    dsub = d // m
    subs = jnp.asarray(rows, jnp.float32).reshape(n, m, dsub)
    cbs = [lloyd(subs[:, j], ncodes, iters=iters, seed=seed + j, impl=impl)[0]
           for j in range(m)]
    return PQCodebook(jnp.stack(cbs, axis=0))


@jax.jit
def encode_pq(cb: PQCodebook, rows: Array) -> Array:
    """Codes [n, m] uint8 of pre-mapped rows [n, d]: per-subspace 1-NN.

    The assignment is one more kNN problem per subspace (the same solve as
    Lloyd's assignment step) — argmin over the codebook in squared euclidean,
    which in gy/residual space is the partition that minimizes decoded-dot
    error for the ADC scan.
    """
    from repro.core.knn import knn_query

    n, d = rows.shape
    m, dsub = cb.m, cb.dsub
    assert d == m * dsub, (d, m, dsub)
    subs = jnp.asarray(rows, jnp.float32).reshape(n, m, dsub)
    cols = [knn_query(subs[:, j], cb.codebooks[j], 1,
                      distance="sqeuclidean").indices[:, 0]
            for j in range(m)]
    return jnp.stack(cols, axis=1).astype(jnp.uint8)


_DECODE_ROWS = 1 << 16


@jax.jit
def decode_pq(cb: PQCodebook, codes: Array) -> Array:
    """Decoded rows [n, d] of codes [n, m] (gy/residual space).

    Decodes ``_DECODE_ROWS`` rows at a time: the TPU pads the gathered
    [rows, m, 1, dsub] block's dsub-wide minor axis to 128 lanes (16x at
    dsub = 8), which at millions of rows is more than the chip's HBM.
    """
    n, m = codes.shape
    assert m == cb.m, (m, cb.m)

    def rows(c):
        gathered = jnp.take_along_axis(
            cb.codebooks[None], c.astype(jnp.int32)[:, :, None, None],
            axis=2)  # [rows, m, 1, dsub]
        return gathered.reshape(c.shape[0], m * cb.dsub)

    if n <= _DECODE_ROWS:
        return rows(codes)
    pad = (-n) % _DECODE_ROWS
    chunks = jnp.pad(codes, ((0, pad), (0, 0))).reshape(-1, _DECODE_ROWS, m)
    return jax.lax.map(rows, chunks).reshape(-1, m * cb.dsub)[:n]


def build_pq(
    x: Array,
    m: int,
    *,
    nbits: int = 8,
    distance: str = "sqeuclidean",
    iters: int = 10,
    seed: int = 0,
    impl: str = "jnp",
) -> tuple[PQCodebook, PQCodes]:
    """Flat (no coarse quantizer) PQ replica of corpus rows ``x`` [n, d]."""
    g = gy_rows(x, distance)
    cb = train_pq(g, m, nbits=nbits, iters=iters, seed=seed, impl=impl)
    codes = encode_pq(cb, g)
    hy = get_distance(distance).matmul_form.hy(
        decode_pq(cb, codes)).astype(jnp.float32)
    return cb, PQCodes(codes, hy)


def build_ivfpq(
    x: Array,
    ivf,
    m: int,
    *,
    nbits: int = 8,
    distance: str = "sqeuclidean",
    iters: int = 10,
    seed: int = 0,
    impl: str = "jnp",
    residual: bool = True,
) -> tuple[PQCodebook, PQCodes]:
    """PQ replica of an IVF index's CELL-PACKED rows (the IVFADC build).

    ``ivf`` is a trained ``core.ivf.IVFCells`` over ``x``; codes are emitted
    in PACKED slot order (one code row per slot, so a probed cell block is
    one contiguous code block for the scan kernel).  ``residual=True``
    encodes ``gy(row) − centroid[cell]`` — training sees the ORIGINAL rows'
    residuals only (pad slots are zero rows whose residuals are
    −centroid: real signal to a k-means fit, so they are excluded), while
    every packed slot gets encoded (pad slots carry arbitrary codes and are
    dead via the live mask at query time, never via the replica).

    Returns (codebook, PQCodes over the packed slots) — ``hy`` is the rank-1
    term of the decoded packed rows INCLUDING the residual base, keeping the
    QuantizedRows contract: scanned value == distance to the decoded corpus.
    """
    g = gy_rows(x, distance)  # [n, d], original row order
    cap = ivf.cell_cap
    if residual:
        cell_of_row = ivf.slot_of_row.astype(jnp.int32) // cap
        train_rows = g - jnp.take(ivf.centroids, cell_of_row, axis=0)
    else:
        train_rows = g
    cb = train_pq(train_rows, m, nbits=nbits, iters=iters, seed=seed,
                  impl=impl)

    g_packed = gy_rows(ivf.packed, distance)  # [S, d], packed slot order
    S = g_packed.shape[0]
    if residual:
        cell_of_slot = jnp.arange(S, dtype=jnp.int32) // cap
        base = jnp.take(ivf.centroids, cell_of_slot, axis=0)
        codes = encode_pq(cb, g_packed - base)
        decoded = base + decode_pq(cb, codes)
    else:
        codes = encode_pq(cb, g_packed)
        decoded = decode_pq(cb, codes)
    hy = get_distance(distance).matmul_form.hy(decoded).astype(jnp.float32)
    return cb, PQCodes(codes, hy)


def pq_to_arrays(cb: PQCodebook, codes: PQCodes) -> dict:
    """Host-side array dict of a trained PQ replica (snapshot payload)."""
    import numpy as np

    return {"codebooks": np.asarray(cb.codebooks),
            "codes": np.asarray(codes.codes), "hy": np.asarray(codes.hy)}


def pq_from_arrays(arrays: dict) -> tuple[PQCodebook, PQCodes]:
    """Rebuild + validate (PQCodebook, PQCodes) from ``pq_to_arrays`` output.

    Structural checks only (geometry, dtypes, code range) — a corrupted
    snapshot must fail here rather than index past the codebook inside the
    ADC scan.  Raises ``ValueError``; ``serving.snapshot`` wraps it.
    """
    import numpy as np

    missing = [f for f in ("codebooks", "codes", "hy") if f not in arrays]
    if missing:
        raise ValueError(f"PQ snapshot missing fields {missing}")
    cbs = np.asarray(arrays["codebooks"], np.float32)
    codes = np.asarray(arrays["codes"])
    hy = np.asarray(arrays["hy"], np.float32)
    if cbs.ndim != 3:
        raise ValueError(f"codebooks must be [m, ncodes, dsub], got {cbs.shape}")
    m, ncodes, _ = cbs.shape
    if ncodes & (ncodes - 1) or not 2 <= ncodes <= 256:
        raise ValueError(f"ncodes {ncodes} not a pow2 in [2, 256]")
    if codes.dtype != np.uint8 or codes.ndim != 2 or codes.shape[1] != m:
        raise ValueError(
            f"codes must be uint8 [n, m={m}], got {codes.dtype} {codes.shape}")
    if hy.shape != (codes.shape[0],):
        raise ValueError(f"hy shape {hy.shape} != ({codes.shape[0]},)")
    if ncodes < 256 and int(codes.max(initial=0)) >= ncodes:
        raise ValueError(
            f"code id {int(codes.max())} out of codebook range {ncodes}")
    return (PQCodebook(jnp.asarray(cbs)),
            PQCodes(jnp.asarray(codes), jnp.asarray(hy)))


@functools.partial(jax.jit, static_argnames=("distance",))
def build_pq_luts(cb: PQCodebook, queries: Array, *,
                  distance: str = "sqeuclidean") -> Array:
    """ADC lookup tables [mq, m, ncodes] fp32 for a query batch.

    ``lut[q, j, c] = alpha * <fx(q)[j·dsub:(j+1)·dsub], codebooks[j, c]>`` —
    the subspace partial of the MXU-form dot, prescaled by alpha so the scan
    is a pure LUT-sum + rank-1 epilogue:

        tile[q, row] = finalize(Σ_j lut[q, j, codes[row, j]]
                                (+ cell bias)  + hx[q] + hy[row])

    Built once per query batch (one [mq, d] x [d-per-subspace] einsum — the
    codebook read amortizes over the batch); both the Pallas kernel and the
    jnp reference consume THIS table, so the two paths score identically.
    """
    mf = get_distance(distance).matmul_form
    assert mf is not None, f"{distance} has no MXU form"
    fx = mf.fx(jnp.asarray(queries, jnp.float32)).astype(jnp.float32)
    mq, d = fx.shape
    assert d == cb.m * cb.dsub, (d, cb.m, cb.dsub)
    fxr = fx.reshape(mq, cb.m, cb.dsub)
    return mf.alpha * jnp.einsum("qjd,jcd->qjc", fxr, cb.codebooks,
                                 precision=EXACT)


@functools.partial(jax.jit, static_argnames=("distance",))
def pq_cell_bias(queries: Array, centroids: Array, *,
                 distance: str = "sqeuclidean") -> Array:
    """Residual-PQ cross term [mq, ncells]: ``alpha * fx(q) · centroid_c``.

    With residual codes the decoded row is ``centroid[cell] + Σ_j cw_j``, so
    the dot against a query splits into the LUT sum plus this per-(query,
    cell) scalar — constant over a cell block, which is why the scan kernel
    carries it as a [bm, 1] operand indexed by the probed cell, costing one
    broadcast add per block.
    """
    mf = get_distance(distance).matmul_form
    assert mf is not None, f"{distance} has no MXU form"
    fx = mf.fx(jnp.asarray(queries, jnp.float32)).astype(jnp.float32)
    return mf.alpha * jnp.matmul(fx, jnp.asarray(centroids, jnp.float32).T,
                                 precision=EXACT)
