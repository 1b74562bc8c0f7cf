"""Cumulatively-computable distance functions (paper Sect. 3).

The paper assumes the distance ``delta`` can be computed *cumulatively*: there
is a step function ``dbar(u_c, v_c, acc) -> acc`` applied coordinate-by-
coordinate plus a finalizer.  This is exactly what lets the GPU algorithm
stream ``C2``-sized coordinate chunks through shared memory; on TPU it is what
lets the Pallas kernel stream ``d``-chunks through VMEM while the running
accumulator lives in registers/VMEM scratch.

Two evaluation paths are provided for every distance:

* ``accumulate(x_chunk, y_chunk, acc)`` — the faithful cumulative form,
  operating on a coordinate chunk of both operands (vectorized over the tile).
* ``matmul_form`` — when the cumulative step is expressible through an inner
  product (squared-euclidean, dot, cosine), the tile can instead be computed
  as ``f(x) @ g(y)^T`` plus rank-1 corrections.  On TPU this is the difference
  between VPU elementwise streaming and the 128x128 MXU; we use it whenever
  the distance allows (DESIGN.md "hardware adaptation").

All distances are *smaller-is-nearer*; similarities (dot, cosine) are negated.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

Array = jnp.ndarray

# Precision of every dot product an exact distance rests on.  On the TPU the
# default for float32 operands is one bfloat16 pass (~1e-3 relative), which
# ranks near neighbours wrongly; HIGHEST keeps float32.  The CPU computes in
# float32 either way.
EXACT = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class Distance:
    """A cumulatively computable distance function.

    Attributes:
      name: identifier used by configs / CLI.
      init: initial accumulator value (the paper's ``a_1``).
      accumulate: ``(x_chunk[m,c], y_chunk[n,c], acc[m,n]) -> acc[m,n]``
        cumulative step over a coordinate chunk (paper's ``dbar`` batched over
        a tile).
      finalize: applied once after all chunks.
      matmul_form: if not None, ``(fx, gy, hx, hy)`` such that the full tile is
        ``finalize(hx[:,None] + hy[None,:] + fx @ gy^T)`` — the MXU-friendly
        rewrite.  ``fx/gy`` map chunks of x/y; ``hx/hy`` produce per-row/col
        rank-1 corrections (also cumulative over chunks).
      pre: whole-vector transform applied before chunked accumulation (e.g.
        row-normalization for cosine — the only non-chunkable step).
      needs_positive: inputs must be positive (KL / Hellinger on distributions).
    """

    name: str
    init: float
    accumulate: Callable[[Array, Array, Array], Array]
    finalize: Callable[[Array], Array]
    matmul_form: "MatmulForm | None" = None
    pre: Callable[[Array], Array] | None = None
    needs_positive: bool = False

    def pairwise(self, x: Array, y: Array, chunk: int | None = None) -> Array:
        """Reference pairwise evaluation (cumulative path), O(m*n*d).

        ``chunk`` mimics the paper's C2 streaming; ``None`` uses one chunk.
        """
        if self.pre is not None:
            x = self.pre(x)
            y = self.pre(y)
        m, d = x.shape
        n, _ = y.shape
        c = d if chunk is None else chunk
        acc = jnp.full((m, n), self.init, dtype=jnp.promote_types(x.dtype, jnp.float32))
        for lo in range(0, d, c):
            acc = self.accumulate(x[:, lo : lo + c], y[:, lo : lo + c], acc)
        return self.finalize(acc)


@dataclasses.dataclass(frozen=True)
class MatmulForm:
    """MXU rewrite: tile = finalize(hx[:,None] + hy[None,:] + alpha * fx@gy^T)."""

    fx: Callable[[Array], Array]
    gy: Callable[[Array], Array]
    hx: Callable[[Array], Array]  # (m,d) -> (m,)
    hy: Callable[[Array], Array]  # (n,d) -> (n,)
    alpha: float = 1.0

    def pairwise(self, x: Array, y: Array, finalize) -> Array:
        fx = self.fx(x).astype(jnp.float32)
        gy = self.gy(y).astype(jnp.float32)
        tile = self.alpha * jnp.matmul(fx, gy.T, precision=EXACT)
        tile = tile + self.hx(x)[:, None] + self.hy(y)[None, :]
        return finalize(tile)


_EPS = 1e-12


def _sqeuclidean_acc(xc, yc, acc):
    diff = xc[:, None, :] - yc[None, :, :]
    return acc + jnp.sum(diff * diff, axis=-1)


def _dot_acc(xc, yc, acc):
    return acc + jnp.einsum("mc,nc->mn", xc, yc, precision=EXACT)


def _hellinger_acc(xc, yc, acc):
    # H^2(p, q) = 1/2 * sum (sqrt(p_i) - sqrt(q_i))^2 ; accumulate the sum.
    diff = jnp.sqrt(jnp.maximum(xc[:, None, :], 0.0)) - jnp.sqrt(
        jnp.maximum(yc[None, :, :], 0.0)
    )
    return acc + jnp.sum(diff * diff, axis=-1)


def _kl_acc(xc, yc, acc):
    # KL(p || q) = sum p_i * (log p_i - log q_i); asymmetric but cumulative.
    p = jnp.maximum(xc[:, None, :], _EPS)
    q = jnp.maximum(yc[None, :, :], _EPS)
    return acc + jnp.sum(p * (jnp.log(p) - jnp.log(q)), axis=-1)


SQEUCLIDEAN = Distance(
    name="sqeuclidean",
    init=0.0,
    accumulate=_sqeuclidean_acc,
    finalize=lambda a: a,
    matmul_form=MatmulForm(
        fx=lambda x: x,
        gy=lambda y: y,
        hx=lambda x: jnp.sum(x.astype(jnp.float32) ** 2, axis=-1),
        hy=lambda y: jnp.sum(y.astype(jnp.float32) ** 2, axis=-1),
        alpha=-2.0,
    ),
)

EUCLIDEAN = Distance(
    name="euclidean",
    init=0.0,
    accumulate=_sqeuclidean_acc,
    finalize=lambda a: jnp.sqrt(jnp.maximum(a, 0.0)),
    matmul_form=MatmulForm(
        fx=lambda x: x,
        gy=lambda y: y,
        hx=lambda x: jnp.sum(x.astype(jnp.float32) ** 2, axis=-1),
        hy=lambda y: jnp.sum(y.astype(jnp.float32) ** 2, axis=-1),
        alpha=-2.0,
    ),
)

# Similarities: negate so that smaller == nearer, uniform with distances.
NEG_DOT = Distance(
    name="neg_dot",
    init=0.0,
    accumulate=lambda xc, yc, acc: acc - jnp.einsum("mc,nc->mn", xc, yc,
                                                    precision=EXACT),
    finalize=lambda a: a,
    matmul_form=MatmulForm(
        fx=lambda x: x,
        gy=lambda y: y,
        hx=lambda x: jnp.zeros(x.shape[:1], jnp.float32),
        hy=lambda y: jnp.zeros(y.shape[:1], jnp.float32),
        alpha=-1.0,
    ),
)

NEG_COSINE = Distance(
    name="neg_cosine",
    init=0.0,
    # Cumulative over chunks after the `pre` row-normalization (the only
    # non-chunkable step; the paper's dbar model allows such a prolog).
    accumulate=lambda xc, yc, acc: acc - jnp.einsum("mc,nc->mn", xc, yc,
                                                    precision=EXACT),
    finalize=lambda a: a,
    pre=lambda x: x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), _EPS),
    matmul_form=MatmulForm(
        fx=lambda x: x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), _EPS),
        gy=lambda y: y / jnp.maximum(jnp.linalg.norm(y, axis=-1, keepdims=True), _EPS),
        hx=lambda x: jnp.zeros(x.shape[:1], jnp.float32),
        hy=lambda y: jnp.zeros(y.shape[:1], jnp.float32),
        alpha=-1.0,
    ),
)

HELLINGER = Distance(
    name="hellinger",
    init=0.0,
    accumulate=_hellinger_acc,
    finalize=lambda a: jnp.sqrt(jnp.maximum(0.5 * a, 0.0)),
    # sqrt-space inner product: H^2 = 1 - <sqrt p, sqrt q> for distributions.
    matmul_form=MatmulForm(
        fx=lambda x: jnp.sqrt(jnp.maximum(x, 0.0)),
        gy=lambda y: jnp.sqrt(jnp.maximum(y, 0.0)),
        hx=lambda x: 0.5 * jnp.sum(jnp.maximum(x.astype(jnp.float32), 0.0), axis=-1),
        hy=lambda y: 0.5 * jnp.sum(jnp.maximum(y.astype(jnp.float32), 0.0), axis=-1),
        alpha=-1.0,
    ),
    needs_positive=True,
)
# Hellinger via matmul needs finalize(sqrt(0.5*(hx+hy) - fx@gy^T)) == sqrt of
# (0.5*sum p + 0.5*sum q - sum sqrt(p q)). finalize above is sqrt(0.5*a) for the
# cumulative path where a = sum (sqrt p - sqrt q)^2 = sum p + sum q - 2 sqrt(pq).
# The matmul form produces a' = 0.5 sum p + 0.5 sum q - sum sqrt(pq) = 0.5*a, so
# we must NOT halve again; handled by `matmul_finalize` below.


def matmul_finalize(dist: Distance):
    """Finalizer to use with the matmul form (accounts for prefactor folding)."""
    if dist.name in ("hellinger",):
        return lambda a: jnp.sqrt(jnp.maximum(a, 0.0))
    return dist.finalize


KL = Distance(
    name="kl",
    init=0.0,
    accumulate=_kl_acc,
    finalize=lambda a: a,
    # KL(p||q) = sum p log p - sum p log q = hx + p @ (-log q)^T : MXU-friendly.
    matmul_form=MatmulForm(
        fx=lambda x: jnp.maximum(x, _EPS),
        gy=lambda y: -jnp.log(jnp.maximum(y, _EPS)),
        hx=lambda x: jnp.sum(
            jnp.maximum(x.astype(jnp.float32), _EPS)
            * jnp.log(jnp.maximum(x.astype(jnp.float32), _EPS)),
            axis=-1,
        ),
        hy=lambda y: jnp.zeros(y.shape[:1], jnp.float32),
        alpha=1.0,
    ),
    needs_positive=True,
)

REGISTRY: dict[str, Distance] = {
    d.name: d
    for d in (SQEUCLIDEAN, EUCLIDEAN, NEG_DOT, NEG_COSINE, HELLINGER, KL)
}


def get_distance(name: str) -> Distance:
    try:
        return REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown distance {name!r}; have {sorted(REGISTRY)}") from None


def is_symmetric(name: str) -> bool:
    """Paper Sect. 3: symmetric distances admit the half-triangle optimization."""
    return name != "kl"


# ---------------------------------------------------------------------------
# Row quantization for the two-stage scan (DESIGN.md §Quantized).
# ---------------------------------------------------------------------------

# Canonical scan dtypes, plus the short spellings the CLIs accept.
SCAN_DTYPES = ("float32", "bfloat16", "int8")
_SCAN_DTYPE_ALIASES = {"fp32": "float32", "f32": "float32", "bf16": "bfloat16"}

# Distances whose ``gy`` map is row-local and invertible enough that the
# rank-1 ``hy`` term of the DEQUANTIZED rows equals ``mf.hy`` applied to them
# directly (identity for sqeuclidean/euclidean/neg_dot, row-normalization for
# neg_cosine — where hy is zero anyway).  KL / Hellinger quantize their
# log/sqrt-space rows nonlinearly; extending them means deriving hy in that
# space, which no serving config needs yet.
QUANTIZABLE = ("sqeuclidean", "euclidean", "neg_dot", "neg_cosine")


def canonical_scan_dtype(name: str) -> str:
    name = _SCAN_DTYPE_ALIASES.get(str(name), str(name))
    if name not in SCAN_DTYPES:
        raise ValueError(f"unknown scan dtype {name!r}; have {SCAN_DTYPES}")
    return name


def gy_rows(y: Array, distance: str) -> Array:
    """Rows mapped to MXU ``gy`` space — the geometry every compressed
    replica (scalar, IVF cells, PQ codebooks) is built in.

    Only ``QUANTIZABLE`` distances participate: the map must be row-local so
    per-row structures (scales, cell assignments, codes) survive it.
    """
    dist = get_distance(distance)
    if distance not in QUANTIZABLE:
        raise ValueError(
            f"distance {distance!r} has no row-local gy map; "
            f"have {QUANTIZABLE}")
    return dist.matmul_form.gy(jnp.asarray(y, jnp.float32)).astype(jnp.float32)


class QuantizedRows(NamedTuple):
    """A low-precision replica of a database, pre-mapped to MXU ``gy`` space.

    The scan kernel computes ``finalize(alpha * (fx @ data^T) * scale + hx +
    hy)`` — the per-row symmetric scale folds into the same rank-1 epilogue
    that already carries ``hy``, so dequantization costs zero extra HBM
    traffic over the fp32 kernel (DESIGN.md §Quantized).

    data:  [n, d] rows in ``float32`` / ``bfloat16`` / ``int8``.
    scale: [n] fp32 per-row symmetric scales (int8 only, else None).
    hy:    [n] fp32 rank-1 term of the DEQUANTIZED rows — the scanned
           distance is exactly the distance to the dequantized corpus, so
           the only retrieval error is candidate ordering, which the exact
           rescore stage repairs.
    """

    data: Array
    scale: Array | None
    hy: Array


def quantize_rows(y: Array, scan_dtype: str, *,
                  distance: str = "sqeuclidean") -> QuantizedRows:
    """Build the quantized scan replica of database rows ``y`` [n, d].

    int8 uses per-row symmetric scales ``max|row| / 127`` with deterministic
    round-to-nearest (a scan replica must be reproducible across rebuilds;
    stochastic rounding buys nothing without a gradient to unbias).
    """
    scan_dtype = canonical_scan_dtype(scan_dtype)
    dist = get_distance(distance)
    if distance not in QUANTIZABLE:
        raise ValueError(
            f"distance {distance!r} has no quantized scan form; have {QUANTIZABLE}")
    g = dist.matmul_form.gy(jnp.asarray(y, jnp.float32)).astype(jnp.float32)
    if scan_dtype == "float32":
        data, scale = g, None
    elif scan_dtype == "bfloat16":
        data, scale = g.astype(jnp.bfloat16), None
    else:  # int8
        amax = jnp.max(jnp.abs(g), axis=-1)
        scale = jnp.maximum(amax, _EPS) / 127.0
        q = jnp.round(g / scale[:, None])
        data = jnp.clip(q, -127, 127).astype(jnp.int8)
    deq = _dequantize(data, scale)
    return QuantizedRows(data, scale, dist.matmul_form.hy(deq).astype(jnp.float32))


def _dequantize(data: Array, scale: Array | None) -> Array:
    deq = data.astype(jnp.float32)
    return deq if scale is None else deq * scale[:, None]


def dequantize_rows(q: QuantizedRows) -> Array:
    """fp32 rows the quantized scan effectively scores against."""
    return _dequantize(q.data, q.scale)
