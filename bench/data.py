"""The benchmark's own data, made on the device from a seed.

Copies of the distributions of the program's ``data/synthetic.py``
(``random_vectors``: i.i.d. standard normal; ``clustered_vectors``: a
Gaussian mixture of tight clusters), drawn with ``jax.random`` so that a
later change to the program cannot move the data, and so that the rows are
made on the chip in one jitted call instead of on the host.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def key(seed: int, stream: int = 0):
    """A PRNG key for (seed, stream); any non-negative seed, 64 bits or more."""
    word = np.random.SeedSequence([int(seed), int(stream)]).generate_state(
        1, np.uint32)[0]
    return jax.random.key(int(word))


@functools.partial(jax.jit, static_argnames=("n", "d", "sharding"))
def _normal(k, *, n, d, sharding=None):
    x = jax.random.normal(k, (n, d), jnp.float32)
    if sharding is not None:
        x = jax.lax.with_sharding_constraint(x, sharding)
    return x


def random_vectors(n: int, d: int, seed: int, *, sharding=None):
    """[n, d] i.i.d. standard normal float32 rows (the paper's Table 1 data)."""
    return _normal(key(seed, 0), n=n, d=d, sharding=sharding)


@functools.partial(jax.jit, static_argnames=("n", "d", "n_clusters", "skew"))
def _mixture(kc, kr, *, n, d, n_clusters, spread, skew=0.0):
    centers = jax.random.normal(kc, (n_clusters, d), jnp.float32)
    ka, kn = jax.random.split(kr)
    if skew:
        rank = jnp.arange(1, n_clusters + 1, dtype=jnp.float32)
        assign = jax.random.categorical(ka, -skew * jnp.log(rank), shape=(n,))
    else:
        assign = jax.random.randint(ka, (n,), 0, n_clusters)
    noise = jax.random.normal(kn, (n, d), jnp.float32)
    return centers[assign] + spread * noise


def clustered_vectors(n: int, d: int, mixture_seed: int, rows_seed: int,
                      stream: int, *, n_clusters: int = 64,
                      spread: float = 0.15, skew: float = 0.0):
    """[n, d] rows of a Gaussian mixture: ``n_clusters`` standard normal
    centres, each row a centre drawn uniformly (or, with ``skew`` > 0, the
    i-th centre with weight proportional to i^-skew, Zipf) plus ``spread``
    * N(0, I).

    The centres come from ``mixture_seed``, the rows from (``rows_seed``,
    ``stream``), so a corpus and its queries can share one mixture.
    """
    return _mixture(key(mixture_seed, 0), key(rows_seed, stream), n=n, d=d,
                    n_clusters=n_clusters, spread=float(spread),
                    skew=float(skew))
