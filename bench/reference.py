"""The plain float32 reference and the comparisons that decide ``correct``.

Copied in spirit from the program's bring-up checks (``chip_smoke.py``
``brute_topk`` / ``check_topk``) and kept here so that no change to the
program can move them.  Nothing in this file imports the program.

The reference is brute force: squared L2 as |q|^2 - 2 q.x + |x|^2 with the
dot at ``Precision.HIGHEST`` (on the TPU a float32 dot otherwise runs as one
bfloat16 pass), then an exact top-k in column chunks.  ``precision="bf16"``
computes the dot from bfloat16 operands, which is what the TPU's default
does: that is the control, the step below float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
CHUNK = 8192  # columns per exact top-k chunk


def _dot(q, x, precision):
    if precision == "bf16":
        return jnp.dot(q.astype(jnp.bfloat16), x.astype(jnp.bfloat16).T,
                       preferred_element_type=jnp.float32)
    assert precision == "highest", precision
    return jnp.dot(q, x.T, precision=HIGHEST)


@functools.partial(jax.jit, static_argnames=("k", "precision"))
def _block_topk(q, x, exclude, *, k, precision):
    d = (jnp.sum(q * q, 1)[:, None] - 2.0 * _dot(q, x, precision)
         + jnp.sum(x * x, 1)[None, :])
    n = x.shape[0]
    cols = jnp.arange(n)
    d = jnp.where(cols[None, :] == exclude[:, None], jnp.inf, d)
    pad = (-n) % CHUNK
    if pad:
        d = jnp.pad(d, ((0, 0), (0, pad)), constant_values=jnp.inf)
    b = d.shape[0]
    c = d.reshape(b, -1, CHUNK)
    neg, loc = jax.lax.top_k(-c, k)  # [b, chunks, k]
    glob = loc + (jnp.arange(c.shape[1]) * CHUNK)[None, :, None]
    neg, pos = jax.lax.top_k(neg.reshape(b, -1), k)
    return -neg, jnp.take_along_axis(glob.reshape(b, -1), pos, axis=1)


def brute_topk(q, x, k, *, exclude=None, precision="highest", block=128):
    """k smallest squared-L2 distances of each row of ``q`` against ``x``.

    ``exclude`` [m] is a column to leave out per row (self in all-pairs), or
    None.  Rows go through in blocks of ``block`` so the [block, n] distance
    matrix fits beside whatever else is on the device.  Returns numpy
    (values [m, k] ascending, ids [m, k]).
    """
    q = jnp.asarray(q, jnp.float32)
    m = q.shape[0]
    ex = (jnp.full((m,), -1, jnp.int32) if exclude is None
          else jnp.asarray(exclude, jnp.int32))
    vals, ids = [], []
    for s in range(0, m, block):
        v, i = _block_topk(q[s:s + block], x, ex[s:s + block], k=k,
                           precision=precision)
        vals.append(np.asarray(v))
        ids.append(np.asarray(i))
    return np.concatenate(vals), np.concatenate(ids)


@jax.jit
def _pair_dist(q, rows):
    """Squared L2 of q [b, d] to rows [b, k, d], the reference's formula."""
    dot = jnp.einsum("bd,bkd->bk", q, rows, precision=HIGHEST)
    return jnp.sum(q * q, 1)[:, None] - 2.0 * dot + jnp.sum(rows * rows, 2)


def distances_of(q, x, ids, *, block=128):
    """Reference distance of each returned id to its query; inf where the id
    is not a row of ``x``."""
    q = jnp.asarray(q, jnp.float32)
    ids = np.asarray(ids)
    n = x.shape[0]
    ok = (ids >= 0) & (ids < n)
    out = []
    for s in range(0, len(ids), block):
        safe = jnp.asarray(np.where(ok[s:s + block], ids[s:s + block], 0))
        out.append(np.asarray(_pair_dist(q[s:s + block], x[safe])))
    d = np.concatenate(out) if out else np.zeros(ids.shape, np.float32)
    return np.where(ok, d, np.inf)


def compare(q, x, got_v, got_i, k, *, exclude=None, precision="highest",
            per_row=False):
    """The numbers compared for a set of returned top-k rows.

    - ``bad_ids``: returned ids that are not rows of ``x``, repeat within a
      row, or are the row itself where ``exclude`` is given.
    - ``topk_err``: the returned rows' reference distances, sorted, against
      the reference top-k values, relative to max(|ref|, 1).  A swap between
      rows at equal distance reads 0; a wrong neighbour does not.
    - ``value_err``: the returned distances against the reference distances
      of the returned ids, relative likewise.
    - ``recall``: mean share of the reference top-k ids returned.

    ``precision`` is the reference's own ("highest" for every benchmark run).
    With ``per_row`` also returns, per returned row, whether it holds a bad
    id and its ``value_err``.
    """
    got_v = np.asarray(got_v, np.float64)
    got_i = np.asarray(got_i).astype(np.int64)
    ref_v, ref_i = brute_topk(q, x, k, exclude=exclude, precision=precision)
    n = x.shape[0]
    bad = (got_i < 0) | (got_i >= n)
    if exclude is not None:
        bad |= got_i == np.asarray(exclude)[:, None]
    srt = np.sort(got_i, axis=1)
    dup = np.zeros_like(bad)
    dup[:, 1:] = srt[:, 1:] == srt[:, :-1]
    d_got = distances_of(q, x, got_i).astype(np.float64)
    ref_v = ref_v.astype(np.float64)
    scale = np.maximum(np.abs(ref_v), 1.0)
    with np.errstate(invalid="ignore"):
        topk = np.abs(np.sort(d_got, axis=1) - ref_v) / scale
        value = np.abs(got_v - d_got) / np.maximum(np.abs(d_got), 1.0)
    topk = np.where(np.isfinite(topk), topk, np.inf)
    value = np.where(np.isfinite(value), value, np.inf)
    hits = [len(set(a) & set(b)) / k
            for a, b in zip(got_i.tolist(), ref_i.tolist())]
    out = {
        "bad_ids": int(bad.sum() + dup.sum()),
        "topk_err": float(topk.max()) if topk.size else 0.0,
        "value_err": float(value.max()) if value.size else 0.0,
        "recall": float(np.mean(hits)) if hits else 0.0,
    }
    if not per_row:
        return out
    return out, {"bad_ids": (bad | dup).any(axis=1),
                 "value_err": value.max(axis=1, initial=0.0)}


def control_topk(q, x, k, *, exclude=None):
    """The control: the reference in the program's place, one precision
    below float32 (bfloat16 operands, float32 accumulation).  Its
    distances are what it computed, as a program's would be."""
    return brute_topk(q, x, k, exclude=exclude, precision="bf16")
