#!/usr/bin/env python3
"""Bring-up smoke test on a TPU: the main retrieval paths once each, at the
sizes their users run, through the entry points a user calls.

    python chip_smoke.py             # one chip: phases (a)-(d)
    python chip_smoke.py --chips 4   # four chips: the multi-chip paths only

One chip:
  (a) the paper's all-pairs problem at Table 1 scale (n = 160,000, d = 256,
      k = 100) with the fused kernel, checked on sampled rows against a
      float32 brute force;
  (b) exact flat serving of 2^20 x 128 rows through ``RetrievalIndex`` and
      ``QueryEngine`` (fused and jnp scorers), with upserts, deletes and a
      ``compact()``, checked against a brute force over the live rows;
  (c) IVF-PQ serving of 2^20 x 128 rows (2048 cells, nprobe 64, pq_m =
      16, overfetch 64; fused scan and rescore) with its recall@10 against
      brute force held to the repository's 0.9 floor, and how each query's
      true neighbours spread over cells against nprobe;
  (d) the serving CLI, ``repro.launch.serve --impl fused --pq-m 16``, in
      this process.

Four chips (each against the same computation on one chip):
  ring all-pairs at phase (a)'s size; mesh-sharded exact flat serving (the
  CLI's ``--mesh``) at phase (b)'s; the sharded IVF-PQ scan at phase (c)'s
  n, 4096 cells, nprobe 64, overfetch 32.

Exits non-zero, with no result line, when JAX finds no TPU or any phase
fails.  Wall times, compile times and peak bytes printed on the way are
bring-up figures, not benchmark numbers.  The last line of standard output
is ``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

K_SERVE = 10
RECALL_FLOOR = 0.9  # tests/test_pq.py RECALL_FLOOR
# IVF-PQ serving at 2^20 rows.  The corpus is 64 tight Gaussian clusters,
# so a query's cluster holds 16k rows in ~32 of phase (c)'s 2048 cells, and
# its ten true neighbours lie in ~8 cells spread over all of them: the
# probe list covers the cluster (nprobe 64), and the ADC shortlist is 1024
# wide (overfetch 64) so that PQ error does not push them out.  The
# four-chip twin checks equality with one chip, not the recall floor, at
# 4096 cells and a 512-wide shortlist (recall@10 ~0.90), which builds and
# compiles in half the time.  PERF.md records the measurements behind both.
IVF_N, PQ_M = 1 << 20, 16
IVF_CELLS, NPROBE, OVERFETCH = 2048, 64, 64
SHARDED_CELLS, SHARDED_NPROBE, SHARDED_OVERFETCH = 4096, 64, 32


def log(msg: str) -> None:
    print(f"[chip-smoke] {msg}", flush=True)


class Phase:
    """Times a phase and reports the device's peak bytes after it."""

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        log(f"{self.name}: start")
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, *_):
        import jax

        dt = time.perf_counter() - self.t0
        stats = jax.devices()[0].memory_stats() or {}
        peak = stats.get("peak_bytes_in_use", "n/a")
        state = "FAILED" if exc_type else "ok"
        log(f"{self.name}: {state} in {dt:.3f}s wall (bring-up figure), "
            f"device 0 peak_bytes_in_use={peak}")
        return False


def timed(label: str, fn, *args, **kw):
    """First call of ``fn`` (compile + run), blocked on its result."""
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args, **kw))
    log(f"  {label}: {time.perf_counter() - t0:.3f}s incl. compile")
    return out


# ---------------------------------------------------------------------------
# References and checks
# ---------------------------------------------------------------------------


def brute_topk(q, x, k, *, live=None, exclude=None):
    """float32 brute-force k smallest squared-L2 distances of q against x."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        q = jnp.asarray(q, jnp.float32)
        x = jnp.asarray(x, jnp.float32)
        d = (jnp.sum(q * q, 1)[:, None] - 2.0 * q @ x.T
             + jnp.sum(x * x, 1)[None, :])
    if live is not None:
        d = jnp.where(jnp.asarray(live)[None, :], d, jnp.inf)
    if exclude is not None:  # [m] column to mask per row (self-exclusion)
        d = d.at[jnp.arange(d.shape[0]), jnp.asarray(exclude)].set(jnp.inf)
    neg, idx = jax.lax.top_k(-d, k)
    return d, -neg, idx


def check_topk(name, dist, ref_vals, got_rows, *, rtol=1e-4):
    """The returned rows are a true top-k, ties aside.

    ``dist`` [m, N] is the float32 reference distance matrix, ``got_rows``
    [m, k] the rows the system returned.  Their reference distances, sorted,
    must equal the reference top-k values within ``rtol``; a swap between
    rows at equal distance passes, a wrong neighbour does not.
    """
    import numpy as np

    got_rows = np.asarray(got_rows)
    assert (got_rows >= 0).all(), f"{name}: missing results (-1 ids)"
    for r in got_rows:
        assert len(set(r.tolist())) == len(r), f"{name}: duplicate ids"
    got = np.sort(np.take_along_axis(np.asarray(dist), got_rows, axis=1), 1)
    ref = np.asarray(ref_vals)
    err = np.abs(got - ref)
    tol = rtol * np.maximum(np.abs(ref), 1.0)
    log(f"  {name}: max |d_got - d_ref| = {err.max():.6g}, "
        f"worst err/tol = {(err / tol).max():.3g}")
    assert (err <= tol).all(), f"{name}: not the reference top-k"


def overlap(a, b) -> float:
    import numpy as np

    a, b = np.asarray(a), np.asarray(b)
    return float(np.mean([len(set(x) & set(y)) / len(x)
                          for x, y in zip(a.tolist(), b.tolist())]))


def assert_same_result(name, v1, i1, v2, i2, q, x):
    """Bit-identical values; ids equal except between tied rows.

    Where the ids differ, both rows must lie at the same float32 distance
    from the query (``q`` [m, d] queries, ``x`` [n, d] rows): the tie may
    sit just past the k-th slot, so it is checked against the rows
    themselves, not within the returned list.
    """
    import numpy as np

    v1, v2, i1, i2 = map(np.asarray, (v1, v2, i1, i2))
    assert v1.shape == v2.shape, (name, v1.shape, v2.shape)
    assert np.array_equal(v1, v2), f"{name}: values differ bitwise"
    diff = i1 != i2
    if diff.any():
        q, x = np.asarray(q, np.float64), np.asarray(x, np.float64)
        for r, c in zip(*np.nonzero(diff)):
            d1 = np.sum((q[r] - x[i1[r, c]]) ** 2)
            d2 = np.sum((q[r] - x[i2[r, c]]) ** 2)
            assert abs(d1 - d2) <= 1e-5 * max(d1, 1.0), (
                f"{name}: ids differ at ({r}, {c}) between rows at distances "
                f"{d1} and {d2}")
    log(f"  {name}: bit-identical values, ids equal "
        f"({int(diff.sum())} swap(s) between tied values)")


def assert_on_devices(name, arr, n):
    devs = {s.device for s in arr.addressable_shards}
    assert len(arr.sharding.device_set) == n and len(devs) == n, (
        f"{name}: placed on {sorted(str(d) for d in devs)}, wanted {n} "
        "distinct devices")
    log(f"  {name}: shards on {n} distinct devices")


# ---------------------------------------------------------------------------
# Data (generated from a seed; making it counts as set-up)
# ---------------------------------------------------------------------------


def gaussian(n, d, seed):
    import jax

    return jax.random.normal(jax.random.PRNGKey(seed), (n, d))


def corpus_and_queries(n, nq, d, seed):
    """Clustered corpus rows plus held-out queries from the same mixture."""
    from repro.data.synthetic import clustered_vectors

    rows = clustered_vectors(n + nq, d, seed=seed)
    return rows[:n], rows[n:]


# ---------------------------------------------------------------------------
# One-chip phases
# ---------------------------------------------------------------------------


def phase_allpairs(n=160_000, d=256, k=100, n_check=256, seed=0):
    import jax.numpy as jnp
    import numpy as np

    from repro.core import knn_allpairs

    x = gaussian(n, d, seed)
    res = timed(f"knn_allpairs(n={n}, d={d}, k={k}, impl='fused')",
                knn_allpairs, x, k, impl="fused")
    rows = np.random.default_rng(seed).choice(n, n_check, replace=False)
    dist, ref_v, _ = brute_topk(x[rows], x, k, exclude=jnp.asarray(rows))
    check_topk("all-pairs vs brute force", dist, ref_v,
               np.asarray(res.indices)[rows])
    return x, res


def _churn(index, rng, d, n):
    """A few upserts (new and existing ids) and deletes."""
    import numpy as np

    new = np.arange(n, n + 512)
    upd = rng.choice(n, 256, replace=False)
    up_ids = np.concatenate([new, upd])
    up_vecs = rng.standard_normal((len(up_ids), d)).astype(np.float32)
    gone = rng.choice(np.setdiff1d(np.arange(n), upd), 2048, replace=False)
    index.upsert(up_ids, up_vecs)
    index.delete(gone)
    return up_ids, up_vecs, gone


def phase_flat(n=1 << 20, d=128, nq=256, seed=1):
    import numpy as np

    from repro.serving import EngineConfig, QueryEngine, RetrievalIndex

    x, q = corpus_and_queries(n, nq, d, seed)
    truth = {}
    engines = {}
    for impl in ("fused", "jnp"):
        index = RetrievalIndex.build(np.arange(n), x, impl=impl)
        rng = np.random.default_rng(seed)
        up_ids, up_vecs, gone = _churn(index, rng, d, n)
        engines[impl] = QueryEngine(index, EngineConfig(k=K_SERVE,
                                                        max_batch=nq))
    # The live rows after the churn, by id.
    vecs = np.concatenate([x, up_vecs[:512]])
    vecs[up_ids[512:]] = up_vecs[512:]
    live = np.ones(len(vecs), bool)
    live[gone] = False
    dist, ref_v, _ = brute_topk(q, vecs, K_SERVE, live=live)
    for when in ("after churn", "after compact"):
        for impl, eng in engines.items():
            res = timed(f"QueryEngine.search impl={impl!r} {when}",
                        eng.search, q)
            truth[impl, when] = res
            check_topk(f"flat {impl} {when} vs brute force", dist, ref_v,
                       res.ids)
        if when == "after churn":
            for eng in engines.values():
                t0 = time.perf_counter()
                eng.index.compact()
                log(f"  compact(): {time.perf_counter() - t0:.3f}s")
    return truth


def cell_spread(q, ivf, true_rows):
    """Where the true top-k of each query lie: distinct cells, and the share
    of them inside the query's p nearest cells, against p."""
    import numpy as np

    from repro.core.ivf import probe_cells

    cells = np.asarray(ivf.slot_of_row)[np.asarray(true_rows)] // ivf.cell_cap
    held = np.array([len(set(r)) for r in cells.tolist()])
    ps = [p for p in (16, 32, 64, 128, 256) if p <= ivf.ncells]
    near = np.asarray(probe_cells(q, ivf.centroids, ps[-1], impl="fused"))
    cover = [np.mean([np.isin(c, o[:p]).mean() for c, o in zip(cells, near)])
             for p in ps]
    log(f"  true top-{true_rows.shape[1]} per query lie in {held.mean():.2f} "
        f"distinct cells (max {held.max()}); share in the query's p nearest "
        "cells: " + ", ".join(f"p={p}: {c:.4f}" for p, c in zip(ps, cover)))


def phase_ivfpq(n=IVF_N, d=128, nq=256, seed=2):
    import numpy as np

    from repro.serving import EngineConfig, QueryEngine, RetrievalIndex

    x, q = corpus_and_queries(n, nq, d, seed)
    t0 = time.perf_counter()
    index = RetrievalIndex.build(np.arange(n), x, impl="fused",
                                 ivf_cells=IVF_CELLS, nprobe=NPROBE,
                                 pq_m=PQ_M, overfetch=OVERFETCH)
    eng = QueryEngine(index, EngineConfig(k=K_SERVE, max_batch=nq))
    res = timed("QueryEngine.search (trains IVF + PQ on first call)",
                eng.search, q)
    ivf = index._dev["main_ivf"]
    log(f"  build + first search: {time.perf_counter() - t0:.3f}s; "
        f"{ivf.ncells} cells, cell_cap {ivf.cell_cap}, "
        f"{ivf.packed.shape[0]} packed slots")
    res = timed("QueryEngine.search (warm)", eng.search, q)
    _, _, ref_i = brute_topk(q, x, K_SERVE)
    cell_spread(q, ivf, ref_i)
    recall = overlap(res.ids, ref_i)
    log(f"  IVF-PQ recall@{K_SERVE} = {recall:.4f} "
        f"(nprobe={NPROBE}, pq_m={PQ_M}, overfetch={OVERFETCH}; "
        f"floor {RECALL_FLOOR})")
    assert recall >= RECALL_FLOOR, f"recall@10 {recall} < {RECALL_FLOOR}"
    return recall


def phase_cli():
    from repro.launch import serve

    serve.main(["--corpus", "65536", "--queries", "64", "--batches", "6",
                "--k", str(K_SERVE), "--impl", "fused",
                "--ivf-cells", "256", "--nprobe", "16", "--pq-m", "16"])


# ---------------------------------------------------------------------------
# Four-chip phases
# ---------------------------------------------------------------------------


def phase_ring(n=160_000, d=256, k=100, seed=0):
    import jax

    from repro.core import distributed as D
    from repro.core import knn_allpairs

    P = len(jax.devices())
    x = gaussian(n, d, seed)
    one = timed("one chip: knn_allpairs impl='fused'", knn_allpairs, x, k,
                impl="fused")
    mesh = jax.make_mesh((P,), ("ring",))
    ring = D.make_ring_allpairs(mesh, k=k, impl="fused")
    res = timed(f"ring all-pairs over {P} chips", ring, x, n)
    assert_on_devices("ring result", res.indices, P)
    assert_same_result("ring vs one chip", res.distances, res.indices,
                       one.distances, one.indices, x, x)


def phase_mesh_flat(n=1 << 20, d=128, nq=256, seed=1):
    import numpy as np

    from repro.launch.mesh import make_host_mesh
    from repro.serving import EngineConfig, QueryEngine, RetrievalIndex

    x, q = corpus_and_queries(n, nq, d, seed)
    mesh = make_host_mesh()
    log(f"  mesh {dict(mesh.shape)}")
    out = {}
    for name, m in (("one chip", None), ("mesh", mesh)):
        index = RetrievalIndex.build(np.arange(n), x, impl="fused", mesh=m)
        eng = QueryEngine(index, EngineConfig(k=K_SERVE, max_batch=nq))
        out[name] = timed(f"{name}: QueryEngine.search", eng.search, q)
        if m is not None:
            db = index._dev["main_padded"][0]
            assert_on_devices("mesh main segment", db, mesh.size)
    a, b = out["mesh"], out["one chip"]
    assert_same_result("mesh flat vs one chip", a.distances, a.ids,
                       b.distances, b.ids, q, x)


def phase_sharded_ivfpq(n=IVF_N, d=128, nq=256, seed=2):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.core import build_ivf, build_ivfpq
    from repro.core import distributed as D
    from repro.core import topk as T
    from repro.core.ivf import packed_live
    from repro.core.pq import PQCodes

    P = len(jax.devices())
    x, q = corpus_and_queries(n, nq, d, seed)
    q = jnp.asarray(q)
    t0 = time.perf_counter()
    ivf = build_ivf(x, SHARDED_CELLS, impl="fused")
    cb, codes = build_ivfpq(x, ivf, PQ_M, impl="fused")
    live = packed_live(ivf)
    log(f"  IVF-PQ build on one chip: {time.perf_counter() - t0:.3f}s "
        f"(cell_cap {ivf.cell_cap})")
    kw = dict(k=K_SERVE, nprobe=SHARDED_NPROBE, cell_cap=ivf.cell_cap,
              impl="fused", overfetch=SHARDED_OVERFETCH)

    # One chip: every shard's pipeline in turn, then one merge.
    S_loc = ivf.packed.shape[0] // P
    parts = []
    for p in range(P):
        sl = slice(p * S_loc, (p + 1) * S_loc)
        parts.append(D.ivfpq_shard_candidates(
            q, ivf.centroids, cb, PQCodes(codes.codes[sl], codes.hy[sl]),
            ivf.packed[sl], ivf.row_of_slot[sl], live[sl], shard=p,
            n_shards=P, **kw))
    one_v, one_i = T.merge_many_sorted(jnp.stack([v for v, _ in parts]),
                                       jnp.stack([i for _, i in parts]),
                                       K_SERVE)
    jax.block_until_ready((one_v, one_i))

    mesh = jax.make_mesh((1, P), ("data", "model"))
    rows = NamedSharding(mesh, PartitionSpec("model"))
    packed = jax.device_put(ivf.packed, rows)
    codes_s = PQCodes(jax.device_put(codes.codes, rows),
                      jax.device_put(codes.hy, rows))
    ros = jax.device_put(ivf.row_of_slot, rows)
    live_s = jax.device_put(live, rows)
    assert_on_devices("packed rows", packed, P)
    assert_on_devices("PQ codes", codes_s.codes, P)
    fn = D.make_ivfpq_query_sharded(mesh, query_axis="data", db_axis="model",
                                    **kw)
    res = timed(f"sharded IVF-PQ over {P} chips", fn, q, ivf.centroids, cb,
                codes_s, packed, ros, live_s)
    assert_same_result("sharded IVF-PQ vs one chip", res.distances,
                       res.indices, one_v, one_i, q, x)
    _, _, ref_i = brute_topk(q, x, K_SERVE)
    log(f"  sharded IVF-PQ recall@{K_SERVE} = "
        f"{overlap(res.indices, ref_i):.4f} (reported, not checked)")


# ---------------------------------------------------------------------------


def require_tpu_kernels():
    """No kernel runs in the Pallas interpreter and none is swapped out."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops
    from repro.kernels._backend import resolve_interpret

    assert resolve_interpret(None) is False, "kernels would be interpreted"
    x = jnp.zeros((256, 128), jnp.float32)
    text = jax.jit(lambda a: ops.fused_knn(a, a, K_SERVE).indices).lower(
        x).as_text()
    assert "tpu_custom_call" in text, "the fused kernel is not a Mosaic call"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs the multi-chip paths (against one chip) "
                         "and nothing else")
    args = ap.parse_args(argv)

    from repro.compile_cache import configure

    configure()
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {devs[0].platform!r})",
              file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees {len(devs)} "
              "device(s)", file=sys.stderr)
        return 1
    log(f"{len(devs)} x {devs[0].device_kind}, jax {jax.__version__}")
    require_tpu_kernels()

    if args.chips == 1:
        phases = [("(a) all-pairs, paper Table 1 scale", phase_allpairs),
                  ("(b) exact flat serving with churn", phase_flat),
                  ("(c) IVF-PQ serving", phase_ivfpq),
                  ("(d) serving CLI", phase_cli)]
    else:
        phases = [("ring all-pairs vs one chip", phase_ring),
                  ("--mesh exact flat serving vs one chip", phase_mesh_flat),
                  ("sharded IVF-PQ vs one chip", phase_sharded_ivfpq)]
    for name, fn in phases:
        with Phase(name):
            fn()
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
