"""IVF-PQ serving through the program's engine.

The corpus goes into ``repro.serving.RetrievalIndex.build(...)`` with the
configuration's index settings; queries go through
``repro.serving.QueryEngine`` ``submit`` / ``flush`` only, so batching
stays with the program.  The first search trains the coarse quantizer and
the PQ codebooks: that is set-up the traffic needs.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import data, reference, traffic, work

CHECKS = ("bad_ids", "value_err")


@dataclasses.dataclass
class State:
    cfg: dict
    mix: dict
    x: object  # the benchmark's copy of the corpus, on the device
    queries: np.ndarray
    index: object = None
    engine: object = None
    centroids: np.ndarray = None
    counts: np.ndarray = None
    setup_log: dict = dataclasses.field(default_factory=dict)


def corpus(cfg: dict):
    """The deployment's corpus: the same rows in every run (from the
    configuration's ``corpus_seed``), so that every run builds the same
    index, with the same cell sizes and compiled shapes."""
    s = int(cfg["corpus_seed"])
    return data.clustered_vectors(int(cfg["n"]), int(cfg["d"]), s, s, 1,
                                  n_clusters=int(cfg["clusters"]),
                                  spread=float(cfg["spread"]))


def setup(cfg: dict, mix: dict, seed: int, chips: int,
          warm: list[int]) -> State:
    """The index built and trained, and one search of each flush size in
    ``warm`` (the sizes the window's loop sends)."""
    from repro.serving import EngineConfig, QueryEngine, RetrievalIndex

    if chips != 1:
        raise ValueError("this configuration serves from one chip")
    log = {}
    t = time.perf_counter()
    x = corpus(cfg)
    q = np.asarray(traffic.queries(cfg, mix, seed))
    x_host = np.asarray(x)
    log["data_s"] = time.perf_counter() - t
    t = time.perf_counter()
    ix = cfg["index"]
    index = RetrievalIndex.build(
        np.arange(x_host.shape[0]), x_host, impl=ix["impl"],
        ivf_cells=int(ix["ivf_cells"]), nprobe=int(ix["nprobe"]),
        pq_m=int(ix["pq_m"]), pq_nbits=int(ix["pq_nbits"]),
        overfetch=int(ix["overfetch"]))
    del x_host
    engine = QueryEngine(index, EngineConfig(
        k=int(mix["k"]), min_batch=int(mix["min_batch"]),
        max_batch=int(mix["max_batch"])))
    log["build_s"] = time.perf_counter() - t
    st = State(cfg, mix, x, q, index, engine)
    sizes = list(warm) or [1]
    t = time.perf_counter()
    jax.block_until_ready(engine.search(q[:sizes[0]]).ids)
    log["train_and_first_search_s"] = time.perf_counter() - t
    t = time.perf_counter()
    for b in sizes[1:]:
        jax.block_until_ready(engine.search(q[:b]).ids)
    log["warm_s"] = time.perf_counter() - t
    ivf = index._dev["main_ivf"]
    st.centroids = np.asarray(ivf.centroids)
    st.counts = np.asarray(ivf.counts)
    log["cell_cap"] = int(ivf.cell_cap)
    log["packed_slots"] = int(ivf.packed.shape[0])
    st.setup_log = log
    return st


def check(st: State, out: dict, limits: dict) -> tuple[dict, int]:
    """Readings over every request answered in the window, and the count of
    requests that got no answer or an answer that fails a limit."""
    answers = out["answers"]
    rids = sorted(answers)
    st.engine = st.index = None  # the program's state goes first
    k = int(st.mix["k"])
    qi = np.array([out["query_of"](r) for r in rids], np.int64)
    q = jnp.asarray(st.queries[qi])
    got_v = np.stack([answers[r][0] for r in rids]) if rids else np.zeros(
        (0, k), np.float32)
    got_i = np.stack([answers[r][1] for r in rids]) if rids else np.zeros(
        (0, k), np.int32)
    readings, per_row = reference.compare(q, st.x, got_v, got_i, k,
                                          per_row=True)
    missing = int(out["sent"]) - len(rids)
    bad = per_row["bad_ids"] | (per_row["value_err"] > limits["value_err"]["max"])
    readings = {"bad_ids": readings["bad_ids"],
                "value_err": readings["value_err"],
                "recall": readings["recall"], "missing": missing}
    return readings, missing + int(bad.sum())


def control(cfg: dict, mix: dict, seed: int, chips: int) -> dict:
    """The control's readings: the reference computed in bfloat16 in the
    program's place, over the configuration's ``control_queries`` queries
    of the cell's own pool and the cell's own corpus."""
    x = corpus(cfg)
    pool = traffic.queries(cfg, mix, seed)
    q = pool[jnp.arange(int(cfg["control_queries"])) % pool.shape[0]]
    k = int(mix["k"])
    v, i = reference.control_topk(q, x, k)
    r = reference.compare(q, x, v, i, k)
    return {"bad_ids": r["bad_ids"], "value_err": r["value_err"],
            "recall": r["recall"]}


def window_work(st: State, out: dict) -> dict:
    """Lower-bound work of the window's scans and rescores.

    The probes are the benchmark's own: each query's ``nprobe`` nearest
    centroids by the float32 reference, over the centroids and live cell
    sizes read once after set-up.  Padding rows are left out.
    """
    ix = st.cfg["index"]
    k = int(st.mix["k"])
    d = int(st.cfg["d"])
    nprobe = min(int(ix["nprobe"]), st.centroids.shape[0])
    rids = sorted(out["answers"])
    qi = np.array([out["query_of"](r) for r in rids], np.int64)
    if len(qi) == 0:
        return {}
    _, probes = reference.brute_topk(st.queries[qi], jnp.asarray(st.centroids),
                                     nprobe)
    row = {r: j for j, r in enumerate(rids)}
    scan = work.ZERO
    start = 0
    for m in out["flush_sizes"]:
        batch = rids[start:start + m]
        start += m
        scan = scan + work.pq_scan(probes[[row[r] for r in batch]],
                                   st.counts, int(ix["pq_m"]))
    kp = min(int(st.cfg["n"]), int(ix["overfetch"]) * (1 << (k - 1).bit_length()),
             int(st.setup_log["cell_cap"]))
    return {"pq_scan": scan, "rescore_topk": work.rescore(len(qi), kp, d)}


def attempted(out: dict) -> int:
    return int(out["sent"])
