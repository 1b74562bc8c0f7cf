#!/usr/bin/env python3
"""One-off measurements that are not cells.

    python bench/probe.py trace-fixture --out DIR [--chips 4]
        records a small trace (two all-pairs solves at n = 4096 inside the
        harness's spans, an idle gap under a ``flush`` span, and on several
        chips the ring with its collectives) for bench/tests;
    python bench/probe.py allpairs-impls [--n 160000]
        times ``knn_allpairs`` with ``impl="fused"`` (full square) and
        ``impl="jnp"`` (symmetric, each pair once) on the same rows: the
        first call with its compilation, then one warm call each.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]

from bench import run as R  # noqa: E402


def trace_fixture(out: str, chips: int) -> None:
    import jax

    from bench import data
    from repro.core import knn_allpairs

    ann = jax.profiler.TraceAnnotation
    x = data.random_vectors(4096, 256, 0)
    solve = lambda: knn_allpairs(x, 100, impl="fused")  # noqa: E731
    jax.block_until_ready(solve())
    ring = None
    if chips > 1:
        from jax.sharding import NamedSharding, PartitionSpec

        from repro.core.distributed import make_ring_allpairs

        mesh = jax.make_mesh((chips,), ("ring",), devices=jax.devices()[:chips],
                             axis_types=(jax.sharding.AxisType.Auto,))
        xs = data.random_vectors(
            4096, 256, 0, sharding=NamedSharding(mesh, PartitionSpec("ring")))
        rfn = make_ring_allpairs(mesh, k=100, impl="fused")
        ring = lambda: rfn(xs, 4096)  # noqa: E731
        jax.block_until_ready(ring())
    jax.profiler.start_trace(out)
    with ann("window"):
        for _ in range(2):
            with ann("solve"):
                r = solve()
            with ann("block"):
                jax.block_until_ready(r)
        with ann("flush"):
            time.sleep(0.05)
        if ring is not None:
            with ann("solve"):
                r = ring()
            with ann("block"):
                jax.block_until_ready(r)
    jax.profiler.stop_trace()
    R.log(f"trace written under {out}")


def allpairs_impls(n: int) -> None:
    import jax

    from bench import data
    from repro.core import knn_allpairs

    x = data.random_vectors(n, 256, 0)
    for impl in ("fused", "jnp"):
        for call in ("first (compile included)", "warm"):
            t = time.perf_counter()
            jax.block_until_ready(knn_allpairs(x, 100, impl=impl))
            R.log(f"knn_allpairs(n={n}, d=256, k=100, impl={impl!r}) {call}: "
                  f"{time.perf_counter() - t:.3f} s")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    t = sub.add_parser("trace-fixture")
    t.add_argument("--out", required=True)
    t.add_argument("--chips", type=int, default=1)
    a = sub.add_parser("allpairs-impls")
    a.add_argument("--n", type=int, default=160_000)
    args = ap.parse_args(argv)
    try:
        R.require_devices(getattr(args, "chips", 1))
    except R.NoChip as e:
        R.log(str(e))
        return 1
    R.configure_cache()
    if args.cmd == "trace-fixture":
        trace_fixture(os.path.abspath(args.out), args.chips)
    else:
        allpairs_impls(args.n)
    return 0


if __name__ == "__main__":
    sys.exit(main())
