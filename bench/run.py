#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name from ``BENCHMARK.json``: the cell names a
configuration (``bench/configs/<config>.json``: sizes, index settings, the
program entry it drives, the limits of its checks) and a traffic mix
(``bench/mixes/<traffic>.json``, parameters read by ``bench/traffic.py``);
the configuration names its system module (``bench/systems/<system>.py``),
the mix its window loop (``bench/loops/<loop>.py``); each metric is read by
``bench/metrics/<metric>.py``.  A new cell, mix, loop or metric is new
files and new entries, not an edit.

A run: the chips are looked up (no TPU, or fewer chips than the cell asks
for, exits 1 with no result line), the device kind is looked up in
``bench/peaks.json``, the data is made on the device from ``--seed``, the
system is built and every shape the window uses is warmed up (all of that is
``setup_s``), the window runs for ``--seconds``, then the program's state is
freed and what the window returned is compared with the float32 reference.
With ``--trace 1`` the window is traced and the per-layer metrics are
printed instead of the end-to-end ones.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared with its limit.
The same numbers are the last lines of standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

import numpy as np  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

TRACE_DIR = os.path.join(ROOT, ".bench_trace")
# JAX records the first for every program it compiles or loads from the
# persistent cache, and the second for each one it loads.
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class NoChip(RuntimeError):
    pass


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str) -> types.ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def find(items, name: str, what: str) -> dict:
    for it in items:
        if it["name"] == name:
            return it
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def require_devices(chips: int):
    """The cell's chips, or ``NoChip``: the benchmark never falls back to
    another platform."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's platform is {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


def configure_cache() -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR`` when
    set, else ``<checkout>/.jax_cache`` (a fixed path: it is part of the
    cache's key).  Every program is kept, however fast it compiled, so that
    only the first run of a cell in a checkout compiles."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileCounter:
    """Counts the programs compiled or loaded from the compile cache, by
    phase: ``setup`` until the window opens, then ``window``."""

    def __init__(self):
        import jax

        self.phase = "setup"
        self.programs = {"setup": 0, "window": 0, "after": 0}
        self.cache_hits = {"setup": 0, "window": 0, "after": 0}
        jax.monitoring.register_event_duration_secs_listener(self._on_time)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_time(self, event, duration, **kw):
        if event == COMPILE_EVENT:
            self.programs[self.phase] += 1

    def _on_event(self, event, **kw):
        if event == CACHE_HIT_EVENT:
            self.cache_hits[self.phase] += 1


def peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks)) if peaks else 0


def load_system(cfg: dict) -> types.ModuleType:
    return load_module(os.path.join(BENCH, "systems", cfg["system"] + ".py"),
                       "bench_system_" + cfg["system"])


def load_loop(mix: dict) -> types.ModuleType:
    return load_module(os.path.join(BENCH, "loops", mix["loop"] + ".py"),
                       "bench_loop_" + mix["loop"])


def slowest_flushes(out: dict, n: int = 3) -> str:
    """The ``n`` longest flushes of a window, as (start s, ms, size)."""
    f = out.get("flush_s") or []
    top = sorted(range(len(f)), key=lambda j: -f[j])[:n]
    return ", ".join(f"({out['flush_start_s'][j]:.3f} s, {f[j] * 1e3:.1f} ms,"
                     f" {out['flush_sizes'][j]})" for j in top)


def run(spec: dict, cell: dict, cfg: dict, mix: dict, *, seed: int,
        seconds: float, trace: bool, devices=None, peaks=None,
        system=None) -> dict:
    """One run of ``cell``; returns the result object (not printed).

    ``devices``, ``peaks`` and ``system`` are looked up when None; tests
    pass them to drive a run off the chip.
    """
    import jax

    from bench import work

    chips = int(cell["chips"])
    if devices is None:
        devices = require_devices(chips)
    kind = devices[0].device_kind
    if peaks is None:
        peaks = work.load_peaks(kind)
    configure_cache()
    if system is None:
        system = load_system(cfg)
    loop = load_loop(mix)
    counter = CompileCounter()
    state = system.setup(cfg, mix, seed, chips, loop.warm_sizes(mix))
    setup_s = time.perf_counter() - T_START
    log(f"set-up {setup_s:.3f} s ({counter.programs['setup']} programs, "
        f"{counter.cache_hits['setup']} of them from the compile cache): "
        + ", ".join(
        f"{k}={v:.3f}" if isinstance(v, float) else f"{k}={v}"
        for k, v in state.setup_log.items()))

    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(TRACE_DIR)
    gc.collect()
    gc.disable()
    counter.phase = "window"
    with jax.profiler.TraceAnnotation("window"):
        out = loop.run(system, state, mix, seconds, seed)
    counter.phase = "after"
    gc.enable()
    if trace:
        jax.profiler.stop_trace()
    log(f"window {out['elapsed_s']:.3f} s, programs compiled or loaded in "
        f"the window: {counter.programs['window']}")
    if out.get("flush_s"):
        f = np.asarray(out["flush_s"]) * 1e3
        log(f"{len(f)} flushes, ms min {f.min():.1f} median "
            f"{np.median(f):.1f} max {f.max():.1f}; slowest (start, ms, "
            f"size): {slowest_flushes(out)}")
    mem = peak_bytes(devices)

    readings, failed = system.check(state, out, cfg["checks"])
    checks = {}
    correct = True
    for name, lim in cfg["checks"].items():
        v = readings[name]
        ok = v >= lim["min"] if "min" in lim else v <= lim["max"]
        correct &= bool(ok)
        checks[name] = {"value": v, ("min" if "min" in lim else "max"):
                        lim.get("min", lim.get("max"))}

    ctx = types.SimpleNamespace(
        cell=cell, cfg=cfg, mix=mix, chips=chips, peaks=peaks,
        setup_s=setup_s, out=out, readings=readings, state=state,
        work=system.window_work(state, out), trace=None)
    result_device = {"platform": devices[0].platform, "kind": kind,
                     "count": chips, "memory_peak_bytes": mem}
    breakdown = None
    if trace:
        from bench import trace as tr

        ctx.trace = tr.load(TRACE_DIR)
        result_device["busy_s"] = tr.mean_busy_s(ctx.trace)
        result_device["window_s"] = ctx.trace.window_s
        breakdown = {"device_ops": tr.device_ops(ctx.trace),
                     "idle_gaps": tr.idle_gaps(ctx.trace)}
    metrics = {}
    for m in spec["per_layer" if trace else "end_to_end"]:
        if not applies(m, cell["name"]):
            continue
        reader = load_module(os.path.join(BENCH, "metrics", m["name"] + ".py"),
                             "bench_metric_" + m["name"].replace(".", "_"))
        v = reader.read(ctx)
        if v is None:
            log(f"metric {m['name']}: nothing to read")
            continue
        metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    res = {"correct": bool(correct), "attempted": system.attempted(out),
           "failed": int(failed), "metrics": metrics,
           "device": result_device}
    if breakdown is not None:
        res["breakdown"] = breakdown
    res["compilations_in_window"] = counter.programs["window"]
    res["checks"] = checks
    return res


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = find(spec["workloads"], args.workload, "workload")
    cfg_entry = find(spec["configs"], cell["config"], "config")
    cfg = load_json(os.path.join(ROOT, cfg_entry["file"]))
    mix = load_json(os.path.join(BENCH, "mixes", cell["traffic"] + ".json"))
    try:
        res = run(spec, cell, cfg, mix, seed=args.seed, seconds=args.seconds,
                  trace=bool(args.trace))
    except NoChip as e:
        log(str(e))
        return 1
    for name, c in res["checks"].items():
        bound = (f">= {c['min']}" if "min" in c else f"<= {c['max']}")
        log(f"check {name} = {c['value']!r} (limit {bound})")
    log(f"correct: {res['correct']}")
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
