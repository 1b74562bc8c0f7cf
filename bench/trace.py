"""Reduce a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read: device busy time, kernel time, collective time that no
compute hides, and the ``breakdown`` of the result line.

Only what the profiler writes is read: the device planes
(``/device:TPU:<i>``), the ops of their ``XLA Ops`` line by instruction
name, and the host spans that the
harness opens with ``jax.profiler.TraceAnnotation`` (``window``, ``solve``,
``block``, ``submit``, ``flush``, ``generate``).  Kernels
are found by the names they are given in ``pallas_call(name=...)``.
"""
from __future__ import annotations

import glob
import os
import re
from typing import NamedTuple

HOST_LABELS = ("generate", "submit", "flush", "solve", "block")
WINDOW = "window"
COLLECTIVES = ("all-reduce", "all-gather", "all-to-all", "reduce-scatter",
               "collective-permute", "send", "recv", "ppermute")
OPS_LINE = "XLA Ops"


class Event(NamedTuple):
    name: str  # the HLO instruction's name, e.g. "fused_knn.1"
    start: int  # ns
    end: int  # ns


class Reduced(NamedTuple):
    window: tuple[int, int]  # ns, the harness's "window" span
    devices: dict  # device plane name -> list[Event] inside the window
    host: list  # harness spans: (label, start, end)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def latest_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def op_name(full: str) -> str:
    """The instruction's name from an op event's name, which on the TPU is
    its HLO text: ``%fused_knn.1 = (f32[...]) custom-call(...)``."""
    return full.split(" = ", 1)[0].lstrip("%")


def _is_device_plane(name: str) -> bool:
    return name.startswith("/device:TPU:")


def load(path: str) -> Reduced:
    """Read the trace at ``path`` (a file, or a directory the profiler wrote)
    and keep what lies inside the harness's ``window`` span."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = latest_xplane(path)
    with open(path, "rb") as f:
        pd = ProfileData.from_serialized_xspace(f.read())
    host, windows, devices = [], [], {}
    for plane in pd.planes:
        if _is_device_plane(plane.name):
            evs = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    s = int(ev.start_ns)
                    evs.append(Event(op_name(ev.name), s,
                                     s + int(ev.duration_ns)))
            devices[plane.name] = sorted(evs, key=lambda e: e.start)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW:
                        windows.append((int(ev.start_ns), int(ev.end_ns)))
                    elif ev.name in HOST_LABELS:
                        host.append((ev.name, int(ev.start_ns),
                                     int(ev.end_ns)))
    if not windows:
        raise ValueError(f"{path}: no '{WINDOW}' span from the harness")
    w0, w1 = max(windows, key=lambda w: w[1] - w[0])
    clipped = {}
    for dev, evs in sorted(devices.items()):
        clipped[dev] = [Event(e.name, max(e.start, w0), min(e.end, w1))
                        for e in evs if e.end > w0 and e.start < w1]
    return Reduced((w0, w1), clipped, host)


def _union(intervals) -> list[tuple[int, int]]:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _length(intervals) -> int:
    return sum(e - s for s, e in intervals)


def _minus(a, b) -> list[tuple[int, int]]:
    """Parts of the (merged, sorted) intervals ``a`` that ``b`` leaves."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def busy_s(r: Reduced) -> dict:
    """Seconds in which some operation ran, per device."""
    return {d: _length(_union((e.start, e.end) for e in evs)) * 1e-9
            for d, evs in r.devices.items()}


def mean_busy_s(r: Reduced) -> float:
    b = busy_s(r)
    return sum(b.values()) / len(b) if b else 0.0


def is_collective(ev: Event) -> bool:
    low = ev.name.lower()
    return any(c in low for c in COLLECTIVES)


def matches(ev: Event, kernel: str) -> bool:
    """The op is a call of the kernel named ``kernel`` (``pallas_call``'s
    name, numbered by XLA: ``pq_scan``, ``pq_scan.3``)."""
    return re.sub(r"\.\d+$", "", ev.name) == kernel


def kernel_s(r: Reduced, kernel: str) -> float:
    """Device seconds of ``kernel``'s events, summed over devices."""
    return sum(e.end - e.start for evs in r.devices.values() for e in evs
               if matches(e, kernel)) * 1e-9


def named_kernel_s(r: Reduced, kernels) -> float:
    """Device seconds of events of any of ``kernels``, summed over devices,
    each event counted once."""
    return sum(e.end - e.start for evs in r.devices.values() for e in evs
               if any(matches(e, k) for k in kernels)) * 1e-9


def collective_exposed_s(r: Reduced) -> float:
    """Seconds, averaged over devices, in which a collective ran and no
    other operation did."""
    out = []
    for evs in r.devices.values():
        coll = _union((e.start, e.end) for e in evs if is_collective(e))
        comp = _union((e.start, e.end) for e in evs if not is_collective(e))
        out.append(_length(_minus(coll, comp)))
    return sum(out) / len(out) * 1e-9 if out else 0.0


def device_ops(r: Reduced, top: int = 10) -> list:
    """[name, seconds] of the operations that took most device time,
    averaged over devices."""
    tot = {}
    for evs in r.devices.values():
        for e in evs:
            tot[e.name] = tot.get(e.name, 0) + (e.end - e.start)
    n = max(len(r.devices), 1)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
    return [[name, ns * 1e-9 / n] for name, ns in best]


def idle_gaps(r: Reduced, top: int = 10) -> list:
    """[label, seconds] of the longest stretches of the window in which the
    first device ran nothing, each labelled with the innermost harness span
    open at its middle ("host" where none is)."""
    if not r.devices:
        return []
    evs = r.devices[sorted(r.devices)[0]]
    busy = _union((e.start, e.end) for e in evs)
    gaps = _minus([r.window], busy)
    out = []
    for s, e in gaps:
        mid = (s + e) // 2
        open_ = [(he - hs, lab) for lab, hs, he in r.host if hs <= mid < he]
        out.append([min(open_)[1] if open_ else "host", (e - s) * 1e-9])
    return sorted(out, key=lambda g: -g[1])[:top]
