"""The program's own spans and stage scopes in a profiler trace.

The serving path writes host spans named ``knn.*`` (``repro.accounting.span``:
``knn.flush`` ⊃ ``knn.chunk`` ⊃ ``knn.pad``, ``knn.state``, ``knn.dispatch``,
``knn.wait``, ``knn.fetch``) and names its device stages with
``jax.named_scope`` (``knn.probe``, ``knn.live_mask``, ``knn.probe_union``,
``knn.lut``, ``knn.scan``, ``knn.rescore``, ``knn.merge``).  This module
reads both from the ``.xplane.pb`` the harness wrote, on the one clock that
host spans and device ops share:

- host spans, with their args, and the python tracer's frames (``$file:line
  function``), through ``jax.profiler.ProfileData``;
- each device op's stage: the innermost ``knn.`` component of the op's
  ``tf_op`` path (``jit(f)/knn.scan/jit(g)/...``), a stat of the XSpace
  event metadata, which ``ProfileData`` does not expose.  A small reader
  of the protobuf wire format takes it from the file; it skips the event
  lines, so it touches only the metadata.

The metric files ``engine_idle_share.*``, ``flush_self_ms.*`` and
``live_mask_share.*`` call ``engine_idle_share``, ``flush_self_ms`` and
``live_mask_share``; the first and last also log, on stderr, the idle time
by innermost span and the device time by stage, which is how a gap or a
glue op is attributed.

    python bench/spans.py record --out DIR

records the small traced engine run kept, gzipped, in ``bench/tests/data/``.
"""
from __future__ import annotations

import argparse
import os
import statistics
import sys
from typing import NamedTuple

if __name__ == "__main__":
    _root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for _p in (os.path.join(_root, "src"), _root):
        if _p not in sys.path:
            sys.path.insert(0, _p)

from bench import trace as tr  # noqa: E402

PREFIX = "knn."
NO_STAGE = "(none)"


class Span(NamedTuple):
    name: str  # "knn.flush", ...
    start: int  # ns
    end: int  # ns
    args: dict


class Op(NamedTuple):
    device: str
    name: str  # the HLO instruction's name, as in bench/trace.py
    stage: str  # innermost knn.* scope of its tf_op, or NO_STAGE
    start: int  # ns, clipped to the window
    end: int


class Spans(NamedTuple):
    window: tuple[int, int]  # ns, the harness's "window" span
    host: list  # Span, every knn.* span that overlaps the window
    ops: list  # Op, every device op inside the window
    frames: list  # (name, start, end) of python frames, inside the window

    def spans(self, name: str) -> list:
        return [s for s in self.host if s.name == name]


# -- the protobuf wire format, as far as XSpace event metadata needs it ------

def _varint(b: bytes, i: int) -> tuple[int, int]:
    r = s = 0
    while True:
        c = b[i]
        i += 1
        r |= (c & 0x7F) << s
        s += 7
        if c < 0x80:
            return r, i


def _fields(b: bytes, i: int, end: int):
    """(field number, value) of one message: a varint, or a (start, end)
    slice of ``b`` for a length-delimited field."""
    while i < end:
        key, i = _varint(b, i)
        f, wt = key >> 3, key & 7
        if wt == 0:
            v, i = _varint(b, i)
        elif wt == 2:
            n, i = _varint(b, i)
            v = (i, i + n)
            i += n
        elif wt == 1:
            v, i = None, i + 8
        elif wt == 5:
            v, i = None, i + 4
        else:
            raise ValueError(f"wire type {wt} at byte {i}: not an XSpace")
        yield f, v


def _text(b: bytes, v) -> str:
    return b[v[0]:v[1]].decode("utf-8", "replace")


def _map_value(b: bytes, v):
    """The value (field 2) of a protobuf map entry."""
    for f, x in _fields(b, *v):
        if f == 2:
            return x
    return None


def tf_ops(xspace: bytes) -> dict:
    """{device plane name: {event metadata name: tf_op path}}.

    XSpace: planes = 1; XPlane: name = 2, event_metadata = 4 (map of
    XEventMetadata: name = 2, stats = 5), stat_metadata = 5 (map of
    XStatMetadata: id = 1, name = 2); XStat: metadata_id = 1, str_value = 5,
    ref_value = 7 (a string kept as a stat metadata's name).
    """
    b = xspace
    out = {}
    for f, plane in _fields(b, 0, len(b)):
        if f != 1:
            continue
        name, emeta, smeta = "", [], {}
        for pf, pv in _fields(b, *plane):
            if pf == 2:
                name = _text(b, pv)
            elif pf == 4:
                emeta.append(pv)
            elif pf == 5:
                sid, sname = None, ""
                for sf, sv in _fields(b, *_map_value(b, pv)):
                    if sf == 1:
                        sid = sv
                    elif sf == 2:
                        sname = _text(b, sv)
                smeta[sid] = sname
        if not tr._is_device_plane(name):
            continue
        tf_id = next((i for i, n in smeta.items() if n == "tf_op"), None)
        ops = {}
        for ev in emeta:
            ename, path = None, None
            for ef, evv in _fields(b, *_map_value(b, ev)):
                if ef == 2:
                    ename = _text(b, evv)
                elif ef == 5:
                    sid, val = None, None
                    for xf, xv in _fields(b, *evv):
                        if xf == 1:
                            sid = xv
                        elif xf == 5:
                            val = _text(b, xv)
                        elif xf == 7:
                            val = smeta.get(xv)
                    if sid == tf_id and val is not None:
                        path = val
            if ename is not None and path is not None:
                ops.setdefault(ename, path)
        out[name] = ops
    return out


def stage(path: str | None) -> str:
    """The innermost ``knn.`` component of a ``tf_op`` path."""
    if not path:
        return NO_STAGE
    parts = [p for p in path.split("/") if p.startswith(PREFIX)]
    return parts[-1] if parts else NO_STAGE


# -- loading ------------------------------------------------------------------

def load(path: str) -> Spans:
    """The ``knn.*`` spans, the staged device ops and the python frames of
    the trace at ``path`` (a file, or a directory the profiler wrote),
    inside the harness's ``window`` span."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = tr.latest_xplane(path)
    with open(path, "rb") as f:
        raw = f.read()
    paths = tf_ops(raw)
    pd = ProfileData.from_serialized_xspace(raw)
    windows, host, ops, frames = [], [], [], []
    for plane in pd.planes:
        if tr._is_device_plane(plane.name):
            tf = paths.get(plane.name, {})
            for line in plane.lines:
                if line.name != tr.OPS_LINE:
                    continue
                for ev in line.events:
                    s = int(ev.start_ns)
                    ops.append(Op(plane.name, tr.op_name(ev.name),
                                  stage(tf.get(ev.name)), s,
                                  s + int(ev.duration_ns)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    name = ev.name
                    if name == tr.WINDOW:
                        windows.append((int(ev.start_ns), int(ev.end_ns)))
                    elif name.startswith(PREFIX):
                        host.append(Span(name, int(ev.start_ns),
                                         int(ev.end_ns), dict(ev.stats)))
                    elif name.startswith("$"):  # the python tracer's frames
                        frames.append((name, int(ev.start_ns),
                                       int(ev.end_ns)))
    if not windows:
        raise ValueError(f"{path}: no '{tr.WINDOW}' span from the harness")
    w0, w1 = max(windows, key=lambda w: w[1] - w[0])
    inside = lambda s, e: e > w0 and s < w1  # noqa: E731
    return Spans(
        (w0, w1),
        sorted((h for h in host if inside(h.start, h.end)),
               key=lambda h: (h.start, -h.end)),
        sorted((Op(o.device, o.name, o.stage, max(o.start, w0),
                   min(o.end, w1)) for o in ops if inside(o.start, o.end)),
               key=lambda o: (o.device, o.start)),
        [f for f in frames if inside(f[1], f[2])])


def of(ctx):
    """The spans of a harness run's trace, read once per run (kept on
    ``ctx``); None in an untraced run.  ``ctx.xplane`` names the file where
    a test gives one, else it is the harness's latest trace."""
    if ctx.trace is None:
        return None
    got = getattr(ctx, "knn_spans", None)
    if got is None:
        path = getattr(ctx, "xplane", None)
        if path is None:
            from bench import run

            path = tr.latest_xplane(run.TRACE_DIR)
        got = ctx.knn_spans = load(path)
    return got


# -- interval arithmetic --------------------------------------------------------

def innermost(spans) -> list:
    """(start, end, span) pieces of the time under any of ``spans``, each
    labelled with the innermost span open over it.  The spans nest (one
    thread's ``with`` blocks); a span that starts later is inside."""
    bounds = sorted({t for s in spans for t in (s.start, s.end)})
    out = []
    order = sorted(spans, key=lambda s: (s.start, -s.end))
    stack, j = [], 0
    for a, b in zip(bounds, bounds[1:]):
        while j < len(order) and order[j].start <= a:
            stack.append(order[j])
            j += 1
        while stack and stack[-1].end <= a:
            stack.pop()
        open_ = [s for s in stack if s.end > a]
        if open_:
            out.append((a, b, open_[-1]))
    return out


def intersect(a, b) -> list:
    """Overlaps of two sorted lists of disjoint (start, end, ...) pieces,
    keeping the rest of each piece of ``b``."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s = max(a[i][0], b[j][0])
        e = min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e) + tuple(b[j][2:]))
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return out


def idle(sp: Spans, device: str) -> list:
    """(start, end) stretches of the window in which ``device`` ran
    nothing."""
    busy = tr._union((o.start, o.end) for o in sp.ops if o.device == device)
    return tr._minus([sp.window], busy)


def log(msg: str) -> None:
    print(f"[spans] {msg}", file=sys.stderr, flush=True)


def _frames_at(sp: Spans, t: int, top: int = 6) -> str:
    """The python frames open at ``t``, outermost first (the innermost
    ``top``)."""
    open_ = sorted((f for f in sp.frames if f[1] <= t < f[2]),
                   key=lambda f: (f[1], -f[2]))
    return " > ".join(f[0] for f in open_[-top:]) or "no python frames"


# -- the metrics ------------------------------------------------------------------

def engine_idle_share(sp: Spans):
    """100 x the time in the window in which the device runs nothing and
    the innermost open host span is a ``knn.*`` span, over the window,
    averaged over devices as ``idle_share`` is.  Logs the idle seconds by
    innermost span and the longest such gap with its python frames."""
    if sp is None or not sp.host:
        return None
    devices = sorted({o.device for o in sp.ops})
    if not devices:
        return None
    pieces = innermost(sp.host)
    total, by_span, longest = 0, {}, None
    for d in devices:
        for s, e, span in intersect(idle(sp, d), pieces):
            total += e - s
            by_span[span.name] = by_span.get(span.name, 0) + e - s
            if longest is None or e - s > longest[1] - longest[0]:
                longest = (s, e, span)
    n = len(devices)
    log("engine idle s by innermost span (mean over devices): " + ", ".join(
        f"{k} {v * 1e-9 / n:.6f}"
        for k, v in sorted(by_span.items(), key=lambda kv: -kv[1])))
    if longest is not None:
        s, e, span = longest
        flush = next((f for f in sp.spans("knn.flush")
                      if f.start <= s < f.end), None)
        log(f"longest engine idle piece {(e - s) * 1e-9:.6f} s under "
            f"{span.name} {span.args} (flush {flush.args if flush else '-'})"
            f"; python frames: {_frames_at(sp, (s + e) // 2)}")
    w = sp.window[1] - sp.window[0]
    return 100.0 * total / n / w


def flush_self_ms(sp: Spans):
    """The median over the window's flushes of the ``knn.flush`` duration
    minus the time its ``knn.wait`` children cover: the engine's host cost
    per flush."""
    if sp is None:
        return None
    w0, w1 = sp.window
    flushes = [f for f in sp.spans("knn.flush") if w0 <= f.start < w1]
    if not flushes:
        return None
    waits = sp.spans("knn.wait")
    selfs = []
    for f in flushes:
        inner = tr._union((max(x.start, f.start), min(x.end, f.end))
                          for x in waits if x.end > f.start and x.start < f.end)
        selfs.append((f.end - f.start - tr._length(inner)) * 1e-6)
    return statistics.median(selfs)


def stage_s(sp: Spans) -> dict:
    """Device seconds by stage, summed over devices."""
    out = {}
    for o in sp.ops:
        out[o.stage] = out.get(o.stage, 0) + (o.end - o.start)
    return {k: v * 1e-9 for k, v in out.items()}


def live_mask_share(sp: Spans):
    """100 x the device time of ops whose stage is ``knn.live_mask``, over
    device busy time (summed over devices).  Logs device seconds by stage,
    the largest ops of each, and the share of busy time under a scope."""
    if sp is None or not any(o.stage != NO_STAGE for o in sp.ops):
        return None
    busy = sum(tr._length(tr._union((o.start, o.end) for o in sp.ops
                                    if o.device == d))
               for d in {o.device for o in sp.ops}) * 1e-9
    if busy <= 0:
        return None
    by = stage_s(sp)
    log("device s by stage: " + ", ".join(
        f"{k} {v:.6f}" for k, v in sorted(by.items(), key=lambda kv: -kv[1])))
    for st in sorted(by, key=lambda k: -by[k]):
        ops = {}
        for o in sp.ops:
            if o.stage == st:
                ops[o.name] = ops.get(o.name, 0) + (o.end - o.start)
        top = sorted(ops.items(), key=lambda kv: -kv[1])[:4]
        log(f"  {st}: " + ", ".join(f"{n} {t * 1e-9:.6f}" for n, t in top))
    scoped = sum(v for k, v in by.items() if k != NO_STAGE)
    log(f"device time under a knn.* scope: {100.0 * scoped / busy:.3f} % "
        "of busy time")
    return 100.0 * by.get("knn.live_mask", 0.0) / busy


# -- the recorded fixture -----------------------------------------------------------

def record(out: str, n: int = 1 << 16, cells: int = 256) -> None:
    """A small traced engine run: an IVF-PQ index of ``n`` rows, warm, then
    flushes of 64, 64, 40 and 100 queries (max_batch 64: the last is two
    chunks) inside the harness's ``window``, ``submit`` and ``flush``
    spans.  The python tracer is off and the host tracer keeps level-1
    events only, which keeps the file small."""
    import jax
    import numpy as np

    from bench import data
    from repro.serving import EngineConfig, QueryEngine, RetrievalIndex

    ann = jax.profiler.TraceAnnotation
    d = 128
    x = np.asarray(data.clustered_vectors(n, d, 0, 0, 1, n_clusters=64,
                                          spread=0.15))
    q = np.asarray(data.clustered_vectors(512, d, 0, 1, 2, n_clusters=64,
                                          spread=0.15))
    index = RetrievalIndex.build(np.arange(n), x, impl="fused",
                                 ivf_cells=cells, nprobe=16, pq_m=16,
                                 pq_nbits=8, overfetch=16)
    engine = QueryEngine(index, EngineConfig(k=10, min_batch=8,
                                             max_batch=64))
    for m in (64, 40, 100):
        jax.block_until_ready(engine.search(q[:m]).ids)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(out, profiler_options=options)
    sent = 0
    with ann("window"):
        for m in (64, 64, 40, 100):
            with ann("submit"):
                for j in range(m):
                    engine.submit(sent + j, q[(sent + j) % len(q)])
            with ann("flush"):
                engine.flush(10)
            sent += m
    jax.profiler.stop_trace()
    log(f"trace written under {out}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    r = sub.add_parser("record", help="record the engine trace fixture")
    r.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    if args.cmd == "record":
        record(os.path.abspath(args.out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
