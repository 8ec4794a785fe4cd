"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics
read: device busy time, time per XLA module and per device op name, and
the idle gaps labelled by the host span open at the time.

Planes whose name starts with ``/device:`` are devices; on each, the line
``XLA Ops`` holds one event per executed op and ``XLA Modules`` one per
executed program.  Host spans are the ``bench.*`` annotations the harness
writes (``jax.profiler.TraceAnnotation``) on the host planes.  Every
number is clipped to the traced window, the span ``bench.window``.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Reduced:
    window_s: float                 # length of the traced window
    busy_s: float                   # union of op intervals, mean over devices
    devices: int
    ops: dict                       # HLO op name -> [count, device seconds]
    modules: dict                   # module name -> [count, device seconds]
    idle_gaps: list                 # [(host span, seconds)], longest first

    def ops_matching(self, predicate) -> tuple:
        """(count, seconds) summed over the op names ``predicate`` accepts."""
        n = s = 0
        for name, (c, t) in self.ops.items():
            if predicate(name):
                n, s = n + c, s + t
        return n, s

    def module(self, fragment: str):
        """(calls, seconds) of the modules whose name holds ``fragment``;
        None when no module does."""
        hits = [v for k, v in self.modules.items() if fragment in k]
        if not hits:
            return None
        return sum(c for c, _ in hits), sum(t for _, t in hits)


def union_length(intervals) -> float:
    """Total length covered by (start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def _gaps(intervals, lo, hi):
    """Uncovered (start, end) stretches of [lo, hi]."""
    out, t = [], lo
    for s, e in sorted(intervals):
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"{len(paths)} xplane files under {log_dir}")
    return paths[0]


def _events(line):
    for e in line.events:
        yield e.name, e.start_ns, e.start_ns + e.duration_ns


def op_name(event_name: str) -> str:
    """The HLO instruction name of a device op event.  A TPU trace names
    each op by its whole HLO line (``%branch_1_fun.59 = bf16[...]
    custom-call(...)``); the name is the part before `` = ``."""
    if event_name.startswith("%"):
        return event_name[1:].split(" ", 1)[0]
    return event_name


def reduce(path: str, top_gaps: int = 10,
           require_device: bool = True) -> Reduced:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    host_spans, device_lines = [], []
    for plane in data.planes:
        lines = {ln.name: ln for ln in plane.lines}
        if plane.name.startswith("/device:"):
            if OPS_LINE in lines:
                device_lines.append((lines[OPS_LINE],
                                     lines.get(MODULES_LINE)))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host_spans += [ev for ev in _events(ln)
                               if ev[0].startswith("bench.")]
    windows = [(s, e) for n, s, e in host_spans if n == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"{len(windows)} '{WINDOW}' spans in {path}")
    lo, hi = windows[0]
    if not device_lines and require_device:
        raise ValueError(f"no device plane with an '{OPS_LINE}' line in {path}")

    ops = collections.defaultdict(lambda: [0, 0.0])
    modules = collections.defaultdict(lambda: [0, 0.0])
    busy, gaps = [], []
    for ops_line, mod_line in device_lines:
        spans = []
        for name, s, e in _events(ops_line):
            s, e = max(s, lo), min(e, hi)
            if e <= s:
                continue
            spans.append((s, e))
            ops[op_name(name)][0] += 1
            ops[op_name(name)][1] += (e - s) * 1e-9
        busy.append(union_length(spans) * 1e-9)
        for name, s, e in (_events(mod_line) if mod_line else ()):
            s, e = max(s, lo), min(e, hi)
            if e > s:
                modules[name][0] += 1
                modules[name][1] += (e - s) * 1e-9
        gaps += _gaps(spans, lo, hi)

    inner = [(n, s, e) for n, s, e in host_spans if n != WINDOW]

    def label(gs, ge):
        best, cover = "host: outside any bench span", 0
        for n, s, e in inner:
            c = min(e, ge) - max(s, gs)
            if c > cover:
                best, cover = "host: " + n, c
        return best

    gaps.sort(key=lambda g: g[0] - g[1])
    labelled = [(label(s, e), (e - s) * 1e-9) for s, e in gaps[:top_gaps]]
    return Reduced(window_s=(hi - lo) * 1e-9,
                   busy_s=sum(busy) / max(len(busy), 1), devices=len(busy),
                   ops=dict(ops), modules=dict(modules),
                   idle_gaps=labelled)


def top_ops(red: Reduced, n: int = 10) -> list:
    return [[k, v[1]] for k, v in
            sorted(red.ops.items(), key=lambda kv: -kv[1][1])[:n]]


def describe(path: str, names: int = 40) -> dict:
    """Planes, lines and the stats of the first event of each distinct
    op name: for reading a new trace by hand."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        lines = []
        for ln in plane.lines:
            seen, total = {}, 0
            for e in ln.events:
                total += 1
                if e.name not in seen and len(seen) < names:
                    seen[e.name] = {k: str(v)[:200] for k, v in e.stats}
            lines.append({"line": ln.name, "events": total, "first": seen})
        out.append({"plane": plane.name, "lines": lines})
    return {"planes": out}
