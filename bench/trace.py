"""Reduction of a profiler trace (``jax.profiler`` XSpace) to the numbers the
per-layer metrics read.

The harness wraps its own work in ``jax.profiler.TraceAnnotation`` spans
named ``bench.<what>``; the span ``bench.window`` marks the traced window.
Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
event per operation that ran on the chip, and ``XLA Modules`` one event per
execution of a compiled program (``jit_<function name>``). Host and device
events share the trace's clock.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
# an op event's name is its HLO instruction; the ops that only hold other
# ops (a scan's while loop) are left out of the op table, not of busy time
CONTAINER = re.compile(r"^%?(while|conditional|call)\b")
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class Summary:
    """What one traced window holds. Times are nanoseconds."""
    window_ns: float                  # length of the traced window
    busy_ns: list                     # per chip: union of op intervals
    ops: dict                         # op name -> [total ns, calls], all chips
    modules: dict                     # program name -> [total ns, calls]
    spans: dict                       # host span name -> [(start, dur)]
    idle_gaps: list                   # [(label, ns)] longest first, chip 0

    @property
    def busy_s(self) -> float:
        return sum(self.busy_ns) / len(self.busy_ns) / 1e9 if self.busy_ns \
            else 0.0

    @property
    def window_s(self) -> float:
        return self.window_ns / 1e9

    def op_ns(self, pattern: str) -> tuple[float, int]:
        """Total device ns and calls of the ops whose name matches from
        its start."""
        rx = re.compile(pattern)
        ns = calls = 0
        for name, (t, c) in self.ops.items():
            if rx.match(name):
                ns += t
                calls += c
        return ns, calls


def find_xplane(log_dir: str) -> str:
    hits = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                            recursive=True))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return hits[-1]


def load(path: str):
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find_xplane(path)
    return ProfileData.from_file(path)


def op_name(hlo: str) -> str:
    """'%fusion.52 = f32[..] fusion(...)' -> '%fusion.52'; a plain name is
    kept as it is."""
    return hlo.split(" = ", 1)[0]


def _union(intervals: list) -> list:
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals: list, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _label(spans: dict, t: float) -> str:
    """Innermost harness span covering host time ``t`` (the shortest)."""
    best, best_d = "none", None
    for name, evs in spans.items():
        if name == WINDOW_SPAN:
            continue
        for s, d in evs:
            if s <= t <= s + d and (best_d is None or d < best_d):
                best, best_d = name[len(SPAN_PREFIX):], d
    return best


def reduce(profile, max_gaps: int = 10) -> Summary:
    """One pass over the planes. The window is the ``bench.window`` span;
    device events outside it are dropped, events across its edges are
    clipped to it."""
    spans: dict = defaultdict(list)
    devices = []
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            devices.append(plane)
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    spans[ev.name].append((ev.start_ns, ev.duration_ns))
    if not spans.get(WINDOW_SPAN):
        raise ValueError(f"trace holds no {WINDOW_SPAN} span")
    w0, wd = spans[WINDOW_SPAN][0]
    w1 = w0 + wd
    devices.sort(key=lambda p: int(p.name.rsplit(":", 1)[1]))
    ops: dict = defaultdict(lambda: [0.0, 0])
    modules: dict = defaultdict(lambda: [0.0, 0])
    busy, gaps = [], []
    for n, plane in enumerate(devices):
        ivs = []
        for line in plane.lines:
            if line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                if e <= w0 or s >= w1:
                    continue
                d = min(e, w1) - max(s, w0)
                name = op_name(ev.name)
                if line.name == OPS_LINE:
                    ivs.append((s, e))
                    if CONTAINER.match(name):
                        continue
                table = ops if line.name == OPS_LINE else modules
                table[name][0] += d
                table[name][1] += 1
        merged = _union(_clip(ivs, w0, w1))
        busy.append(sum(e - s for s, e in merged))
        if n == 0:
            edges = [w0] + [x for iv in merged for x in iv] + [w1]
            for s, e in zip(edges[0::2], edges[1::2]):
                if e > s:
                    gaps.append((s, e))
    gaps.sort(key=lambda g: g[0] - g[1])
    labelled = [(_label(spans, (s + e) / 2), e - s)
                for s, e in gaps[:max_gaps]]
    return Summary(window_ns=wd, busy_ns=busy, ops=dict(ops),
                   modules=dict(modules),
                   spans=dict(spans), idle_gaps=labelled)


def breakdown(summary: Summary, n: int = 10) -> dict:
    """The ``breakdown`` entry of a result line: the device ops that took
    most time and the longest idle gaps, in seconds."""
    top = sorted(summary.ops.items(), key=lambda kv: -kv[1][0])[:n]
    nchips = max(len(summary.busy_ns), 1)
    return {"device_ops": [[k, v[0] / 1e9 / nchips] for k, v in top],
            "idle_gaps": [[k, ns / 1e9] for k, ns in summary.idle_gaps[:n]]}
