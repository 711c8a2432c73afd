#!/usr/bin/env python3
"""Where the host's time goes while a serving cell's chip sits idle: the
engine's phase spans (``serve.*``, see ``repro.serve.engine.Engine.step``)
and its tick records, read beside the device's events on the trace's one
clock.

    python bench/phases.py --workload qwen3-qks.chat --seed 5 --seconds 51

One process builds the cell once (weights, warm-up), then runs two windows
of ``--seconds`` on fresh engines with the cell's traffic: the first with
the profiler off, the second traced as ``bench/run.py --trace 1`` traces
it. Each prints one JSON line.

- Untraced: the host time of the steps that decoded in the window (mean,
  median, and a line in the live slots, so two runs compare at one
  load), the slowest step with its phases and syncs, the p50/p95 of the
  admission queue's wait and of the prefill time, and the window's ITL
  p95.
- Traced: the same step times over the traced steps (their mean is what
  ``engine_tick_ms.itl`` reads); every idle interval of the traced window split by the innermost
  host span covering each piece (``idle_share_by_span``, % of the window,
  ``none`` where no span covers it); the ten longest gaps with their
  labels; the share of the decode program's executions on the device that
  lie inside a ``serve.decode`` span; the host syncs of the traced steps
  that decoded beside their live slots.

An engine without tick records or spans leaves those readings out. The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import collections
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import run as R  # noqa: E402
from bench import trace as T  # noqa: E402
from bench.common import percentile  # noqa: E402

ENGINE_PREFIX = "serve."
DECODE_SPAN = "serve.decode"
DECODE_PROGRAM = "decode_step"        # in the module name jit_decode_step
NONE = "none"


# --------------------------------------------------------------- reduction
def span_label(name: str) -> str:
    """An engine span keeps its full name without the profiler's metadata
    suffix (``serve.prefill#uid=3,chunk=0#`` -> ``serve.prefill``); a
    harness span is named as ``bench/trace.py`` labels gaps (``step``)."""
    name = name.split("#", 1)[0]
    return name[len(T.SPAN_PREFIX):] if name.startswith(T.SPAN_PREFIX) \
        else name


def host_spans(profile) -> list:
    """(label, start, end) of every harness and engine span in the trace
    but the window's own."""
    out = []
    for plane in profile.planes:
        if T.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                name = ev.name.split("#", 1)[0]
                if name == T.WINDOW_SPAN or not name.startswith(
                        (T.SPAN_PREFIX, ENGINE_PREFIX)):
                    continue
                out.append((span_label(name), ev.start_ns,
                            ev.start_ns + ev.duration_ns))
    return out


def label_at(spans: list, t: float) -> str:
    """The innermost (shortest) span covering time ``t``."""
    best, best_d = NONE, None
    for label, s, e in spans:
        if s <= t <= e and (best_d is None or e - s < best_d):
            best, best_d = label, e - s
    return best


def split_idle(idle: list, spans: list) -> dict:
    """Nanoseconds of the sorted, disjoint ``idle`` intervals by the
    innermost span covering each piece of them: one sweep over every
    span's and interval's edges."""
    edges = sorted({x for _, s, e in spans for x in (s, e)}
                   | {x for iv in idle for x in iv})
    by_start = sorted(range(len(spans)), key=lambda i: spans[i][1])
    by_end = sorted(range(len(spans)), key=lambda i: spans[i][2])
    active: set = set()
    out: dict = collections.defaultdict(float)
    i = j = k = 0
    for a, b in zip(edges, edges[1:]):
        while i < len(by_start) and spans[by_start[i]][1] <= a:
            active.add(by_start[i])
            i += 1
        while j < len(by_end) and spans[by_end[j]][2] <= a:
            active.discard(by_end[j])
            j += 1
        while k < len(idle) and idle[k][1] <= a:
            k += 1
        if k == len(idle) or idle[k][0] > a:
            continue                  # a busy piece
        inner = min(active, key=lambda n: spans[n][2] - spans[n][1],
                    default=None)
        out[NONE if inner is None else spans[inner][0]] += b - a
    return dict(out)


def reduce(profile, max_gaps: int = 10) -> dict:
    """The traced window's idle time by span, averaged over the chips as
    ``bench/trace.py`` averages busy time, with the same window and the
    same busy intervals; the longest gaps of chip 0, labelled; and the
    decode program's executions inside a ``serve.decode`` span."""
    spans = host_spans(profile)
    windows = [(ev.start_ns, ev.start_ns + ev.duration_ns)
               for plane in profile.planes
               if not T.DEVICE_PLANE.match(plane.name)
               for line in plane.lines for ev in line.events
               if ev.name == T.WINDOW_SPAN]
    if not windows:
        raise ValueError(f"trace holds no {T.WINDOW_SPAN} span")
    w0, w1 = windows[0]
    decodes = [(s, e) for label, s, e in spans if label == DECODE_SPAN]
    devices = sorted((p for p in profile.planes
                      if T.DEVICE_PLANE.match(p.name)),
                     key=lambda p: int(p.name.rsplit(":", 1)[1]))
    by_span: dict = collections.defaultdict(float)
    busy, gaps = [], []
    inside = runs = 0
    for n, plane in enumerate(devices):
        ivs = []
        for line in plane.lines:
            for ev in line.events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                if e <= w0 or s >= w1:
                    continue
                if line.name == T.OPS_LINE:
                    ivs.append((s, e))
                elif line.name == T.MODULES_LINE and \
                        DECODE_PROGRAM in ev.name:
                    runs += 1
                    inside += any(a <= s and e <= b for a, b in decodes)
        merged = T._union(T._clip(ivs, w0, w1))
        busy.append(sum(e - s for s, e in merged))
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        idle = [(s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s]
        for label, ns in split_idle(idle, spans).items():
            by_span[label] += ns / len(devices)
        if n == 0:
            gaps = sorted(idle, key=lambda g: g[0] - g[1])[:max_gaps]
    return {"window_ns": w1 - w0, "busy_ns": busy,
            "idle_by_span": dict(by_span),
            "gaps": [(label_at(spans, (s + e) / 2), e - s) for s, e in gaps],
            "decode_runs": runs, "decode_runs_in_span": inside}


def shares(red: dict) -> dict:
    """``reduce``'s numbers as shares of the window, in %."""
    w = red["window_ns"]
    busy = sum(red["busy_ns"]) / len(red["busy_ns"]) if red["busy_ns"] \
        else 0.0
    by = {k: 100.0 * ns / w for k, ns in sorted(red["idle_by_span"].items(),
                                                key=lambda kv: -kv[1])}
    out = {"idle_share": 100.0 * (1.0 - busy / w),
           "idle_share_by_span": by,
           "idle_outside_engine_share": sum(
               v for k, v in by.items() if not k.startswith(ENGINE_PREFIX)),
           "gaps_ms": [[k, ns / 1e6] for k, ns in red["gaps"]]}
    if red["decode_runs"]:
        out["decode_in_span_share"] = \
            100.0 * red["decode_runs_in_span"] / red["decode_runs"]
    return out


# ------------------------------------------------------------------ windows
def _in_window(run, st, t0: float, traced: bool) -> list:
    """(step entry, tick record or None) of the steps that decoded, inside
    the window (``traced``: inside the traced part of it)."""
    if not st.steps:
        return []
    recs = list(getattr(st.engine, "_ticks", []))
    if len(recs) != len(st.steps):    # no tick log, or it rolled over
        recs = [None] * len(st.steps)
    spans = run.spans[-len(st.steps):]
    return [(s, r) for s, r, (_, a, _, _) in zip(st.steps, recs, spans)
            if s["decoded"] and (s["traced"] if traced
                                 else a - t0 < run.seconds)]


def _request_times(eng) -> dict:
    reqs = [r for r in list(eng.finished) + list(eng.active.values())
            if getattr(r, "admitted_t", 0.0) and r.first_token_t]
    wait = [r.admitted_t - r.enqueued_t for r in reqs]
    pre = [r.first_token_t - r.admitted_t for r in reqs]
    return {"queue_wait_ms": [1e3 * percentile(wait, 50),
                              1e3 * percentile(wait, 95)],
            "prefill_ms": [1e3 * percentile(pre, 50),
                           1e3 * percentile(pre, 95)]} if reqs else {}


def step_times(steps: list) -> dict:
    """Host time of the steps that decoded: mean, median, the mean live
    slots, and a line fitted to the steps that ran no prefill chunk,
    ``ms = fixed + per_live_slot * live``, over those within three times
    the median (a host stall of seconds would tilt the line). Each side
    of a comparison can then be read at the same load."""
    dt = 1e3 * np.array([s["dt"] for s, _ in steps])
    live = np.array([s["decode_tokens"] for s, _ in steps])
    out = {"step_ms_mean": float(dt.mean()),
           "step_ms_p50": float(np.median(dt)),
           "live_mean": float(live.mean())}
    keep = np.array([not s["chunk_rows"] for s, _ in steps]) \
        & (dt <= 3 * np.median(dt))
    if len(set(live[keep])) > 1:
        slope, fixed = np.polyfit(live[keep], dt[keep], 1)
        out["decode_only_fit_ms"] = {"fixed": float(fixed),
                                     "per_live_slot": float(slope),
                                     "steps": int(keep.sum())}
    return out


def untraced_report(kind, run, st, t0: float) -> dict:
    steps = _in_window(run, st, t0, traced=False)
    out = {"traced": False, "steps_decoded": len(steps),
           "itl_p95_ms": kind.end_to_end(run, st)["itl_p95_ms"]}
    out.update(_request_times(st.engine))
    if not steps:
        return out
    out.update(step_times(steps))
    slow, rec = max(steps, key=lambda sr: sr[0]["dt"])
    out["slowest_step"] = {"dt_ms": 1e3 * slow["dt"]}
    if rec is not None:
        out["slowest_step"].update(
            live=rec["live"], chunks=rec["chunks"], syncs=rec["syncs"],
            phases_ms={k: ns / 1e6 for k, ns in rec["phases"].items()})
    return out


def traced_report(run, st, t0: float) -> dict:
    steps = _in_window(run, st, t0, traced=True)
    out = {"traced": True, "steps_decoded": len(steps)}
    if steps:
        out.update(step_times(steps))
        out["engine_tick_ms"] = out["step_ms_mean"]
    recs = [r for _, r in steps if r is not None]
    if recs:
        out["host_syncs_per_tick"] = float(np.mean([r["syncs"]
                                                    for r in recs]))
        out["live_per_tick"] = float(np.mean([r["live"] for r in recs]))
        out["syncs_minus_live"] = dict(sorted(collections.Counter(
            r["syncs"] - r["live"] for r in recs).items()))
        out["phase_ms_mean"] = {
            k: float(np.mean([r["phases"].get(k, 0) for r in recs])) / 1e6
            for k in sorted({k for r in recs for k in r["phases"]})}
    red = reduce(T.load(run._trace_dir))
    out.update(shares(red))
    run.trace_reduce()
    if red["busy_ns"]:                # the benchmark's busy time, and ours
        out["busy_s_check"] = [run.summary.busy_s, sum(red["busy_ns"])
                               / len(red["busy_ns"]) / 1e9]
    return out


def phases(cell: str, seed: int, seconds: float, *,
           require_tpu: bool = True, sizes_override=None,
           params_override=None) -> list:
    """The overrides let the CPU tests run at a size a test can hold."""
    from repro.serve import Engine

    kind, run = R.prepare(cell, seed, seconds, False,
                          require_tpu=require_tpu,
                          sizes_override=sizes_override,
                          params_override=params_override)
    built = kind.setup(run)
    ecfg, built.engine = built.engine.cfg, None
    rows = []
    for traced in (False, True):
        run.trace, run.spans = traced, []
        st = kind.State(built.model, built.params,
                        Engine(built.model, built.params, ecfg))
        R.settle()
        t0 = time.perf_counter()
        kind.measure(run, st)
        gc.unfreeze()
        rows.append(traced_report(run, st, t0) if traced
                    else untraced_report(kind, run, st, t0))
        print(json.dumps(rows[-1]), flush=True)
        del st
        gc.collect()
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    try:
        phases(args.workload, args.seed, args.seconds)
    except R.NoChip as e:
        print(f"phases: {e}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
