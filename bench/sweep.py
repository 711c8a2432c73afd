#!/usr/bin/env python3
"""Offered-rate sweep of an open-loop serving cell: the rate a cell's file
fixes is about four fifths of the highest rate the engine sustains, and
this finds that rate.

    python bench/sweep.py --workload qwen3-qks.chat --seed 5 \
        --seconds 51 --rates 0.75,1,1.25,1.5,1.75,2

One process builds the cell once (weights, warm-up), then for each rate
runs a window of ``--seconds`` on a fresh engine with the cell's traffic at
that rate and prints one JSON line: the requests due, the time to first
token (median, p95, and the medians of the requests due in the first and
in the second half of the window), the mean engine step, the backlog (the
requests due in the window that had no first token when it closed) and
the host stalls the run saw. A backlog that grows with
the window, and a second-half median well above the first, mark a rate
the engine does not sustain. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import run as R  # noqa: E402
from bench.common import percentile  # noqa: E402


def row(rate: float, st, seconds: float) -> dict:
    """One rate's readings from a measured serving state."""
    sent = [r for r in st.reqs if r.uid >= 0]
    ttft = R.traffic("open_loop_serve").ttft_s(st)
    due = np.array([r.due for r in sent])
    first = np.array([r.first for r in sent])
    half = due < seconds / 2
    steps = [x["dt"] for x in st.steps]
    return {"rate_per_s": rate, "due": len(sent),
            "step_mean_ms": 1e3 * float(np.mean(steps)) if steps else 0.0,
            "ttft_p50_ms": 1e3 * percentile(ttft, 50),
            "ttft_p95_ms": 1e3 * percentile(ttft, 95),
            "ttft_p50_first_half_ms": 1e3 * percentile(ttft[half], 50),
            "ttft_p50_second_half_ms": 1e3 * percentile(ttft[~half], 50),
            "backlog_at_close": int(np.sum(~(first <= seconds)))}


def sweep(cell: str, seed: int, seconds: float, rates: list,
          drain_s: float, *, require_tpu: bool = True,
          sizes_override=None, params_override=None) -> list:
    """The overrides let the CPU tests sweep at a size a test can hold."""
    from repro.serve import Engine

    kind, run = R.prepare(
        cell, seed, seconds, False, require_tpu=require_tpu,
        sizes_override=sizes_override,
        params_override=dict(params_override or {}, drain_s=drain_s))
    built = kind.setup(run)
    rows = []
    for rate in rates:
        run.params["rate_per_s"] = rate
        st = kind.State(built.model, built.params,
                        Engine(built.model, built.params, built.engine.cfg))
        R.settle()
        t0 = time.perf_counter()
        run._longest = (0.0, 0.0, t0)
        kind.measure(run, st)
        gc.unfreeze()
        rows.append(dict(row(rate, st, seconds), **run.stalls_since(t0)))
        print(json.dumps(rows[-1]), flush=True)
        del st
        gc.collect()
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--drain", type=float, default=10.0)
    args = ap.parse_args(argv)
    try:
        sweep(args.workload, args.seed, args.seconds,
              [float(r) for r in args.rates.split(",")], args.drain)
    except R.NoChip as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
