#!/usr/bin/env python3
"""Readings that a cell's limits are set from: the program's, the
control's and the planted faults', each on several seeds in one process.

    python bench/control.py --workload <cell> --seeds 11,12,13 --seconds 10

For each seed it runs the cell as ``run.py`` does (set-up, a window of
``--seconds`` at the cell's own load and sizes) and prints one JSON line:
the program's readings of the compared numbers, and, with ``--control 1``,
those of the control (the reference one precision step lower, in the
program's place) and of each fault the traffic kind plants in the
reference's place. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import run as R  # noqa: E402


def readings(cell: str, seed: int, seconds: float, control: bool,
             **overrides) -> dict:
    kind, run = R.prepare(cell, seed, seconds, False, **overrides)
    state = run.state = kind.setup(run)
    kind.measure(run, state)
    out = {"seed": seed, "program": kind.readings(run, state)}
    if control:
        out.update(kind.controls(run, state))
    out["limits"] = run.params["limits"]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)
    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            print(json.dumps(readings(args.workload, seed, args.seconds,
                                      bool(args.control))), flush=True)
        except R.NoChip as e:
            print(f"control: {e}", file=sys.stderr)
            return 1
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
