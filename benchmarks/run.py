"""Benchmark harness entry point — one section per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run            # everything
  python -m benchmarks.run --kd-steps 40             # quick KD budget
  python -m benchmarks.run --sections kernels,serve  # subset (CI artifacts)

Writes a machine-readable run summary (section status + wall time) to
``BENCH_run.json`` at the REPO ROOT regardless of CWD — like every
``BENCH_*.json`` artifact — so the perf trajectory is captured across PRs.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--sections", default="",
                    help="comma-separated section keys to run "
                         "(kd,resources,spikes,efficiency,timestep,"
                         "kernels,ops,serve); empty = all")
    ap.add_argument("--kd-steps", type=int, default=None,
                    help="training-step budget for the kd section "
                         "(forwarded to fig8_kd_accuracy.run; default: "
                         "fig8_kd_accuracy.DEFAULT_STEPS)")
    args = ap.parse_args()

    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    from benchmarks.common import artifact_path
    from benchmarks import (fig8_kd_accuracy, kernel_bench, ops_dispatch,
                            serve_throughput, table1_resources,
                            table2_spikes, table3_efficiency,
                            timestep_ablation)
    sections = [
        ("kd", "Fig 8 — KD pipeline accuracy (KDT/F&Q/KD-QAT/W2TTFS)",
         lambda: fig8_kd_accuracy.main(steps=args.kd_steps)),
        ("resources", "Table I — per-module resources", table1_resources.main),
        ("spikes", "Table II — ResNet-11 vs QKFResNet-11 spikes/latency/energy",
         table2_spikes.main),
        ("efficiency", "Table III — synaptic-op efficiency (GSOPS/W model)",
         table3_efficiency.main),
        ("timestep", "Timestep ablation — single- vs multi-timestep execution",
         timestep_ablation.main),
        ("kernels", "Kernel bench — Pallas kernels roofline + oracle timing "
         "+ byte-skip sparsity sweep",
         lambda: kernel_bench.main(with_sweep=True, with_grad=True)),
        ("ops", "ops dispatch — repro.ops entry-point overhead vs direct "
         "kernel calls (< 1% bar)", ops_dispatch.main),
        ("serve", "Serving throughput — continuous batching + elastic-FIFO "
         "chunked prefill + QKFormer (C4) mode", serve_throughput.main),
    ]
    if args.sections:
        keys = {k.strip() for k in args.sections.split(",") if k.strip()}
        unknown = keys - {k for k, _, _ in sections}
        if unknown:
            sys.exit(f"unknown --sections keys: {sorted(unknown)}")
        sections = [s for s in sections if s[0] in keys]
    sections = [(title, fn) for _, title, fn in sections]
    failed = []
    section_log = []
    for title, fn in sections:
        print(f"\n{'=' * 72}\n== {title}\n{'=' * 72}")
        t0 = time.time()
        ok = True
        try:
            fn()
        except Exception:
            traceback.print_exc()
            failed.append(title)
            ok = False
        dt = time.time() - t0
        section_log.append({"section": title, "ok": ok, "seconds": dt})
        print(f"== ({dt:.1f}s)")
    out_path = artifact_path("BENCH_run.json")
    with open(out_path, "w") as f:
        json.dump({"sections": section_log,
                   "failed": failed,
                   "finished_at": time.strftime("%Y-%m-%dT%H:%M:%S")},
                  f, indent=1)
    print(f"\nwrote {out_path}")
    if failed:
        print(f"FAILED sections: {failed}")
        sys.exit(1)
    print("All benchmark sections completed.")


if __name__ == "__main__":
    main()
