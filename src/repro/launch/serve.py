"""Serving driver: batched requests through the continuous-batching engine,
optionally chunk-prefilled (elastic-FIFO pipeline) and data-parallel across
replica shards.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b --reduced \
      --requests 16 [--qk-attention] [--prefill-chunk 16] [--replicas 2]
"""
from __future__ import annotations

import argparse

import jax
import numpy as np


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--spiking", action="store_true")
    ap.add_argument("--qk-attention", action="store_true")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunked prefill: tokens per chunk interleaved "
                         "with decode ticks (0 = blocking prefill)")
    ap.add_argument("--chunks-per-tick", type=int, default=1)
    ap.add_argument("--max-queue", type=int, default=0,
                    help="admission FIFO bound; submit applies "
                         "backpressure when full (0 = unbounded)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="data-parallel engine replicas; slot pools shard "
                         "across local devices, least-loaded dispatch")
    ap.add_argument("--deadline-ticks", type=int, default=0,
                    help="per-request deadline in engine ticks; requests "
                         "that exceed it finish with status "
                         "'deadline_miss' (0 = no deadline)")
    ap.add_argument("--integrity-every", type=int, default=0,
                    help="run the numeric/packed-state integrity guard "
                         "every N decode ticks; flagged slots are "
                         "quarantined and replayed (0 = off)")
    ap.add_argument("--chaos", action="store_true",
                    help="run the canned deterministic fault plan "
                         "(replica kill + NaN injections + fused-kernel "
                         "fault) against the trace — demo of the "
                         "self-healing path; implies --integrity-every 1")
    ap.add_argument("--chaos-seed", type=int, default=0)
    args = ap.parse_args()

    from .compile_cache import use_compile_cache
    use_compile_cache()
    from ..configs import get_config, reduced as reduce_cfg, build_model
    from ..serve import (Engine, EngineConfig, ReplicaRouter,
                         demo_chaos_plan)

    overrides = {}
    if args.spiking:
        overrides["spiking"] = True
    if args.qk_attention:
        overrides["attention_kind"] = "qk_spiking"
    cfg = get_config(args.arch, **overrides)
    if args.reduced:
        cfg = reduce_cfg(cfg, **overrides)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    integrity = args.integrity_every or (1 if args.chaos else 0)
    ecfg = EngineConfig(max_slots=args.slots, max_len=args.max_len,
                        prefill_chunk=args.prefill_chunk,
                        prefill_chunks_per_tick=args.chunks_per_tick,
                        max_queue=args.max_queue,
                        integrity_every=integrity,
                        deadline_ticks=args.deadline_ticks)
    faults = None
    if args.chaos:
        faults = demo_chaos_plan(args.chaos_seed, n_replicas=args.replicas)
        print(f"[serve] chaos plan: {faults.summary()['events']}")
    if args.replicas > 1:
        eng = ReplicaRouter(model, params, ecfg, n_replicas=args.replicas,
                            faults=faults)
    else:
        eng = Engine(model, params, ecfg, faults=faults)
    rng = np.random.default_rng(0)
    for i in range(args.requests):
        plen = int(rng.integers(4, 24))
        eng.submit(rng.integers(0, cfg.vocab_size, plen),
                   max_new=args.max_new, temperature=args.temperature)
    eng.run_until_drained()
    print("[serve]", eng.stats())


if __name__ == "__main__":
    main()
