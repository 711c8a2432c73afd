"""End-to-end training driver.

Two regimes from one entry point:
  * CPU / laptop:  ``--reduced`` trains a miniature of any assigned arch on
    synthetic data and prints a real loss curve (examples use this).
  * Cluster:       full config on the production mesh (the dry-run proves
    the program compiles; this driver is what you'd actually launch).

Features wired in: microbatching, checkpoint/restart (+async), straggler
monitoring, elastic re-mesh on failure (--simulate-failure exercises the
whole failure path end-to-end), optional spiking/QKFormer modes.

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b --reduced \
      --steps 50 --batch 8 --seq 128 [--spiking] [--simulate-failure 20]
"""
from __future__ import annotations

import argparse
import time
from pathlib import Path

import jax
import numpy as np


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=10)
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--spiking", action="store_true")
    ap.add_argument("--qk-attention", action="store_true",
                    help="paper C4: spiking QKFormer attention")
    ap.add_argument("--policy", default=None,
                    choices=["reference", "fused_dense", "fused_packed"],
                    help="execution policy for the spiking layers "
                         "(repro.ops.ExecutionPolicy); the training step "
                         "resolves it through its gradient axis, so "
                         "--policy fused_dense trains the forward on the "
                         "event-driven kernels it deploys on")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--simulate-failure", type=int, default=0,
                    help="inject a device failure at this step (elastic path)")
    ap.add_argument("--compress", action="store_true",
                    help="int8+error-feedback DP gradient compression "
                         "(pure-DP shard_map path, no elastic runner)")
    ap.add_argument("--log-every", type=int, default=5)
    args = ap.parse_args()

    from .compile_cache import use_compile_cache
    use_compile_cache()
    from ..configs import get_config, reduced as reduce_cfg, build_model
    from ..data import ShardedLoader, SyntheticTokenDataset
    from .mesh import make_mesh
    from ..models import sharding as shd
    from ..optim import linear_warmup_cosine
    from ..train import (ElasticRunner, make_train_step, train_state_init,
                         TrainState)
    from ..train.elastic import ElasticConfig

    overrides = {}
    if args.spiking:
        overrides["spiking"] = True
    if args.qk_attention:
        overrides["attention_kind"] = "qk_spiking"
    if args.policy:
        if not args.spiking:
            ap.error("--policy requires --spiking (execution policies "
                     "govern the spiking layers)")
        # a training driver always wants the gradient axis: forward runs
        # the chosen kernels, backward gets the surrogate custom_vjp
        overrides["policy"] = args.policy + "+grad"
    cfg = get_config(args.arch, **overrides)
    if args.reduced:
        cfg = reduce_cfg(cfg, **overrides)
    model = build_model(cfg)
    schedule = linear_warmup_cosine(args.lr, args.warmup, args.steps)

    n_dev = len(jax.devices())

    def mesh_full():
        return make_mesh((n_dev,), ("data",))

    def mesh_half():
        return make_mesh((max(n_dev // 2, 1),), ("data",),
                         devices=jax.devices()[:max(n_dev // 2, 1)])

    ds = SyntheticTokenDataset(cfg.vocab_size, args.seq + 1)

    def make_np_batch(step, bs, shard, n_shards):
        return {"tokens": ds.batch(step, bs, shard, n_shards)}

    if args.compress:
        import jax.numpy as jnp
        from ..optim import error_feedback_init
        from ..train import make_compressed_train_step
        mesh = mesh_full()
        params = model.init(jax.random.PRNGKey(0))
        from ..train import train_state_init
        step_fn = jax.jit(make_compressed_train_step(model, mesh,
                                                     schedule=schedule))
        carry = (train_state_init(params), error_feedback_init(params))
        t0 = time.time()
        with mesh:
            for i in range(args.steps):
                batch = {"tokens": jnp.asarray(make_np_batch(
                    i, args.batch, 0, 1)["tokens"])}
                carry, m = step_fn(carry, batch)
                if i % args.log_every == 0:
                    print(f"step {i}: loss={float(m['loss']):.4f} "
                          f"(int8+EF compressed DP)")
        dt = time.time() - t0
        print(f"[train] compressed-DP done: {args.steps} steps in {dt:.1f}s")
        return

    def make_step(mesh):
        step = make_train_step(model, schedule=schedule,
                               microbatch=args.microbatch)
        return jax.jit(step, donate_argnums=(0,))

    def make_state(mesh):
        params = model.init(jax.random.PRNGKey(0))
        return train_state_init(params)

    def state_shardings(state_shape, mesh):
        from jax.sharding import NamedSharding, PartitionSpec as P
        return jax.tree_util.tree_map(
            lambda _: NamedSharding(mesh, P()), state_shape)

    loader = ShardedLoader(make_np_batch, args.batch, mesh_full())
    runner = ElasticRunner(
        [mesh_full, mesh_half], make_step, make_state, state_shardings,
        loader, ElasticConfig(ckpt_dir=args.ckpt_dir,
                              ckpt_every=args.ckpt_every))
    if args.simulate_failure:
        runner.inject_failure(args.simulate_failure)

    t0 = time.time()
    state, events = runner.run(args.steps)
    dt = time.time() - t0
    print(f"[train] {args.arch} done: {int(state.step)} steps in {dt:.1f}s "
          f"({int(state.step) / dt:.2f} steps/s)")
    for e in events:
        print("[event]", e)


if __name__ == "__main__":
    main()
