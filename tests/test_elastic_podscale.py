"""Elastic re-mesh with the PRODUCTION mesh topology (pod, data, model),
scaled to 32 virtual devices so collectives can actually EXECUTE on one CPU
core (512-thread rendezvous deadlocks a 1-core host; the full-size meshes
are exercised compile-only by the dry-run): compile+run a train step on the
2-pod mesh, lose a pod, rebuild the 1-pod mesh via make_elastic_mesh,
reshard the checkpoint onto it, recompile, and take a step.

The ``ElasticRunner`` edge-case tests below run IN-PROCESS on 1-device
meshes (the re-mesh/reshard/resume control flow is device-count-agnostic):
failure at step 0 with no checkpoint on disk, back-to-back failures before
any ``restore_capacity``, and a failure on the very first step after a
downgrade.
"""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def test_pod_loss_remesh_at_512():
    code = """
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config, reduced, build_model
        from repro.launch.mesh import (make_elastic_mesh, make_mesh,
                                       make_production_mesh)
        from repro.models import sharding as shd
        from repro.optim.schedules import constant_lr
        from repro.train import (make_train_step, train_state_init,
                                 save_checkpoint, restore_checkpoint,
                                 latest_checkpoint)
        import tempfile

        cfg = reduced(get_config('qwen3-1.7b'))
        model = build_model(cfg)
        step = make_train_step(model, schedule=constant_lr(1e-3))
        ckdir = tempfile.mkdtemp()

        def run_on(mesh, state=None):
            shd.set_global_mesh(mesh)
            NS = lambda t: jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s), t,
                is_leaf=lambda s: isinstance(s, P))
            if state is None:
                params = model.init(jax.random.PRNGKey(0))
                params = jax.device_put(params, NS(shd.param_specs(params, mesh)))
                state = train_state_init(params)
            batch = {'tokens': jax.device_put(
                jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0,
                                   cfg.vocab_size),
                NS(shd.batch_specs({'t': jax.ShapeDtypeStruct((8, 32),
                                                              jnp.int32)},
                                   mesh))['t'])}
            with mesh:
                state, m = jax.jit(step)(state, batch)
            return state, float(m['loss'])

        # 2 pods of (data=4, model=4) = 32 chips (production topology)
        mesh2 = make_mesh((2, 4, 4), ('pod', 'data', 'model'),
                          devices=jax.devices()[:32])
        state, loss2 = run_on(mesh2)
        save_checkpoint(ckdir, int(state.step), state)

        # pod failure -> elastic 1-pod mesh (16 chips), reshard, resume
        mesh1 = make_elastic_mesh(1, pod_shape=(4, 4))
        shd.set_global_mesh(mesh1)
        shape = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
        NS1 = lambda t: jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh1, s), t,
            is_leaf=lambda s: isinstance(s, P))
        from repro.optim.adamw import AdamWState
        sh = jax.tree_util.tree_map(lambda _: NamedSharding(mesh1, P()),
                                    shape)
        restored, stp = restore_checkpoint(latest_checkpoint(ckdir), shape, sh)
        state3, loss1 = run_on(mesh1, restored)
        print('OK steps', stp, int(state3.step), 'losses', loss2, loss1)
        assert int(state3.step) == stp + 1
    """
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=32",
               PYTHONPATH=str(ROOT / "src"), JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, timeout=540)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK steps" in r.stdout


# ===================================================== ElasticRunner edges
def _make_runner(lm_zoo, ckpt_dir, *, ckpt_every=2, n_builders=3):
    """In-process ElasticRunner on 1-device meshes: every builder is
    buildable, so ``level`` tracks pure control-flow (degrade on failure,
    climb on restore_capacity) without needing a multi-device host."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.mesh import make_mesh
    from repro.optim.schedules import constant_lr
    from repro.train import make_train_step, train_state_init
    from repro.train.elastic import ElasticConfig, ElasticRunner

    cfg, model, params = lm_zoo("qwen3-1.7b")
    step = make_train_step(model, schedule=constant_lr(1e-3))
    builders = [
        (lambda: make_mesh((1,), ("data",))) for _ in range(n_builders)]

    def make_step(mesh):
        return jax.jit(step)

    def make_state(mesh):
        return train_state_init(params)

    def state_shardings(shape, mesh):
        return jax.tree_util.tree_map(
            lambda _: NamedSharding(mesh, P()), shape)

    tokens = jnp.asarray(
        __import__("numpy").random.default_rng(0).integers(
            0, cfg.vocab_size, (2, 16)), jnp.int32)

    def loader(step_idx):
        return {"tokens": tokens}

    return ElasticRunner(builders, make_step, make_state, state_shardings,
                         loader, ElasticConfig(ckpt_dir=str(ckpt_dir),
                                               ckpt_every=ckpt_every))


def test_elastic_failure_at_step_zero_no_checkpoint(lm_zoo, tmp_path):
    """Failure BEFORE the first step with an empty checkpoint dir: the
    runner must degrade the mesh and restart from a FRESH init (there is
    nothing to restore) instead of crashing on a missing checkpoint."""
    runner = _make_runner(lm_zoo, tmp_path / "ck0")
    runner.inject_failure(0)
    state, events = runner.run(2)
    assert int(state.step) == 2
    kinds = [e["kind"] for e in events]
    assert kinds[:2] == ["failure", "remesh"]
    assert runner.level == 1
    # fresh init, not a restore: no restore event before the remesh
    assert "restore" not in kinds
    (remesh,) = [e for e in events if e["kind"] == "remesh"]
    assert remesh["resume_step"] == 0


def test_elastic_back_to_back_failures_before_restore(lm_zoo, tmp_path):
    """Two failures with NO restore_capacity in between: level degrades
    monotonically (0 -> 1 -> 2), each recovery resumes from the latest
    durable checkpoint, and training still reaches the target step."""
    runner = _make_runner(lm_zoo, tmp_path / "ck1")
    state, _ = runner.run(3)            # checkpoint lands at step 2
    runner.inject_failure(3)
    state, _ = runner.run(4)
    assert runner.level == 1 and int(state.step) == 4
    runner.inject_failure(4)            # second failure, still degraded
    state, events = runner.run(6)
    assert runner.level == 2 and int(state.step) == 6
    fails = [e["step"] for e in events if e["kind"] == "failure"]
    assert fails == [3, 4]
    # every restore — each run()'s warm start AND both post-failure
    # recoveries — came from the step-2 checkpoint (the latest durable)
    restores = [e["step"] for e in events if e["kind"] == "restore"]
    assert len(restores) >= 2 and set(restores) == {2}
    runner.restore_capacity()
    assert runner.level == 0


def test_elastic_failure_on_first_step_after_downgrade(lm_zoo, tmp_path):
    """The downgraded mesh dies on the VERY FIRST step it executes (before
    it ever writes a checkpoint of its own): the runner must re-degrade a
    level further and re-restore from the same pre-failure checkpoint, not
    loop or lose the durable state. The second failure is armed from
    inside the loader — the only hook that runs between the remesh and the
    first degraded step."""
    runner = _make_runner(lm_zoo, tmp_path / "ck2")
    base_loader, tripped = runner.loader, []

    def tripwire(step_idx):
        if not tripped and any(e["kind"] == "remesh" for e in runner.events):
            tripped.append(step_idx)
            runner.inject_failure(step_idx + 1)  # dies right after this step
        return base_loader(step_idx)

    runner.loader = tripwire
    runner.run(3)                       # durable checkpoint labeled step 2
    runner.inject_failure(3)
    state, events = runner.run(6)
    assert int(state.step) == 6
    assert runner.level == 2            # two downgrades, no capacity back
    # the degraded mesh got exactly one step in before its own failure
    assert tripped == [3]
    fails = [e["step"] for e in events if e["kind"] == "failure"]
    assert fails == [3, 4]
    # both recoveries (and run(6)'s warm start) restored the SAME durable
    # checkpoint — the one labeled step 2, written before any failure
    restores = [e["step"] for e in events if e["kind"] == "restore"]
    assert len(restores) >= 2 and set(restores) == {2}
    remeshes = [e["resume_step"] for e in events if e["kind"] == "remesh"]
    assert remeshes == [3, 3]
