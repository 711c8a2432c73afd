"""Distributed execution on 8 virtual host devices — run in SUBPROCESSES so
the main pytest process keeps its single-device view (the brief's rule).

Covers: pjit train step on a (2,4) data x model mesh with the production
sharding rules, decode with sequence-sharded cache (context-parallel path),
int8+EF compressed DP training under shard_map, and the elastic runner's
failure -> re-mesh -> resume cycle on a real multi-device mesh.
"""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


def _run(code: str, timeout=420) -> subprocess.CompletedProcess:
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=str(ROOT / "src"),
               JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, env=env,
                          timeout=timeout)


def test_sharded_train_step_runs_and_matches_single_device():
    r = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config, reduced, build_model
        from repro.launch.mesh import make_mesh
        from repro.models import sharding as shd
        from repro.optim import adamw_init
        from repro.train import make_train_step, train_state_init
        from repro.optim.schedules import constant_lr

        cfg = reduced(get_config('qwen3-1.7b'))
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        batch = {'tokens': jax.random.randint(jax.random.PRNGKey(1), (4, 32),
                                              0, cfg.vocab_size)}
        step = make_train_step(model, schedule=constant_lr(1e-2))
        # single-device reference
        s_ref, m_ref = jax.jit(step)(train_state_init(params), batch)
        loss_ref = float(m_ref['loss'])

        mesh = make_mesh((2, 4), ('data', 'model'))
        shd.set_global_mesh(mesh)
        NS = lambda t: jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), t,
            is_leaf=lambda s: isinstance(s, P))
        p_sh = NS(shd.param_specs(params, mesh))
        params_sharded = jax.device_put(params, p_sh)
        state = train_state_init(params_sharded)
        b_sh = NS(shd.batch_specs(batch, mesh))
        batch_sharded = jax.device_put(batch, b_sh)
        with mesh:
            s_out, m = jax.jit(step)(state, batch_sharded)
        loss_sharded = float(m['loss'])
        assert abs(loss_ref - loss_sharded) < 1e-2, (loss_ref, loss_sharded)
        # params moved identically (allclose across the two regimes)
        a = jax.tree_util.tree_leaves(s_ref.params)[0]
        b = jax.tree_util.tree_leaves(s_out.params)[0]
        np.testing.assert_allclose(np.asarray(a), np.asarray(jax.device_get(b)),
                                   rtol=2e-2, atol=2e-4)
        print('OK', loss_ref, loss_sharded)
    """)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


def test_context_parallel_decode_matches_replicated():
    """long-context path: KV cache sharded over sequence on 'data' must give
    identical logits (GSPMD flash-decode combine is exact)."""
    r = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config, reduced, build_model
        from repro.launch.mesh import make_mesh
        from repro.models import sharding as shd

        cfg = reduced(get_config('qwen3-1.7b'))
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (1, 64), 0,
                                  cfg.vocab_size)
        _, cache = model.prefill(params, {'tokens': toks}, max_len=65)
        nxt = jnp.ones((1, 1), jnp.int32)
        ref, _ = model.decode_step(params, nxt, cache)

        mesh = make_mesh((8, 1), ('data', 'model'))
        shd.set_global_mesh(mesh)
        NS = lambda t: jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), t,
            is_leaf=lambda s: isinstance(s, P))
        c_sh = NS(shd.cache_specs(cache, mesh, batch=1,
                                  context_parallel=True))
        cache_sharded = jax.device_put(cache, c_sh)
        with mesh:
            out, _ = jax.jit(model.decode_step)(params, nxt, cache_sharded)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                                   rtol=1e-3, atol=1e-3)
        print('OK')
    """)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


def test_compressed_dp_training_converges_like_uncompressed():
    r = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config, reduced, build_model
        from repro.launch.mesh import make_mesh
        from repro.models import sharding as shd
        from repro.optim import error_feedback_init
        from repro.optim.schedules import constant_lr
        from repro.train import (make_train_step, make_compressed_train_step,
                                 train_state_init)

        cfg = reduced(get_config('qwen3-1.7b'))
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        mesh = make_mesh((8,), ('data',))
        shd.set_global_mesh(None)
        batch = {'tokens': jax.random.randint(jax.random.PRNGKey(1),
                                              (8, 32), 0, cfg.vocab_size)}
        plain = make_train_step(model, schedule=constant_lr(5e-3))
        comp = make_compressed_train_step(model, mesh,
                                          schedule=constant_lr(5e-3))
        sp = train_state_init(params)
        sc = (train_state_init(params), error_feedback_init(params))
        with mesh:
            cjit = jax.jit(comp)
            pjit_ = jax.jit(plain)
            lp = lc = None
            for _ in range(6):
                sp, mp = pjit_(sp, batch)
                sc, mc = cjit(sc, batch)
                lp, lc = float(mp['loss']), float(mc['loss'])
        print('plain', lp, 'compressed', lc)
        assert lc < 6.0 and abs(lp - lc) < 0.5, (lp, lc)
        print('OK')
    """)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


def test_elastic_failure_remesh_resume():
    """Full elastic cycle through the real driver: checkpoint -> injected
    failure -> degraded mesh -> restore -> finish."""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_ENABLE_COMPILATION_CACHE="false",  # tests keep it off
               PYTHONPATH=str(ROOT / "src"))
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "qwen3-1.7b",
         "--reduced", "--steps", "10", "--batch", "4", "--seq", "32",
         "--ckpt-every", "4", "--simulate-failure", "6",
         "--ckpt-dir", "/tmp/repro_ckpt_elastic_test"],
        capture_output=True, text=True, env=env, timeout=420)
    assert r.returncode == 0, r.stderr[-3000:]
    out = r.stdout
    assert "'kind': 'failure'" in out
    assert "'kind': 'remesh'" in out
    assert "'kind': 'restore'" in out
    assert "done: 10 steps" in out


def test_dryrun_cell_on_test_mesh():
    """A miniature of the dry-run itself: reduced arch, 8-device mesh,
    lower+compile+cost/memory analysis + collective extraction."""
    r = _run("""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config, reduced, build_model
        from repro.launch.mesh import make_mesh
        from repro.models import sharding as shd
        from repro.launch.hlo_analysis import analyze

        cfg = reduced(get_config('olmoe-1b-7b'))
        model = build_model(cfg)
        mesh = make_mesh((2, 4), ('data', 'model'))
        shd.set_global_mesh(mesh)
        NS = lambda t: jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), t,
            is_leaf=lambda s: isinstance(s, P))
        pshape = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        psh = NS(shd.param_specs(pshape, mesh))
        batch = {'tokens': jax.ShapeDtypeStruct((4, 64), jnp.int32)}
        bsh = NS(shd.batch_specs(batch, mesh))
        with mesh:
            lowered = jax.jit(lambda p, b: model.loss(p, b)[0],
                              in_shardings=(psh, bsh)).lower(pshape, batch)
            compiled = lowered.compile()
        assert compiled.memory_analysis() is not None
        r = analyze(compiled.as_text())
        assert r['flops'] > 0
        assert r['collective_wire_bytes'] > 0   # EP combine must exist
        print('OK', r['collectives'].keys())
    """)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout
