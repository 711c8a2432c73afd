"""The layer-pattern hybrid (granite-4.0-h-micro's Mamba-2/attention
``layer_types``, reduced to two periods of mamba, attention, mamba) against
its plain reference (``hybrid_reference``: sequential per-token recurrence,
full causal softmax, no cache), through the model and through ``Engine``;
and the pad-inert SSM prefill that lets SSM and hybrid prompts take
``prefill_pad`` buckets.

Tolerances: the model computes here in float32, as the reference does; the
two differ by summation order only (chunked SSD against one state update
per token, blocked attention against full softmax), measured at ~1.5e-7 on
logits of size ~1. ``TOL`` = 1e-5 leaves ~60 times that, and the same model
computing in bfloat16 misses it by ~three orders of magnitude (asserted
below), so a lower precision fails."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import hybrid_reference
from repro.configs import build_model, get_config, reduced
from repro.serve import Engine, EngineConfig

TOL = 1e-5
ENGINE = dict(max_slots=3, max_len=64, prefill_pad=16, prefill_chunk=16)


@pytest.fixture(scope="module")
def granite():
    cfg = reduced(get_config("granite-4.0-h-micro"))
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


def _tokens(cfg, n, seed=0):
    return np.random.default_rng(seed).integers(
        0, cfg.vocab_size, n).astype(np.int32)


def _gap(a, b):
    return float(np.abs(np.asarray(a, np.float32) - b).max())


def test_the_reduced_pattern_keeps_whole_periods_of_both_types(granite):
    cfg, _, params = granite
    assert cfg.layer_period == 3 and cfg.n_layers == 6
    assert set(cfg.layer_types[:3]) == {"mamba", "attention"}
    full = get_config("granite-4.0-h-micro")
    assert full.layer_period == 10 and full.layer_types.count("attention") == 4
    assert [i for i, t in enumerate(full.layer_types) if t == "attention"] \
        == [5, 15, 25, 35]
    # one stack per run of like layers, [periods, run length, ...]
    assert [r["mlp"]["gate"]["w"].shape[:2] for r in params["blocks"]["mamba"]] \
        == [(2, 1), (2, 1)]
    assert [r["mlp"]["gate"]["w"].shape[:2] for r in params["blocks"]["attn"]] \
        == [(2, 1)]


def test_full_forward_and_prefill_match_the_reference(granite):
    cfg, model, params = granite
    toks = _tokens(cfg, 37)
    ref = hybrid_reference.logits(params, cfg, toks)
    batch = {"tokens": jnp.asarray(toks)[None]}
    x, _ = model._stack_train(params, *model._embed(params, batch))
    assert _gap(model._logits(params, x)[0], ref) < TOL
    pre, _ = model.prefill(params, batch, return_all_logits=True)
    assert _gap(pre[0], ref) < TOL
    # the tolerance is tight enough that bfloat16 compute fails it
    low = build_model(dataclasses.replace(cfg, dtype=jnp.bfloat16))
    pre_low, _ = low.prefill(params, batch, return_all_logits=True)
    assert _gap(pre_low[0], ref) > 100 * TOL


def test_the_multipliers_and_nope_are_the_published_ones():
    cfg = get_config("granite-4.0-h-micro")
    assert (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.logits_scaling, cfg.attention_multiplier) == \
        (12.0, 0.22, 8.0, 1 / 64)
    assert cfg.position_embedding_type == "nope"
    from repro.models.attention import attn_scale
    assert attn_scale(cfg) == 1 / 64
    assert attn_scale(get_config("qwen3-1.7b")) == 128 ** -0.5


@pytest.mark.parametrize("arch", ["granite-4.0-h-micro", "mamba2-130m",
                                  "zamba2-7b"])
@pytest.mark.parametrize("length", [5, 21, 30])
def test_right_padded_prefill_leaves_the_true_length_state(arch, length):
    """A prompt right-padded to a 32-token bucket (told its length) leaves
    the same SSM and conv state, and the same logits at its positions, as
    the prompt prefilled at its true length: lengths below, across and off
    the 16-token SSD chunk."""
    cfg = reduced(get_config(arch))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    toks = _tokens(cfg, 32, seed=length)
    toks[length:] = 7                              # pad ids never read
    true_l, true_c = model.prefill(
        params, {"tokens": jnp.asarray(toks[:length])[None]},
        return_all_logits=True)
    pad_l, pad_c = model.prefill(
        params, {"tokens": jnp.asarray(toks)[None],
                 "lengths": jnp.asarray([length], jnp.int32)},
        return_all_logits=True)
    assert _gap(pad_l[0, :length], np.asarray(true_l[0])) < TOL
    kinds = set()
    raw_l, raw_c = model.prefill(params, {"tokens": jnp.asarray(toks)[None]})
    for (path, pad), true, raw in zip(
            jax.tree_util.tree_flatten_with_path(pad_c["layers"])[0],
            jax.tree_util.tree_leaves(true_c["layers"]),
            jax.tree_util.tree_leaves(raw_c["layers"])):
        kind = [k for k in ("ssm", "conv") if k in jax.tree_util.keystr(path)]
        if kind:
            kinds.add(kind[0])
            assert _gap(pad, np.asarray(true)) < TOL, path
            if kind == ["ssm"]:
                # without its length the padded prefill integrates the pads
                assert _gap(raw, np.asarray(true)) > TOL, path
    assert kinds == {"ssm", "conv"}


def _recorded_serve(model, params, prompts, max_new, monkeypatch):
    """Serve greedily through a chunked-prefill Engine; returns each
    request's tokens and the logits row each token was sampled from."""
    rows: dict = {}
    orig = Engine._sample

    def record(self, logits, req):
        row = logits[req.slot] if logits.ndim > 1 else logits
        rows.setdefault(req.uid, []).append(np.asarray(row))
        return orig(self, logits, req)

    monkeypatch.setattr(Engine, "_sample", record)
    eng = Engine(model, params, EngineConfig(**ENGINE))
    uids = [eng.submit(p, max_new=max_new) for p in prompts]
    fin = {r.uid: r for r in eng.run_until_drained()}
    monkeypatch.setattr(Engine, "_sample", orig)
    return [fin[u].out for u in uids], [rows[u] for u in uids], eng


def test_engine_chunked_prefill_and_decode_match_the_teacher_forced_reference(
        granite, monkeypatch):
    """Chunked prefill (16-token chunks over a 32-token bucket, the second
    chunk mostly pads) then batched decode through the slot pool: the
    logits of every served position equal the reference's full forward
    over prompt + served tokens."""
    cfg, model, params = granite
    prompt = _tokens(cfg, 21, seed=3)
    (out,), (rows,), eng = _recorded_serve(model, params, [prompt], 9,
                                           monkeypatch)
    ref = hybrid_reference.logits(params, cfg,
                                  np.concatenate([prompt, out[:-1]]))
    assert len(rows) == len(out) == 9
    for i, row in enumerate(rows):
        assert _gap(row, ref[len(prompt) - 1 + i]) < TOL, i
    st = eng.stats()
    assert st["prefill_pad_tokens"] == 32 - 21
    assert sum(r["pad_tokens"] for r in eng._ticks) == 32 - 21


def test_three_concurrent_greedy_requests_each_equal_it_served_alone(
        granite, monkeypatch):
    cfg, model, params = granite
    prompts = [_tokens(cfg, n, seed=n) for n in (9, 21, 40)]
    together, _, eng = _recorded_serve(model, params, prompts, 7,
                                       monkeypatch)
    for prompt, out in zip(prompts, together):
        alone, _, _ = _recorded_serve(model, params, [prompt], 7,
                                      monkeypatch)
        assert out == alone[0]
    assert eng.stats()["prefill_pad_tokens"] == (16 - 9) + (32 - 21) \
        + (48 - 40)


def test_slot_write_and_restore_cover_both_kinds_of_state(granite):
    """``_write_slot`` fills a slot's SSM, conv and KV rows from a prefill
    and leaves the other slots alone; ``_restore_slot`` puts back all three
    from ``_slot_rows``; ``state_bytes`` counts each kind per slot."""
    from repro.serve.engine import _path_str, _slot_axis

    cfg, model, params = granite
    eng = Engine(model, params, EngineConfig(**ENGINE))
    _, cache = model.prefill(params, {"tokens": jnp.asarray(
        _tokens(cfg, 20))[None]}, max_len=ENGINE["max_len"])
    before = jax.tree_util.tree_map(np.asarray, eng.cache["layers"])
    rows = eng._slot_rows(1)
    eng._rec = {"phases": {}}
    eng._write_slot(1, cache)
    assert "write_slot" in eng._rec["phases"]
    kinds = set()
    for (path, pool), new in zip(
            jax.tree_util.tree_flatten_with_path(eng.cache["layers"])[0],
            jax.tree_util.tree_leaves(cache["layers"])):
        ax = _slot_axis(_path_str(path), pool.ndim)
        kinds.add("ssm" if "ssm" in _path_str(path) else
                  "conv" if "conv" in _path_str(path) else "kv")
        pool = np.moveaxis(np.asarray(pool), ax, 0)
        assert np.array_equal(pool[1], np.moveaxis(np.asarray(new), ax, 0)[0])
        assert not pool[0].any() and not pool[2].any()
    assert kinds == {"ssm", "conv", "kv"}
    eng._restore_slot(1, rows)
    for a, b in zip(jax.tree_util.tree_leaves(eng.cache["layers"]),
                    jax.tree_util.tree_leaves(before)):
        assert np.array_equal(np.asarray(a), b)
    sb = eng.state_bytes
    hd = cfg.d_model * cfg.ssm_expand
    assert sb["ssm"] == 4 * (hd // cfg.ssm_headdim) * cfg.ssm_headdim \
        * cfg.ssm_state * 4
    assert sb["conv"] == 4 * (cfg.ssm_conv - 1) * (hd + 2 * cfg.ssm_state) * 4
    assert sb["kv"] == 2 * 2 * ENGINE["max_len"] * cfg.n_kv_heads \
        * cfg.head_dim * 4


@pytest.mark.parametrize("step", ["decode", "chunk"])
def test_the_programs_hold_one_period_whatever_the_depth(granite, step):
    """The pattern scans whole periods: a model of four periods lowers to
    a program the size of one of two periods (within a few lines of loop
    bookkeeping), not to twice it."""
    cfg, _, _ = granite

    def hlo_lines(periods):
        c = dataclasses.replace(cfg, n_layers=3 * periods,
                                layer_types=cfg.layer_types[:3] * periods)
        m = build_model(c)
        params = jax.eval_shape(m.init, jax.random.PRNGKey(0))
        if step == "decode":
            cache = jax.eval_shape(lambda: m.init_cache(2, 32))
            args = (params, jax.ShapeDtypeStruct((2, 1), jnp.int32), cache)
            fn = m.decode_step
        else:
            cache = jax.eval_shape(lambda: m.init_cache(1, 32))
            args = (params, jax.ShapeDtypeStruct((1, 16), jnp.int32), cache,
                    jax.ShapeDtypeStruct((), jnp.int32))
            fn = m.prefill_chunk
        return len(jax.jit(fn).lower(*args).as_text().splitlines())

    two, four = hlo_lines(2), hlo_lines(4)
    assert abs(four - two) < 0.02 * two, (two, four)
