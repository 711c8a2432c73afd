"""Serving weights: the FFN weights cast to the compute dtype once, when the
Engine is built.

A config that stores float32 parameters and computes in bfloat16 reads each
FFN weight through ``.astype(x.dtype)``; inside the layer scan the compiler
hoists that cast out of the loop and converts the whole weight stack on every
call. ``LM.serving_params`` hands the served programs those weights already
in the compute dtype. The invariants:

  * the logits of ``decode_step`` and ``prefill_chunk`` on the serving tree
    equal those on the stored tree exactly, for every family with an FFN;
  * an Engine serves the same greedy tokens with the cast as without it, and
    ``stats()["weights_cast"]`` counts the leaves it cast;
  * the served programs' jaxprs hold no float32 -> bfloat16 convert of an
    FFN weight's shape once the tree is cast (three per scan body before);
  * where there is nothing to cast, the same leaf objects come back.
"""
import jax
import jax.extend.core as jex
import jax.numpy as jnp
import numpy as np
import pytest

from repro import ops
from repro.serve import Engine, EngineConfig

BF16 = dict(dtype=jnp.bfloat16, param_dtype=jnp.float32)
SPIKING = dict(spiking=True, attention_kind="qk_spiking")
# (arch, model overrides): float32 parameters, bfloat16 compute
CASES = {
    "spiking_reference": ("qwen3-1.7b",
                          dict(BF16, policy=ops.REFERENCE, **SPIKING)),
    "spiking_fused_packed": ("qwen3-1.7b",
                             dict(BF16, policy=ops.FUSED_PACKED, **SPIKING)),
    "softmax": ("qwen3-1.7b", BF16),
    "moe": ("olmoe-1b-7b", BF16),
    "hybrid": ("zamba2-7b", BF16),
    "pattern": ("granite-4.0-h-micro", BF16),
}
# the cases the scan-body count below is stated for: one dense scan
DENSE = ("spiking_reference", "spiking_fused_packed", "softmax")


def _leaves(tree):
    return jax.tree_util.tree_leaves(tree)


def _tokens(cfg, shape, seed=3):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(1, cfg.vocab_size, shape), jnp.int32)


def _cache(model, batch=2, max_len=32):
    cache = model.init_cache(batch, max_len)
    cache["len"] = jnp.zeros((), jnp.int32)
    return cache


def _chunk_then_decode(model, params):
    """Logits of one 16-token chunk, then of one decode on its cache."""
    cfg = model.cfg
    chunk = jax.jit(model.prefill_chunk)
    decode = jax.jit(model.decode_step)
    lc, cache = chunk(params, _tokens(cfg, (2, 16)), _cache(model))
    ld, _ = decode(params, _tokens(cfg, (2, 1), seed=4), cache)
    return np.asarray(lc), np.asarray(ld)


@pytest.mark.parametrize("case", CASES)
def test_served_logits_are_bit_identical_on_the_serving_tree(lm_zoo, case):
    arch, overrides = CASES[case]
    _, model, params = lm_zoo(arch, **overrides)
    dtypes = [a.dtype for a in _leaves(params)]
    served = model.serving_params(params)
    # the caller's tree is left as it was
    assert [a.dtype for a in _leaves(params)] == dtypes
    cast = [b for a, b in zip(_leaves(params), _leaves(served)) if a is not b]
    assert cast and all(b.dtype == jnp.bfloat16 for b in cast)
    for before, after in zip(_chunk_then_decode(model, params),
                             _chunk_then_decode(model, served)):
        np.testing.assert_array_equal(after, before)


@pytest.mark.parametrize("case", ["spiking_fused_packed", "softmax", "moe",
                                  "pattern"])
def test_engine_serves_the_same_tokens_and_counts_the_cast(lm_zoo, case,
                                                           monkeypatch):
    arch, overrides = CASES[case]
    cfg, model, params = lm_zoo(arch, **overrides)
    ecfg = dict(max_slots=3, max_len=64, prefill_pad=16, prefill_chunk=16)
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, cfg.vocab_size, int(n)) for n in (5, 11, 19)]

    def serve():
        eng = Engine(model, params, EngineConfig(**ecfg))
        uids = [eng.submit(p, max_new=5) for p in prompts]
        fin = {r.uid: r for r in eng.run_until_drained()}
        return [fin[u].out for u in uids], eng.stats()["weights_cast"]

    tokens, counted = serve()
    with monkeypatch.context() as m:
        m.setattr(type(model), "serving_params", lambda self, p: p)
        before, uncast = serve()
    assert tokens == before
    assert uncast == {"leaves": 0, "bytes_before": 0, "bytes_after": 0}
    assert counted["leaves"] > 0
    assert counted["bytes_after"] * 2 == counted["bytes_before"]
    if case in DENSE:
        # the layer-stacked gate, up and down weights
        stacked = params["blocks"]["mlp"]
        assert counted["leaves"] == 3
        assert counted["bytes_before"] == sum(
            stacked[k]["w"].nbytes for k in ("gate", "up", "down"))


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for j in (v if isinstance(v, (tuple, list)) else (v,)):
            if isinstance(j, jex.ClosedJaxpr):
                yield j.jaxpr
            elif isinstance(j, jex.Jaxpr):
                yield j


def _ffn_casts(jaxpr, shapes) -> int:
    """float32 -> bfloat16 converts of an operand shaped like an FFN weight,
    in ``jaxpr`` and every jaxpr inside it."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "convert_element_type":
            src = eqn.invars[0].aval
            if (src.dtype == jnp.float32
                    and eqn.params["new_dtype"] == jnp.bfloat16
                    and tuple(src.shape) in shapes):
                n += 1
        for sub in _sub_jaxprs(eqn):
            n += _ffn_casts(sub, shapes)
    return n


@pytest.mark.parametrize("step", ["decode_step", "prefill_chunk"])
@pytest.mark.parametrize("case", DENSE)
def test_served_programs_cast_no_ffn_weight(lm_zoo, case, step):
    arch, overrides = CASES[case]
    cfg, model, params = lm_zoo(arch, **overrides)
    d, f = cfg.d_model, cfg.d_ff
    shapes = {(d, f), (f, d)}
    width = 1 if step == "decode_step" else 16
    args = (_tokens(cfg, (2, width)), _cache(model))

    def casts(tree):
        return _ffn_casts(jax.make_jaxpr(getattr(model, step))(
            tree, *args).jaxpr, shapes)

    assert casts(params) == 3         # gate (d, f), up (d, f), down (f, d)
    assert casts(model.serving_params(params)) == 0


@pytest.mark.parametrize("arch,overrides", [
    ("granite-4.0-h-micro", dict(dtype=jnp.bfloat16,
                                 param_dtype=jnp.bfloat16)),
    ("qwen3-1.7b", dict(SPIKING)),     # float32 parameters and compute
])
def test_nothing_to_cast_gives_back_the_same_leaves(lm_zoo, arch, overrides):
    _, model, params = lm_zoo(arch, **overrides)
    served = model.serving_params(params)
    assert all(a is b for a, b in zip(_leaves(params), _leaves(served)))
    assert Engine(model, params, EngineConfig(max_slots=2, max_len=32)
                  ).weights_cast == {"leaves": 0, "bytes_before": 0,
                                     "bytes_after": 0}
