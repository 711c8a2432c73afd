"""The configurations' work counts: against hand arithmetic for one layer,
and against XLA's own count of the plain reference at a tiny size."""
import json

import jax
import jax.numpy as jnp
import pytest

from bench import run as R

# one layer: XLA's cost analysis counts a scanned layer's body once
QWEN_TINY = dict(num_hidden_layers=1, hidden_size=256, num_attention_heads=4,
                 num_key_value_heads=2, head_dim=64, intermediate_size=512,
                 vocab_size=1024)


def _flops(fn, *args) -> float:
    cost = jax.jit(fn).lower(*args).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    return float(cost["flops"])


def test_qwen_flops_per_token_by_hand():
    mod, sizes = R.config("qwen3-1.7b-qks")
    d, f, v = 2048, 6144, 151936
    layer = d * 16 * 128 + d * 8 * 128 + 16 * 128 * d + 3 * d * f
    assert mod.flops_per_token(sizes) == 2.0 * (28 * layer + d * v)
    # the event kernels of one layer at 64 rows: Q, masked K, O
    q, k, o = mod.event_kernel_calls(sizes, 64)[:3]
    assert q == (2.0 * 64 * d * 2048, 64 * d * 2 + d * 2048 * 4 + 64 * 2048 / 8)
    assert k == (2.0 * 64 * d * 1024,
                 64 * d * 2 + d * 1024 * 4 + 2 * 64 * 2048 / 8)
    assert o == (2.0 * 64 * 2048 * d, 64 * 2048 / 8 + 2048 * d * 4 + 64 * d * 4)
    assert len(mod.event_kernel_calls(sizes, 64)) == 3 * 28


def test_qwen_count_matches_xla_on_the_reference():
    mod, sizes = R.config("qwen3-1.7b-qks")
    sizes = dict(sizes, **QWEN_TINY)
    from repro.configs import build_model

    m = build_model(mod.model_config(sizes))
    params = jax.eval_shape(m.init, jax.random.PRNGKey(0))
    n = 64
    toks = jax.ShapeDtypeStruct((n,), jnp.int32)
    d = tuple(sorted(mod.dims(sizes).items()))
    xla = _flops(lambda p, t: mod._ref_logits(p, t, d, False), params, toks)
    counted = n * mod.flops_per_token(sizes)
    # XLA also counts the norms, thresholds and the mask; the matmuls are
    # what the model count holds, and they dominate
    assert counted <= xla <= 1.1 * counted


def test_vgg_counts_by_hand():
    mod, sizes = R.config("vgg11-cifar")
    convs = [(32, 3, 64), (16, 64, 128), (8, 128, 256), (8, 256, 256),
             (4, 256, 512), (4, 512, 512), (2, 512, 512), (2, 512, 512)]
    # SAME padding: (3h - 2)^2 taps land inside an h x h map
    assert [mod.conv_taps(h) for h in (32, 2)] == [94 * 94, 16]
    fwd = sum(2.0 * (3 * h - 2) ** 2 * a * b for h, a, b in convs)
    assert mod.deploy_flops_per_image(sizes) == fwd + 2.0 * 512 * 10
    assert fwd == pytest.approx(228.64e6, rel=1e-4)
    calls = mod.deploy_event_calls(sizes, 1024)
    assert len(calls) == 7
    m, k, n = 1024 * 16 * 16, 9 * 64, 128
    assert calls[0] == (2.0 * m * k * n, m * k / 8 + k * n * 4 + n * 4 + m * n / 8)


def test_vgg_deploy_count_matches_xla_on_the_reference():
    mod, sizes = R.config("vgg11-cifar")
    sizes = dict(sizes, width_mult=0.25)
    calib = mod.images(sizes, jax.random.PRNGKey(1), 8)
    art = mod.make_artifact(sizes, jax.random.PRNGKey(2), calib)
    x = jax.ShapeDtypeStruct((16, 32, 32, 3), jnp.float32)
    sz = mod._freeze(sizes)
    xla = _flops(lambda a, x: mod._deploy_ref(a, x, sz, False), art, x)
    counted = 16 * mod.deploy_flops_per_image(sizes)
    assert counted <= xla <= 1.1 * counted


def test_benchmark_metrics_have_their_modules():
    spec = R.benchmark_spec()
    for m in spec["per_layer"]:
        mod = R.metric(m["name"])
        assert (mod.LAYER, mod.SOURCE, mod.MOVES, mod.UNIT) == \
            (m["layer"], m["source"], m["moves"], m["unit"]), m["name"]
    for c in spec["configs"]:
        with open(R.ROOT / c["file"]) as f:
            assert json.load(f)["reduced"] == c["reduced"]
