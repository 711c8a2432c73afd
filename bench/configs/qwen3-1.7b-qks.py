"""qwen3-1.7b in this repository's spiking form (``spiking=True``,
``attention_kind="qk_spiking"``): the builder, the plain reference and the
work counts. Sizes come from ``qwen3-1.7b-qks.json`` beside this file.

Layer equations the reference follows (T=1 LIF, threshold ``v_th``;
H(z) = 1 if z >= 0 else 0). The configuration stores parameters in float32
and computes in bfloat16: every matmul takes both operands in bfloat16 and
sums the products in float32:

    x    = bf16(E[token])                                  embedding
    per layer:
      n  = rms(x; ln1)                                     bf16 out
      q  = H(n @ Wq - v_th)        [H*dh]
      k  = H(n @ Wk - v_th)        [Hkv*dh]
      a_h = H(sum_d q[h, d] - v_th)                        QK token mask
      s[h] = a_h * k[h // (H/Hkv)]                         QKTA (token-local)
      x  = x + bf16(s @ Wo)
      m  = rms(x; ln2)
      g, u = bf16(m @ Wg), bf16(m @ Wu)
      x  = x + bf16((H(g - v_th) * u) @ Wd)
    logits = rms(x; final) @ E^T                          float32 out

QKTA's mask of a token depends on that token alone and the spiking form has
no RoPE and no KV, so the logits at a position are a function of the token
at that position: the reference runs rows of tokens, not sequences. ``Wv``
is allocated by the model and never read in this form.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


# ------------------------------------------------------------------ sizes
def dims(sizes: dict) -> dict:
    return dict(L=sizes["num_hidden_layers"], D=sizes["hidden_size"],
                H=sizes["num_attention_heads"],
                Hkv=sizes["num_key_value_heads"], dh=sizes["head_dim"],
                F=sizes["intermediate_size"], V=sizes["vocab_size"],
                eps=float(sizes["rms_norm_eps"]), vth=float(sizes["lif_v_th"]))


def flops_per_token(sizes: dict) -> float:
    """Model FLOPs of one token through the spiking form: the Q, K and O
    projections, the three FFN matmuls and the tied readout (Wv unused)."""
    d = dims(sizes)
    per_layer = (d["D"] * d["H"] * d["dh"] + d["D"] * d["Hkv"] * d["dh"]
                 + d["H"] * d["dh"] * d["D"] + 3 * d["D"] * d["F"])
    return 2.0 * (d["L"] * per_layer + d["D"] * d["V"])


def event_kernel_calls(sizes: dict, rows: int) -> list:
    """(flops, bytes) of each event-kernel call one forward of ``rows``
    tokens makes under ``fused_packed``, counted at the op's entry in
    ``repro.ops``: operands read once, results written once, at the dtypes
    the policy hands the op (bf16 residual rows, float32 weights, spike
    maps packed 32 to a word). Per layer: ``dense_lif`` for Q, the masked
    ``dense_lif`` for K (grouped weights as given, Q map as the mask) and
    ``matmul`` of the masked map with Wo (float32 out)."""
    d = dims(sizes)
    m, dm, hq, hk = rows, d["D"], d["H"] * d["dh"], d["Hkv"] * d["dh"]
    bits = lambda n: m * n / 8.0  # noqa: E731 - a packed [rows, n] map
    q = (2.0 * m * dm * hq, m * dm * 2 + dm * hq * 4 + bits(hq))
    k = (2.0 * m * dm * hk, m * dm * 2 + dm * hk * 4 + bits(hq) + bits(hq))
    o = (2.0 * m * hq * dm, bits(hq) + hq * dm * 4 + m * dm * 4)
    return [q, k, o] * d["L"]


# ---------------------------------------------------------------- builder
def model_config(sizes: dict):
    """The repo's ModelConfig for these sizes, in the spiking form."""
    from repro.configs import get_config

    dt = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    return get_config(
        "qwen3-1.7b", n_layers=sizes["num_hidden_layers"],
        d_model=sizes["hidden_size"], n_heads=sizes["num_attention_heads"],
        n_kv_heads=sizes["num_key_value_heads"], head_dim=sizes["head_dim"],
        d_ff=sizes["intermediate_size"], vocab_size=sizes["vocab_size"],
        tie_embeddings=sizes["tie_word_embeddings"],
        rms_eps=float(sizes["rms_norm_eps"]), spiking=sizes["spiking"],
        attention_kind=sizes["attention_kind"],
        dtype=dt[sizes["compute_dtype"]], param_dtype=dt[sizes["param_dtype"]])


def _init_leaf(name: str, sd, key, d_model: int):
    if name.endswith("scale"):
        return jnp.ones(sd.shape, sd.dtype)
    std = d_model ** -0.5 if name.endswith("emb") else sd.shape[-2] ** -0.5
    w = jax.random.truncated_normal(key, -2.0, 2.0, sd.shape, jnp.float32)
    return (w * std).astype(sd.dtype)


def make_weights(model, key, device=None) -> dict:
    """All parameters in the model's layout, in one jitted call on the
    device, from ``key``."""
    from bench.common import path_name

    shapes = jax.eval_shape(model.init, key)
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    d_model = model.cfg.d_model

    def gen(k):
        keys = jax.random.split(k, len(flat))
        return jax.tree_util.tree_unflatten(treedef, [
            _init_leaf(path_name(p), sd, kk, d_model)
            for (p, sd), kk in zip(flat, keys)])

    out = None
    if device is not None:
        out = jax.sharding.SingleDeviceSharding(device)
    return jax.jit(gen, out_shardings=out)(key)


def build(sizes: dict, key, device=None):
    """(model, params): the repo's LM for these sizes and the weights."""
    from repro.configs import build_model

    model = build_model(model_config(sizes))
    return model, make_weights(model, key, device)


def engine_config(sizes: dict):
    from repro.serve import EngineConfig

    return EngineConfig(**sizes["engine"])


# -------------------------------------------------------------- reference
def _mm(a, b, lower: bool):
    """a @ b in the compute dtype: both operands in bfloat16, products
    summed in float32. ``lower`` first rounds both operands one precision
    step below, to float8_e4m3fn (exact in bfloat16): the control."""
    def rnd(x):
        if lower:
            x = x.astype(jnp.float8_e4m3fn)
        return x.astype(jnp.bfloat16)
    return jnp.matmul(rnd(a), rnd(b), preferred_element_type=jnp.float32)


def _rms(x, scale, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(jnp.bfloat16)


@functools.partial(jax.jit, static_argnames=("d", "lower"))
def _ref_logits(params, tokens, d: tuple, lower: bool):
    d = dict(d)
    bf = jnp.bfloat16
    emb = params["embed"]["emb"]
    x = jnp.take(emb, tokens, axis=0).astype(bf)
    n = tokens.shape[0]

    def layer(x, p):
        a = p["attn"]
        xn = _rms(x, p["ln1"]["scale"], d["eps"])
        q = (_mm(xn, a["wq"]["w"], lower) >= d["vth"]).astype(jnp.float32)
        k = (_mm(xn, a["wk"]["w"], lower) >= d["vth"]).astype(jnp.float32)
        mask = (q.reshape(n, d["H"], d["dh"]).sum(-1) >= d["vth"])
        kx = jnp.repeat(k.reshape(n, d["Hkv"], d["dh"]),
                        d["H"] // d["Hkv"], axis=1)
        s = (mask[..., None] * kx).reshape(n, d["H"] * d["dh"])
        x = x + _mm(s, a["wo"]["w"], lower).astype(bf)
        f = p["mlp"]
        m = _rms(x, p["ln2"]["scale"], d["eps"])
        g = _mm(m, f["gate"]["w"], lower).astype(bf)
        u = _mm(m, f["up"]["w"], lower).astype(bf)
        h = jnp.where(g >= d["vth"], u, jnp.zeros_like(u))
        x = x + _mm(h, f["down"]["w"], lower).astype(bf)
        return x, None

    x, _ = jax.lax.scan(layer, x, params["blocks"])
    xn = _rms(x, params["final_norm"]["scale"], d["eps"])
    return _mm(xn, emb.T, lower)


@functools.partial(jax.jit, static_argnames=("d", "lower"))
def _gaps(params, inputs, served, d: tuple, lower: bool):
    """Per row: how far the reference puts the served token below its best,
    and (with ``lower``) how far it puts the control's first token."""
    ref = _ref_logits(params, inputs, d, False)
    best = ref.max(axis=-1)
    gap = best - jnp.take_along_axis(ref, served[:, None], axis=-1)[:, 0]
    if not lower:
        return gap, jnp.zeros_like(gap)
    ctl = jnp.argmax(_ref_logits(params, inputs, d, True), axis=-1)
    gap_c = best - jnp.take_along_axis(ref, ctl[:, None], axis=-1)[:, 0]
    return gap, gap_c


def served_gaps(params, sizes: dict, inputs: np.ndarray, served: np.ndarray,
                control: bool = False, block: int = 128):
    """For each (input token, served next token) pair: the reference's best
    logit minus its logit of the served token; with ``control``, also the
    same gap for the token the lower-precision control puts first."""
    d = tuple(sorted(dims(sizes).items()))
    n = len(inputs)
    pad = -(-n // block) * block
    inp = np.zeros(pad, np.int32)
    srv = np.zeros(pad, np.int32)
    inp[:n], srv[:n] = inputs, served
    gaps, gaps_c = [], []
    for i in range(0, pad, block):
        g, gc = _gaps(params, jnp.asarray(inp[i:i + block]),
                      jnp.asarray(srv[i:i + block]), d, control)
        gaps.append(np.asarray(g))
        gaps_c.append(np.asarray(gc))
    return np.concatenate(gaps)[:n], np.concatenate(gaps_c)[:n]
