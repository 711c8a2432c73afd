"""Plain reference of the layer-pattern hybrid (``ModelConfig.layer_types``,
granitemoehybrid as granite-4.0-h-micro publishes it), for the CPU tests.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision,
one sequence at a time, no cache, no batching, no kernels:

    x = E[token] * embedding_multiplier
    per layer (its type from layer_types, its own weights):
      x = x + residual_multiplier * mixer(rms(x; ln1))
      x = x + residual_multiplier * mlp(rms(x; ln2))
    logits = rms(x; final) @ E^T / logits_scaling

    mamba mixer (Mamba-2): z, xBC, dt = x @ W_in; xBC = silu(causal
      depthwise conv_K(xBC) + b); x, B, C = xBC; dt = softplus(dt + dt_bias);
      per token t, one state update: h = exp(dt*A) h + dt * x (x) B,
      y = h . C + D x, A = -exp(A_log); out = rms(y * silu(z); norm) @ W_out
    attention mixer: GQA, no position code (nope), full causal softmax with
      scale attention_multiplier (1/sqrt(head_dim) where none is given)
    mlp: (silu(x @ Wg) * (x @ Wu)) @ Wd

It reads the parameters in the model's layout (``blocks["mamba"]`` and
``blocks["attn"]``: per run of like layers in a period, one stack shaped
[periods, run length, ...]) and shares no code with the model. Departures from the published model: none
in the equations; the weights are random.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(F32)


def _w(p):
    return p["w"].astype(F32)


def _mamba(p, cfg, x):
    s = x.shape[0]
    d_inner = cfg.ssm_expand * cfg.d_model
    hp, n, g, k = cfg.ssm_headdim, cfg.ssm_state, cfg.ssm_ngroups, cfg.ssm_conv
    h = d_inner // hp
    proj = x @ _w(p["in_proj"])
    z = proj[:, :d_inner]
    xbc = proj[:, d_inner:2 * d_inner + 2 * g * n]
    dt = proj[:, 2 * d_inner + 2 * g * n:]
    cw = p["conv_w"].astype(F32)
    padded = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1]), F32), xbc])
    conv = sum(padded[i:i + s] * cw[i] for i in range(k)) \
        + p["conv_b"].astype(F32)
    xbc = jax.nn.silu(conv)
    xs = xbc[:, :d_inner].reshape(s, h, hp)
    bm = jnp.repeat(xbc[:, d_inner:d_inner + g * n].reshape(s, g, n),
                    h // g, axis=1)
    cm = jnp.repeat(xbc[:, d_inner + g * n:].reshape(s, g, n), h // g, axis=1)
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(F32))
    a = -jnp.exp(p["A_log"].astype(F32))
    dd = p["D"].astype(F32)

    def step(state, inp):
        x_t, b_t, c_t, dt_t = inp
        state = jnp.exp(dt_t * a)[:, None, None] * state \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return state, jnp.einsum("hpn,hn->hp", state, c_t) + dd[:, None] * x_t

    _, y = jax.lax.scan(step, jnp.zeros((h, hp, n), F32), (xs, bm, cm, dt))
    y = y.reshape(s, d_inner) * jax.nn.silu(z)
    return _rms(y, p["norm"]["scale"], cfg.rms_eps) @ _w(p["out_proj"])


def _attention(p, cfg, x):
    assert cfg.position_embedding_type == "nope"
    s = x.shape[0]
    h, hkv = cfg.n_heads, cfg.n_kv_heads
    dh = cfg.head_dim or cfg.d_model // h
    q = (x @ _w(p["wq"])).reshape(s, h, dh)
    k = jnp.repeat((x @ _w(p["wk"])).reshape(s, hkv, dh), h // hkv, axis=1)
    v = jnp.repeat((x @ _w(p["wv"])).reshape(s, hkv, dh), h // hkv, axis=1)
    scale = cfg.attention_multiplier or dh ** -0.5
    scores = jnp.einsum("qhd,khd->hqk", q, k) * scale
    causal = jnp.tril(jnp.ones((s, s), bool))
    w = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,khd->qhd", w, v).reshape(s, h * dh) @ _w(p["wo"])


def _mlp(p, x):
    return (jax.nn.silu(x @ _w(p["gate"])) * (x @ _w(p["up"]))) @ _w(p["down"])


def _layers(params: dict, cfg):
    """(type key, weights) of every layer in order. The model stacks each
    run of like layers of a period as one entry of ``blocks[key]``, shaped
    [periods, run length, ...]."""
    period = cfg.layer_types[:cfg.layer_period]
    runs, seen = [], {"mamba": 0, "attn": 0}
    for i, t in enumerate(period):
        key = "mamba" if t == "mamba" else "attn"
        if i and period[i - 1] == t:
            runs[-1][2] += 1
        else:
            runs.append([key, seen[key], 1])
            seen[key] += 1
    for g in range(cfg.n_layers // len(period)):
        for key, j, n in runs:
            for i in range(n):
                yield key, jax.tree_util.tree_map(
                    lambda a: a[g, i], params["blocks"][key][j])


def logits(params: dict, cfg, tokens) -> np.ndarray:
    """[S, V] float32 logits of one sequence of token ids."""
    with jax.default_matmul_precision("highest"):
        emb = params["embed"]["emb"].astype(F32)
        x = emb[jnp.asarray(tokens)] * cfg.embedding_multiplier
        m = cfg.residual_multiplier
        for key, p in _layers(params, cfg):
            xn = _rms(x, p["ln1"]["scale"], cfg.rms_eps)
            mix = _mamba(p["mamba"], cfg, xn) if key == "mamba" \
                else _attention(p["attn"], cfg, xn)
            x = x + m * mix
            x = x + m * _mlp(p["mlp"], _rms(x, p["ln2"]["scale"], cfg.rms_eps))
        x = _rms(x, params["final_norm"]["scale"], cfg.rms_eps)
        return np.asarray(x @ emb.T / cfg.logits_scaling)
