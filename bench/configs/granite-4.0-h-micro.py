"""granite-4.0-h-micro, a Mamba-2/attention hybrid (granitemoehybrid): the
builder, the plain reference and the work counts. Sizes come from
``granite-4.0-h-micro.json`` beside this file (the published config.json).

Layer equations the reference follows, in float32 at ``highest`` matmul
precision (the program stores parameters and computes in bfloat16, with
float32 SSM state, norm statistics and read-out):

    x = E[token] * embedding_multiplier                       (12)
    per layer, of the type layer_types gives, with its own weights:
      x = x + residual_multiplier * mixer(rms(x; ln1))        (0.22)
      x = x + residual_multiplier * mlp(rms(x; ln2))
    logits = rms(x; final) @ E^T / logits_scaling             (8)

    mamba (Mamba-2, 64 heads of 64, d_state 128, one group, conv 4):
      z, xBC, dt = x @ W_in                                    no bias
      xBC = silu(causal depthwise conv_4(xBC) + b)
      x_h, B, C = xBC;  dt = softplus(dt + dt_bias);  A = -exp(A_log)
      per token t, one state update per head:
        h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T;  y_t = h_t C_t + D x_t
      out = rms(y * silu(z); norm) @ W_out                    gated RMSNorm
    attention (32 query, 8 KV heads of 64, no position code):
      softmax(q k^T * attention_multiplier (1/64), causal) v @ W_o
    mlp: (silu(x @ Wg) * (x @ Wu)) @ Wd                       width 8192

Departures from the published model: the weights are random from the seed
(``LM.init``'s distributions, the embedding's scaled by
``embedding_init_scale``: see the file of sizes); the published checkpoint's fused
``input_linear`` of the MLP is held as separate gate and up matrices, which
is the same map. The reference runs the whole sequence of each sampled
request (prompt and served tokens, teacher-forced) without a cache, one
layer at a time with that layer's weights upcast, so that it fits on the
chip once the engine's slot pool is freed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
SEQ_BLOCK = 512                       # reference sequences padded to this
Q_BLOCK = 512                         # attention query rows per block


# ------------------------------------------------------------------ sizes
def dims(sizes: dict) -> dict:
    d, h = sizes["hidden_size"], sizes["num_attention_heads"]
    d_inner = sizes["mamba_expand"] * d
    assert sizes["mamba_n_heads"] * sizes["mamba_d_head"] == d_inner
    types = tuple(sizes["layer_types"])
    return dict(
        L=sizes["num_hidden_layers"], D=d, H=h,
        Hkv=sizes["num_key_value_heads"], dh=d // h,
        F=sizes["shared_intermediate_size"], V=sizes["vocab_size"],
        eps=float(sizes["rms_norm_eps"]), DI=d_inner,
        HM=sizes["mamba_n_heads"], P=sizes["mamba_d_head"],
        N=sizes["mamba_d_state"], G=sizes["mamba_n_groups"],
        K=sizes["mamba_d_conv"], C=d_inner + 2 * sizes["mamba_n_groups"]
        * sizes["mamba_d_state"],
        LM=types.count("mamba"), LA=types.count("attention"),
        emb_mult=float(sizes["embedding_multiplier"]),
        res_mult=float(sizes["residual_multiplier"]),
        attn_mult=float(sizes["attention_multiplier"]),
        logit_div=float(sizes["logits_scaling"]))


def _matmul_params(d: dict) -> tuple[int, int]:
    """Weights every token multiplies by: (per Mamba layer, per attention
    layer); the tied read-out's V x D is counted apart."""
    mlp = 3 * d["D"] * d["F"]
    mamba = d["D"] * (2 * d["DI"] + 2 * d["G"] * d["N"] + d["HM"]) \
        + d["DI"] * d["D"] + mlp
    attn = d["D"] * (d["H"] + 2 * d["Hkv"]) * d["dh"] \
        + d["H"] * d["dh"] * d["D"] + mlp
    return mamba, attn


def _itemsize(sizes: dict, key: str) -> int:
    return {"bfloat16": 2, "float32": 4}[sizes[key]]


def param_bytes(sizes: dict) -> int:
    """Bytes of every parameter as the program holds them: the parameter
    dtype, and the Mamba layers' A_log, dt_bias and D in float32."""
    d = dims(sizes)
    mamba, attn = _matmul_params(d)
    mamba_other = d["K"] * d["C"] + d["C"] + d["DI"] + 2 * d["D"]
    attn_other = 2 * d["D"]
    n = (d["LM"] * (mamba + mamba_other) + d["LA"] * (attn + attn_other)
         + d["V"] * d["D"] + d["D"])
    return _itemsize(sizes, "param_dtype") * n + 4 * 3 * d["HM"] * d["LM"]


def flops_per_token(sizes: dict) -> float:
    """Matmul FLOPs of one token through the model: every layer's
    projections and MLP and the tied read-out (the SSM update and the
    attention scores, which depend on the context, are left out)."""
    d = dims(sizes)
    mamba, attn = _matmul_params(d)
    return 2.0 * (d["LM"] * mamba + d["LA"] * attn + d["D"] * d["V"])


def decode_work(sizes: dict, slots: int) -> dict:
    """(flops, bytes) of one pool-wide decode call (``jit_decode_step``)
    over ``slots`` slots, by part: the weights (each read once, every
    slot's matmuls), the SSM state and the conv state of every Mamba layer
    read and written for every slot, and the KV rows of every attention
    layer read over ``max_len`` (the program attends over the whole row,
    masked) with its scores. SSM state is float32; conv state and KV rows
    are in the compute dtype."""
    d = dims(sizes)
    act = _itemsize(sizes, "compute_dtype")
    max_len = sizes["engine"]["max_len"]
    ssm = d["LM"] * d["HM"] * d["P"] * d["N"]          # state elements / slot
    conv = d["LM"] * (d["K"] - 1) * d["C"]
    kv = d["LA"] * 2 * max_len * d["Hkv"] * d["dh"]
    attn_flops = d["LA"] * 4.0 * max_len * d["H"] * d["dh"]
    return {
        "weights": (slots * flops_per_token(sizes), float(param_bytes(sizes))),
        "ssm": (slots * 5.0 * ssm, 2.0 * slots * 4 * ssm),
        "conv": (slots * 2.0 * d["LM"] * d["K"] * d["C"],
                 2.0 * slots * act * conv),
        "kv": (slots * attn_flops, slots * act * float(kv))}


# ---------------------------------------------------------------- builder
def model_config(sizes: dict):
    """The repo's ModelConfig for these sizes."""
    from repro.configs import get_config

    d = dims(sizes)
    dt = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    return get_config(
        "granite-4.0-h-micro", n_layers=d["L"], d_model=d["D"],
        n_heads=d["H"], n_kv_heads=d["Hkv"], head_dim=d["dh"], d_ff=d["F"],
        vocab_size=d["V"], tie_embeddings=sizes["tie_word_embeddings"],
        rms_eps=d["eps"], rope_theta=float(sizes["rope_theta"]),
        ssm_state=d["N"], ssm_expand=sizes["mamba_expand"],
        ssm_headdim=d["P"], ssm_conv=d["K"],
        ssm_chunk=sizes["mamba_chunk_size"], ssm_ngroups=d["G"],
        layer_types=tuple(sizes["layer_types"]),
        position_embedding_type=sizes["position_embedding_type"],
        attention_multiplier=d["attn_mult"],
        embedding_multiplier=d["emb_mult"],
        residual_multiplier=d["res_mult"], logits_scaling=d["logit_div"],
        dtype=dt[sizes["compute_dtype"]], param_dtype=dt[sizes["param_dtype"]])


def build(sizes: dict, key, device=None):
    """(model, params): the repo's LM for these sizes and its weights, made
    on the device in one jitted call of ``LM.init``, the embedding scaled
    by ``embedding_init_scale``."""
    from repro.configs import build_model

    model = build_model(model_config(sizes))
    scale = sizes["embedding_init_scale"]

    def init(k):
        p = model.init(k)
        emb = p["embed"]["emb"]
        return dict(p, embed={"emb": (emb * scale).astype(emb.dtype)})

    out = None if device is None else jax.sharding.SingleDeviceSharding(device)
    return model, jax.jit(init, out_shardings=out)(key)


def engine_config(sizes: dict):
    from repro.serve import EngineConfig

    return EngineConfig(**sizes["engine"])


# -------------------------------------------------------------- reference
def _mm(a, b, lower: bool):
    """a @ b in float32; ``lower`` first rounds both operands one precision
    step below the configuration's bfloat16, to float8_e4m3fn: the
    control."""
    if lower:
        a = a.astype(jnp.float8_e4m3fn).astype(F32)
        b = b.astype(jnp.float8_e4m3fn).astype(F32)
    return jnp.matmul(a.astype(F32), b.astype(F32))


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(F32)


def _mlp(p, x, lower):
    g = _mm(x, p["gate"]["w"], lower)
    return _mm(jax.nn.silu(g) * _mm(x, p["up"]["w"], lower),
               p["down"]["w"], lower)


@functools.partial(jax.jit, static_argnames=("d", "lower"))
def _mamba_layer(p, x, d: tuple, lower: bool):
    """One Mamba-2 layer and its MLP over one sequence x [T, D]."""
    d = dict(d)
    t = x.shape[0]
    di, hm, hp, n, g, k = d["DI"], d["HM"], d["P"], d["N"], d["G"], d["K"]
    m = p["mamba"]
    xn = _rms(x, p["ln1"]["scale"], d["eps"])
    proj = _mm(xn, m["in_proj"]["w"], lower)
    z, xbc, dt = proj[:, :di], proj[:, di:di + d["C"]], proj[:, di + d["C"]:]
    cw = m["conv_w"].astype(F32)
    padded = jnp.concatenate([jnp.zeros((k - 1, d["C"]), F32), xbc])
    xbc = jax.nn.silu(sum(padded[i:i + t] * cw[i] for i in range(k))
                      + m["conv_b"].astype(F32))
    xs = xbc[:, :di].reshape(t, hm, hp)
    bm = jnp.repeat(xbc[:, di:di + g * n].reshape(t, g, n), hm // g, axis=1)
    cm = jnp.repeat(xbc[:, di + g * n:].reshape(t, g, n), hm // g, axis=1)
    dt = jax.nn.softplus(dt + m["dt_bias"].astype(F32))
    a = -jnp.exp(m["A_log"].astype(F32))
    dskip = m["D"].astype(F32)

    def step(h, inp):                 # one state update per token
        x_t, b_t, c_t, dt_t = inp
        h = jnp.exp(dt_t * a)[:, None, None] * h \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return h, jnp.einsum("hpn,hn->hp", h, c_t) + dskip[:, None] * x_t

    _, y = jax.lax.scan(step, jnp.zeros((hm, hp, n), F32), (xs, bm, cm, dt))
    y = _rms(y.reshape(t, di) * jax.nn.silu(z), m["norm"]["scale"], d["eps"])
    x = x + d["res_mult"] * _mm(y, m["out_proj"]["w"], lower)
    return x + d["res_mult"] * _mlp(
        p["mlp"], _rms(x, p["ln2"]["scale"], d["eps"]), lower)


@functools.partial(jax.jit, static_argnames=("d", "lower"))
def _attn_layer(p, x, d: tuple, lower: bool):
    """One attention layer and its MLP over one sequence x [T, D]: full
    causal softmax, computed a block of query rows at a time."""
    d = dict(d)
    t = x.shape[0]
    h, hkv, dh = d["H"], d["Hkv"], d["dh"]
    a = p["attn"]
    xn = _rms(x, p["ln1"]["scale"], d["eps"])
    q = _mm(xn, a["wq"]["w"], lower).reshape(t, h, dh)
    k = jnp.repeat(_mm(xn, a["wk"]["w"], lower).reshape(t, hkv, dh),
                   h // hkv, axis=1)
    v = jnp.repeat(_mm(xn, a["wv"]["w"], lower).reshape(t, hkv, dh),
                   h // hkv, axis=1)
    if lower:
        q, k, v = (u.astype(jnp.float8_e4m3fn).astype(F32) for u in (q, k, v))

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK)
        s = jnp.einsum("qhd,khd->hqk", qi, k) * d["attn_mult"]
        rows = i * Q_BLOCK + jnp.arange(Q_BLOCK)[:, None]
        s = jnp.where(jnp.arange(t)[None, :] <= rows, s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)

    out = jax.lax.map(block, jnp.arange(t // Q_BLOCK)).reshape(t, h * dh)
    x = x + d["res_mult"] * _mm(out, a["wo"]["w"], lower)
    return x + d["res_mult"] * _mlp(
        p["mlp"], _rms(x, p["ln2"]["scale"], d["eps"]), lower)


@functools.partial(jax.jit, static_argnames=("d", "lower"))
def _readout(params, x, rows, d: tuple, lower: bool):
    d = dict(d)
    xn = _rms(x[rows], params["final_norm"]["scale"], d["eps"])
    return _mm(xn, params["embed"]["emb"].T, lower) / d["logit_div"]


def _period(types: list) -> list:
    n = len(types)
    p = next(p for p in range(1, n + 1)
             if n % p == 0 and types == types[:p] * (n // p))
    return types[:p]


def _layers(params, sizes: dict):
    """(type key, weights) of every layer in order. The program stacks
    each run of like layers of a period as one entry of ``blocks[key]``,
    shaped [periods, run length, ...]."""
    period = _period(list(sizes["layer_types"]))
    runs, seen = [], {"mamba": 0, "attn": 0}
    for i, t in enumerate(period):
        key = "mamba" if t == "mamba" else "attn"
        if i and period[i - 1] == t:
            runs[-1][2] += 1
        else:
            runs.append([key, seen[key], 1])
            seen[key] += 1
    for g in range(len(sizes["layer_types"]) // len(period)):
        for key, j, n in runs:
            for i in range(n):
                yield key, jax.tree_util.tree_map(
                    lambda a: a[g, i], params["blocks"][key][j])


def reference_logits(params, sizes: dict, tokens: np.ndarray,
                     rows: np.ndarray, lower: bool = False) -> np.ndarray:
    """The reference's logits [len(rows), V] at positions ``rows`` of one
    token sequence, run whole (teacher-forced) without a cache, one layer
    at a time. Sequences are right-padded to ``SEQ_BLOCK`` (causal: pads
    never reach an earlier position) so that few shapes compile."""
    d = dims(sizes)
    dt = tuple(sorted(d.items()))
    t = len(tokens)
    tp = -(-t // SEQ_BLOCK) * SEQ_BLOCK
    toks = np.zeros(tp, np.int32)
    toks[:t] = tokens
    nr = len(rows)
    rp = np.zeros(-(-nr // 256) * 256, np.int32)
    rp[:nr] = rows
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["emb"][jnp.asarray(toks)].astype(F32) \
            * d["emb_mult"]
        for key, p in _layers(params, sizes):
            fn = _mamba_layer if key == "mamba" else _attn_layer
            x = fn(p, x, dt, lower)
        out = _readout(params, x, jnp.asarray(rp), dt, lower)
    return np.asarray(out)[:nr]


def sequence_gaps(params, sizes: dict, seqs: list, control: bool = False):
    """For each (prompt, served tokens) sequence: at every served position,
    the reference's best logit minus its logit of the served token, over
    the whole sequence teacher-forced, in units of the standard deviation
    of the reference's logits at that position (random weights put the
    logits at no fixed scale); with ``control``, also the same gap for the
    token the lower-precision control puts first. Returns both as flat
    arrays over all served positions."""
    gaps, gaps_c = [], []
    for prompt, served in seqs:
        served = np.asarray(served, np.int32)
        toks = np.concatenate([np.asarray(prompt, np.int32), served[:-1]])
        rows = np.arange(len(prompt) - 1, len(toks))
        ref = reference_logits(params, sizes, toks, rows)
        ref = ref / ref.std(-1, keepdims=True)
        best = ref.max(-1)
        gaps.append(best - ref[np.arange(len(served)), served])
        if control:
            ctl = reference_logits(params, sizes, toks, rows, lower=True)
            pick = ctl.argmax(-1)
            gaps_c.append(best - ref[np.arange(len(served)), pick])
        else:
            gaps_c.append(np.zeros(len(served), np.float32))
    if not gaps:
        return np.zeros(0, np.float32), np.zeros(0, np.float32)
    return np.concatenate(gaps), np.concatenate(gaps_c)
