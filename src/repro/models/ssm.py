"""Mamba2 (SSD — state-space duality, arXiv:2405.21060) layer.

Chunked SSD algorithm: within-chunk computation is attention-like (dense
matmuls — MXU-friendly), across chunks a tiny sequential recurrence carries
the [H, P, N] state. Chunk length is ``cfg.ssm_chunk``.

Decode is O(1): a per-layer (conv_state, ssm_state) pair replaces the KV
cache entirely — which is why the ssm/hybrid archs are the ones that run
the long_500k cell.

Sharding: batch on ('pod','data'); the d_inner axis (and thus heads) on
'model'; the recurrent state [B, H, P, N] shards the same way. The
inter-chunk scan is sequential in time but involves no collectives.

Right pads are inert (``n_valid``): at a pad position the step size is 0,
so exp(dt*A) = 1 and the input term dt*x*B is 0, and the conv state is
taken from the last K-1 valid inputs. A right-padded prefill then leaves
the same state as one at the true length, and the serving engine can
prefill SSM and hybrid prompts in fixed-length buckets.

Spiking hook (paper C3): ``spiking`` replaces the SiLU on the conv branch
with a LIF spike, making xBC a binary event stream (the SSM input events).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from .layers import dense_apply, dense_init, maybe_spike, rmsnorm_gated_apply, rmsnorm_init

Array = jax.Array


def ssm_dims(cfg: ModelConfig, d_model: Optional[int] = None):
    d = d_model or cfg.d_model
    d_inner = cfg.ssm_expand * d
    nheads = d_inner // cfg.ssm_headdim
    g, n = cfg.ssm_ngroups, cfg.ssm_state
    conv_dim = d_inner + 2 * g * n
    return d, d_inner, nheads, g, n, conv_dim


def mamba_init(rng: Array, cfg: ModelConfig, d_model: Optional[int] = None) -> dict:
    d, d_inner, h, g, n, conv_dim = ssm_dims(cfg, d_model)
    r1, r2, r3, r4 = jax.random.split(rng, 4)
    d_in_proj = 2 * d_inner + 2 * g * n + h
    dt = jnp.exp(jax.random.uniform(r3, (h,)) * (jnp.log(0.1) - jnp.log(0.001))
                 + jnp.log(0.001))
    dt = jnp.clip(dt, 1e-4, None)
    return {
        "in_proj": dense_init(r1, d, d_in_proj, dtype=cfg.param_dtype),
        "conv_w": (jax.random.normal(r2, (cfg.ssm_conv, conv_dim)) * 0.02
                   ).astype(cfg.param_dtype),
        "conv_b": jnp.zeros((conv_dim,), cfg.param_dtype),
        "A_log": jnp.log(jnp.arange(1, h + 1, dtype=jnp.float32)),
        "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(jnp.float32),  # inv softplus
        "D": jnp.ones((h,), jnp.float32),
        "norm": rmsnorm_init(d_inner, cfg.param_dtype),
        "out_proj": dense_init(r4, d_inner, d, dtype=cfg.param_dtype),
    }


def _split_proj(zxbcdt: Array, cfg: ModelConfig, d_inner: int, g: int, n: int):
    z, xbc, dt = jnp.split(
        zxbcdt, [d_inner, 2 * d_inner + 2 * g * n], axis=-1)
    return z, xbc, dt


def _causal_conv(xbc: Array, w: Array, b: Array, spiking: bool, cfg,
                 tail: Optional[Array] = None) -> Array:
    """Depthwise causal conv over time. xbc: [B,S,C]; w: [K,C].

    ``tail`` [B, K-1, C] supplies the previous chunk's last K-1 pre-conv
    inputs (chunked prefill); None means sequence start (zero history)."""
    k = w.shape[0]
    if tail is None:
        pad = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    else:
        pad = jnp.concatenate([tail.astype(xbc.dtype), xbc], axis=1)
    # depthwise conv as K shifted adds — K is tiny (4); avoids conv lowering
    out = sum(pad[:, i:i + xbc.shape[1], :] * w[i][None, None, :].astype(xbc.dtype)
              for i in range(k))
    out = out + b.astype(out.dtype)
    return maybe_spike(out, True, cfg.lif) if spiking else jax.nn.silu(out)


def _ssd_chunked(xs: Array, dt: Array, A: Array, Bm: Array, Cm: Array,
                 chunk: int, init_state: Optional[Array] = None
                 ) -> tuple[Array, Array]:
    """Chunked SSD: ONE scan over chunks carrying the [B,H,P,N] state.

    xs: [B,S,H,P]; dt: [B,S,H] (post-softplus); A: [H] (negative);
    Bm/Cm: [B,S,G,N]. Returns (y [B,S,H,P], final_state [B,H,P,N]).

    The intra-chunk decay matrix L [B,q,q,H] lives only inside one scan step
    (and the body is checkpointed), so peak memory is O(S/chunk) smaller than
    the fully-vectorized formulation — the same working-set argument as the
    paper's elastic-FIFO streaming: stream blocks, keep one in flight.
    """
    b, s, h, p = xs.shape
    g, n = Bm.shape[-2:]
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk
    hg = h // g                                       # heads per B/C group
    mask = jnp.tril(jnp.ones((chunk, chunk), bool))

    # chunk-major inputs for the scan: [nc, b, chunk, ...]
    def cm(x):
        return jnp.moveaxis(x.reshape(b, nc, chunk, *x.shape[2:]), 1, 0)

    xs_c, dt_c, B_c, C_c = cm(xs), cm(dt), cm(Bm), cm(Cm)

    def body(state, inp):
        x_i, dt_i, B_i, C_i = inp                    # [b,q,h,p] [b,q,h] [b,q,g,n]
        dA = dt_i * A[None, None, :]                 # [b,q,h] (negative)
        dA_cs = jnp.cumsum(dA, axis=1)
        # intra-chunk: L[i,j] = exp(dA_cs[i]-dA_cs[j]) for i>=j (masked pre-exp)
        li = dA_cs[:, :, None, :] - dA_cs[:, None, :, :]      # [b,i,j,h]
        L = jnp.exp(jnp.where(mask[None, :, :, None], li, -jnp.inf))
        scores = jnp.einsum("bign,bjgn->bijg", C_i.astype(jnp.float32),
                            B_i.astype(jnp.float32))          # [b,i,j,g]
        dx = dt_i[..., None] * x_i.astype(jnp.float32)        # [b,q,h,p]
        # group heads: h = g*hg — contract without materializing repeat()
        Lg = L.reshape(b, chunk, chunk, g, hg)
        dxg = dx.reshape(b, chunk, g, hg, p)
        y_intra = jnp.einsum("bijgr,bijg,bjgrp->bigrp", Lg, scores, dxg)
        # inter-chunk: contribution of the incoming state
        decay_in = jnp.exp(dA_cs)                             # [b,q,h]
        stg = state.reshape(b, g, hg, p, n)
        y_inter = jnp.einsum("bqgn,bghpn->bqghp",
                             C_i.astype(jnp.float32), stg)
        y_inter = y_inter * decay_in.reshape(b, chunk, g, hg)[..., None]
        # state update
        seg_end = dA_cs[:, -1:, :]                            # [b,1,h]
        decay_to_end = jnp.exp(seg_end - dA_cs)               # [b,q,h]
        wdx = (dx * decay_to_end[..., None]).reshape(b, chunk, g, hg, p)
        new_state = jnp.einsum("bqgn,bqghp->bghpn",
                               B_i.astype(jnp.float32), wdx)
        new_state = new_state.reshape(b, h, p, n)
        state = state * jnp.exp(seg_end[:, 0, :])[..., None, None] + new_state
        y = (y_intra + y_inter.reshape(b, chunk, g, hg, p)).reshape(
            b, chunk, h, p)
        return state, y

    s0 = (jnp.zeros((b, h, p, n), jnp.float32) if init_state is None
          else init_state.astype(jnp.float32))
    final, y_c = jax.lax.scan(jax.checkpoint(body), s0,
                              (xs_c, dt_c, B_c, C_c))
    y = jnp.moveaxis(y_c, 0, 1).reshape(b, s, h, p)
    return y, final


def mamba_apply(p: dict, cfg: ModelConfig, x: Array,
                d_model: Optional[int] = None,
                init_state: Optional[dict] = None,
                return_state: bool = False,
                n_valid: Optional[Array] = None):
    """Full-sequence forward. x: [B,S,D] -> y: [B,S,D] (+ state dict).

    ``n_valid`` (scalar or [B]): positions from ``n_valid`` on are right
    pads, which leave the state as they found it; their outputs are
    undefined. None: every position is valid."""
    d, d_inner, h, g, n, conv_dim = ssm_dims(cfg, d_model)
    b, s, _ = x.shape
    zxbcdt = dense_apply(p["in_proj"], x)
    z, xbc_raw, dt_raw = _split_proj(zxbcdt, cfg, d_inner, g, n)
    conv_tail = None if init_state is None else init_state["conv"]
    xbc = _causal_conv(xbc_raw, p["conv_w"], p["conv_b"], cfg.spiking, cfg,
                       tail=conv_tail)
    xs, Bm, Cm = jnp.split(xbc, [d_inner, d_inner + g * n], axis=-1)
    xs = xs.reshape(b, s, h, cfg.ssm_headdim)
    Bm = Bm.reshape(b, s, g, n)
    Cm = Cm.reshape(b, s, g, n)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])
    if n_valid is not None:
        lens = jnp.broadcast_to(jnp.reshape(n_valid, (-1,)), (b,))
        pad = jnp.arange(s)[None, :] >= lens[:, None]           # [B, S]
        dt = jnp.where(pad[..., None], 0.0, dt)
    A = -jnp.exp(p["A_log"])

    chunk = min(cfg.ssm_chunk, s)
    s0 = (s // chunk) * chunk
    state0 = None if init_state is None else init_state["ssm"]
    if s0:
        y0, st = _ssd_chunked(xs[:, :s0], dt[:, :s0], A, Bm[:, :s0],
                              Cm[:, :s0], chunk, state0)
    else:
        y0, st = None, state0
    if s0 < s:                      # remainder chunk (exact, no padding)
        y1, st = _ssd_chunked(xs[:, s0:], dt[:, s0:], A, Bm[:, s0:],
                              Cm[:, s0:], s - s0, st)
        y = y1 if y0 is None else jnp.concatenate([y0, y1], axis=1)
    else:
        y = y0
    final = st
    y = y + p["D"][None, None, :, None] * xs.astype(jnp.float32)
    y = y.reshape(b, s, d_inner).astype(x.dtype)
    y = rmsnorm_gated_apply(p["norm"], y, z, cfg.rms_eps)
    out = dense_apply(p["out_proj"], y)
    if not return_state:
        return out
    # conv state = last K-1 valid PRE-conv inputs; history (the incoming
    # conv tail or zeros at sequence start) covers chunks shorter than K-1
    k1 = cfg.ssm_conv - 1
    hist = (jnp.zeros((b, k1, xbc_raw.shape[-1]), x.dtype)
            if conv_tail is None else conv_tail.astype(x.dtype))
    seq = jnp.concatenate([hist, xbc_raw], axis=1)            # [B, K-1+S, C]
    if n_valid is None:
        tail = seq[:, -k1:, :]
    else:
        rows = lens[:, None] + jnp.arange(k1)[None, :]        # [B, K-1]
        tail = jnp.take_along_axis(seq, rows[..., None], axis=1)
    return out, {"ssm": final.astype(jnp.float32), "conv": tail}


def mamba_init_state(cfg: ModelConfig, batch: int,
                     d_model: Optional[int] = None, dtype=jnp.float32) -> dict:
    d, d_inner, h, g, n, conv_dim = ssm_dims(cfg, d_model)
    return {
        "ssm": jnp.zeros((batch, h, cfg.ssm_headdim, n), jnp.float32),
        "conv": jnp.zeros((batch, cfg.ssm_conv - 1, conv_dim), dtype),
    }


def mamba_decode_step(p: dict, cfg: ModelConfig, x: Array, state: dict,
                      d_model: Optional[int] = None) -> tuple[Array, dict]:
    """One-token step. x: [B,1,D]; state: {'ssm':[B,H,P,N], 'conv':[B,K-1,C]}."""
    d, d_inner, h, g, n, conv_dim = ssm_dims(cfg, d_model)
    b = x.shape[0]
    zxbcdt = dense_apply(p["in_proj"], x[:, 0, :])           # [B, dproj]
    z, xbc_new, dt_raw = _split_proj(zxbcdt, cfg, d_inner, g, n)

    # conv state update: window = [conv_state, xbc_new]
    window = jnp.concatenate([state["conv"].astype(x.dtype),
                              xbc_new[:, None, :]], axis=1)  # [B,K,C]
    conv_out = jnp.einsum("bkc,kc->bc", window, p["conv_w"].astype(x.dtype))
    conv_out = conv_out + p["conv_b"].astype(conv_out.dtype)
    xbc = (maybe_spike(conv_out, True, cfg.lif) if cfg.spiking
           else jax.nn.silu(conv_out))
    new_conv = window[:, 1:, :]

    xs, Bm, Cm = jnp.split(xbc, [d_inner, d_inner + g * n], axis=-1)
    xs = xs.reshape(b, h, cfg.ssm_headdim).astype(jnp.float32)
    Bm = Bm.reshape(b, g, n).astype(jnp.float32)
    Cm = Cm.reshape(b, g, n).astype(jnp.float32)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])  # [B,H]
    A = -jnp.exp(p["A_log"])
    hg = h // g

    decay = jnp.exp(dt * A)[..., None, None]                 # [B,H,1,1]
    Bh = jnp.repeat(Bm, hg, axis=-2)                         # [B,H,N]
    Ch = jnp.repeat(Cm, hg, axis=-2)
    upd = (dt[..., None] * xs)[..., :, None] * Bh[:, :, None, :]  # [B,H,P,N]
    new_ssm = state["ssm"] * decay + upd
    y = jnp.einsum("bhpn,bhn->bhp", new_ssm, Ch)             # [B,H,P]
    y = y + p["D"][None, :, None] * xs
    y = y.reshape(b, d_inner).astype(x.dtype)
    y = rmsnorm_gated_apply(p["norm"], y, z, cfg.rms_eps)
    out = dense_apply(p["out_proj"], y)[:, None, :]
    return out, {"ssm": new_ssm, "conv": new_conv}
