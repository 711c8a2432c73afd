"""Attention layer: GQA softmax attention (full / chunked-flash / decode) and
the paper's spiking Q-K attention (C4) as a drop-in replacement.

Softmax path
  * ``full``     — materializes [B,H,Sq,Sk] scores; right choice for
                   train_4k (4k^2 tiles fit VMEM budgets after blocking).
  * ``chunked``  — flash-style streaming over KV blocks with running
                   (max, denom) — used above ``cfg.flash_threshold`` so
                   prefill_32k never materializes a 32k^2 score matrix.
  * ``decode``   — one query position against the cache; with the cache
                   sequence-sharded (long_500k) GSPMD turns the softmax
                   reductions into the flash-decoding partial-softmax
                   combine across the 'data' axis automatically.

Spiking path (attention_kind="qk_spiking", paper C4 / QKFormer QKTA)
  Q,K are LIF spike maps; a per-token mask = spike(rowsum(Q) - theta) gates
  K; output = mask * K. O(N*Dh) — no score matrix, no softmax, and the mask
  for token i depends only on token i, so decode needs NO KV cache at all
  (this is what makes long_500k feasible for every arch in spiking mode).
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from .. import ops
from ..configs.base import ModelConfig
from ..core.qk_attention import qk_grouped_token_attention
from ..ops import SpikeTensor
from .layers import (apply_rope, causal_mask, dense_apply, dense_init,
                     maybe_spike, rmsnorm_apply, rmsnorm_init)
from .sharding import shard_act

Array = jax.Array


# ----------------------------------------------------------------------- init
def attn_init(rng: Array, cfg: ModelConfig, d_model: Optional[int] = None,
              n_heads: Optional[int] = None, n_kv: Optional[int] = None) -> dict:
    d = d_model or cfg.d_model
    h = n_heads or cfg.n_heads
    hkv = n_kv or (cfg.n_kv_heads or h)
    dh = cfg.resolved_head_dim
    rq, rk, rv, ro = jax.random.split(rng, 4)
    p = {
        "wq": dense_init(rq, d, h * dh, bias=cfg.qkv_bias, dtype=cfg.param_dtype),
        "wk": dense_init(rk, d, hkv * dh, bias=cfg.qkv_bias, dtype=cfg.param_dtype),
        "wv": dense_init(rv, d, hkv * dh, bias=cfg.qkv_bias, dtype=cfg.param_dtype),
        "wo": dense_init(ro, h * dh, d, dtype=cfg.param_dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = rmsnorm_init(dh, cfg.param_dtype)
        p["k_norm"] = rmsnorm_init(dh, cfg.param_dtype)
    return p


def attn_scale(cfg: ModelConfig) -> float:
    """Softmax scale of the scores: the config's published
    ``attention_multiplier`` where it gives one, else 1/sqrt(head_dim)."""
    return cfg.attention_multiplier or cfg.resolved_head_dim ** -0.5


def _position_code(cfg: ModelConfig, x: Array, positions: Array) -> Array:
    """Rotary position code on q or k; none under ``nope``."""
    if cfg.position_embedding_type == "nope":
        return x
    return apply_rope(x, positions, cfg.rope_theta)


def _project_qkv(p: dict, cfg: ModelConfig, x: Array, positions: Array,
                 h: int, hkv: int):
    b, s, _ = x.shape
    dh = cfg.resolved_head_dim
    q = dense_apply(p["wq"], x).reshape(b, s, h, dh)
    k = dense_apply(p["wk"], x).reshape(b, s, hkv, dh)
    v = dense_apply(p["wv"], x).reshape(b, s, hkv, dh)
    if cfg.qk_norm:
        q = rmsnorm_apply(p["q_norm"], q, cfg.rms_eps)
        k = rmsnorm_apply(p["k_norm"], k, cfg.rms_eps)
    return _position_code(cfg, q, positions), \
        _position_code(cfg, k, positions), v


def _expand_kv(k: Array, h: int) -> Array:
    """[B,S,Hkv,Dh] -> [B,S,H,Dh] by repeating each KV head h/hkv times."""
    hkv = k.shape[-2]
    if hkv == h:
        return k
    return jnp.repeat(k, h // hkv, axis=-2)


# ---------------------------------------------------------------- full attn
def _attn_full(q: Array, k: Array, v: Array, scale: float,
               causal: bool, q_offset: int = 0) -> Array:
    # f32 via preferred_element_type (not .astype): the backward transposed
    # dots then produce bf16 dq/dk directly — their TP partial-sum
    # all-reduces run at half the wire width (EXPERIMENTS §Perf A7)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        scores = scores + causal_mask(q.shape[1], k.shape[1], q_offset)
    w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", w, v)


# ----------------------------------------------------------- chunked (flash)
def _attn_chunked(q: Array, k: Array, v: Array, scale: float, causal: bool,
                  q_block: int, kv_block: int) -> Array:
    """Flash-style: stream KV blocks, keep running (max, denom, out). The
    scan over KV blocks bounds live memory to O(q_block * kv_block)."""
    b, sq, h, dh = q.shape
    sk = k.shape[1]
    q_block = min(q_block, sq)
    kv_block = min(kv_block, sk)
    assert sq % q_block == 0 and sk % kv_block == 0, (sq, sk, q_block, kv_block)
    nq, nk = sq // q_block, sk // kv_block

    qb = q.reshape(b, nq, q_block, h, dh)
    kb = k.reshape(b, nk, kv_block, h, dh)
    vb = v.reshape(b, nk, kv_block, h, dh)

    def process_q_block(qi, q_i):
        # q_i: [b, q_block, h, dh]
        def kv_step(carry, inputs):
            m, l, o = carry
            ki, (k_j, v_j) = inputs
            s_ij = jnp.einsum("bqhd,bkhd->bhqk", q_i, k_j).astype(jnp.float32) * scale
            if causal:
                q_pos = qi * q_block + jnp.arange(q_block)[:, None]
                k_pos = ki * kv_block + jnp.arange(kv_block)[None, :]
                s_ij = s_ij + jnp.where(k_pos <= q_pos, 0.0, -1e30)[None, None]
            m_new = jnp.maximum(m, s_ij.max(axis=-1))
            p_ij = jnp.exp(s_ij - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p_ij.sum(axis=-1)
            o_new = o * corr[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p_ij.astype(q.dtype), v_j).astype(jnp.float32)
            return (m_new, l_new, o_new), None

        m0 = jnp.full((b, h, q_block), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((b, h, q_block), jnp.float32)
        o0 = jnp.zeros((b, h, q_block, dh), jnp.float32)
        ks = jnp.arange(nk)
        # checkpoint the block body: backward recomputes p_ij from (q, k)
        # instead of saving [q_block, kv_block] scores per step — the
        # flash-attention memory property under autodiff
        (m, l, o), _ = jax.lax.scan(
            jax.checkpoint(kv_step), (m0, l0, o0),
            (ks, (jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0))))
        out = o / jnp.maximum(l[..., None], 1e-30)
        return jnp.einsum("bhqd->bqhd", out).astype(q.dtype)

    outs = jax.lax.map(lambda args: process_q_block(*args),
                       (jnp.arange(nq), jnp.moveaxis(qb, 1, 0)))
    return jnp.moveaxis(outs, 0, 1).reshape(b, sq, h, dh)


# -------------------------------------------------------------------- public
def attn_apply(p: dict, cfg: ModelConfig, x: Array, positions: Array,
               *, causal: bool = True, n_heads: Optional[int] = None,
               n_kv: Optional[int] = None,
               kv_override: Optional[tuple[Array, Array]] = None) -> Array:
    """Training/prefill attention over a full sequence.

    ``kv_override`` supplies external K/V (cross-attention: encoder states
    already projected). Returns [B, S, D_out].
    """
    h = n_heads or cfg.n_heads
    hkv = n_kv or (cfg.n_kv_heads or h)
    dh = cfg.resolved_head_dim
    b, s, _ = x.shape
    scale = attn_scale(cfg)

    if cfg.attention_kind == "qk_spiking":
        return _qk_spiking_apply(p, cfg, x, h, hkv)

    if kv_override is None:
        q, k, v = _project_qkv(p, cfg, x, positions, h, hkv)
    else:
        q = dense_apply(p["wq"], x).reshape(b, s, h, dh)
        if cfg.qk_norm:
            q = rmsnorm_apply(p["q_norm"], q, cfg.rms_eps)
        q = _position_code(cfg, q, positions)
        k, v = kv_override
    k = _expand_kv(k, h)
    v = _expand_kv(v, h)

    if s * k.shape[1] > cfg.flash_threshold ** 2 and s > 1:
        out = _attn_chunked(q, k, v, scale, causal, cfg.attn_q_block,
                            cfg.attn_kv_block)
    else:
        out = _attn_full(q, k, v, scale, causal)
    return dense_apply(p["wo"], out.reshape(b, s, h * dh))


def attn_prefill(p: dict, cfg: ModelConfig, x: Array, positions: Array,
                 *, n_heads: Optional[int] = None,
                 n_kv: Optional[int] = None) -> tuple[Array, tuple[Array, Array]]:
    """Prefill: full-sequence attention that ALSO returns (k, v) for the cache."""
    h = n_heads or cfg.n_heads
    hkv = n_kv or (cfg.n_kv_heads or h)
    dh = cfg.resolved_head_dim
    b, s, _ = x.shape
    if cfg.attention_kind == "qk_spiking":
        empty = jnp.zeros((b, 0, hkv, dh), x.dtype)
        if cfg.exec_policy.packed:
            # cache the last token's masked spike map BIT-PACKED — the
            # engine's per-slot spike state (8x fewer bytes than int8; the
            # telemetry popcounts it for measured sparsity)
            out, state = _qk_spiking_apply(p, cfg, x, h, hkv,
                                           return_spike_state=True)
            return out, (state, empty)
        out = _qk_spiking_apply(p, cfg, x, h, hkv)
        # QKTA keeps no inter-token state: empty cache entries
        return out, (empty, empty)
    q, k, v = _project_qkv(p, cfg, x, positions, h, hkv)
    ke, ve = _expand_kv(k, h), _expand_kv(v, h)
    scale = attn_scale(cfg)
    if s * s > cfg.flash_threshold ** 2:
        out = _attn_chunked(q, ke, ve, scale, True, cfg.attn_q_block,
                            cfg.attn_kv_block)
    else:
        out = _attn_full(q, ke, ve, scale, True)
    return dense_apply(p["wo"], out.reshape(b, s, h * dh)), (k, v)


def attn_append(p: dict, cfg: ModelConfig, x: Array,
                cache_k: Array, cache_v: Array, cache_len: Array,
                *, n_heads: Optional[int] = None,
                n_kv: Optional[int] = None) -> tuple[Array, tuple[Array, Array]]:
    """Continued (chunked) prefill: C new tokens against a partially-filled
    cache. x: [B, C, D]; cache_[kv]: [B, S_max, Hkv, Dh]; cache_len: scalar
    or [B] — the number of already-valid cache rows per sequence.

    The chunk's K/V rows are written at positions cache_len..cache_len+C-1
    and query i attends the cached prefix plus chunk positions <= i — the
    serving engine's elastic-FIFO prefill unit (one chunk per call, decode
    ticks interleave between calls). Bit-identical to running the whole
    prompt through ``attn_prefill`` in one pass: per-position projections
    are local, masked-out keys get exactly-zero softmax weight, and scores
    accumulate in f32 either way. C == 1 is ``attn_decode``'s math.

    NOTE: scores read the cache as written, so bit-identity to blocking
    prefill requires the cache dtype to be the COMPUTE dtype — with a
    quantized (f8) serving cache the engine keeps per-request chunk caches
    at compute precision and quantizes once on the slot write, exactly
    where the blocking path does. Bit-identity also assumes the blocking
    pass took the full-softmax branch: above ``cfg.flash_threshold``
    ``attn_prefill`` streams KV blocks with running-max rescaling, a
    different f32 reduction order this append path does not reproduce.
    """
    h = n_heads or cfg.n_heads
    hkv = n_kv or (cfg.n_kv_heads or h)
    dh = cfg.resolved_head_dim
    b, c, _ = x.shape
    scale = attn_scale(cfg)

    if cfg.attention_kind == "qk_spiking":
        # token-local: the chunk is self-contained; packed mode refreshes
        # the per-slot spike state with the chunk's last token
        if cfg.exec_policy.packed:
            out, state = _qk_spiking_apply(p, cfg, x, h, hkv,
                                           return_spike_state=True)
            return out, (state, cache_v)
        out = _qk_spiking_apply(p, cfg, x, h, hkv)
        return out, (cache_k, cache_v)

    lens = jnp.broadcast_to(jnp.asarray(cache_len), (b,))        # [B]
    positions = lens[:, None] + jnp.arange(c)[None, :]           # [B, C]
    q, k_new, v_new = _project_qkv(p, cfg, x, positions, h, hkv)

    if jnp.ndim(cache_len) == 0:
        k = jax.lax.dynamic_update_slice(cache_k, k_new.astype(cache_k.dtype),
                                         (0, cache_len, 0, 0))
        v = jax.lax.dynamic_update_slice(cache_v, v_new.astype(cache_v.dtype),
                                         (0, cache_len, 0, 0))
    else:
        bi = jnp.arange(b)[:, None]
        rows = positions
        k = cache_k.at[bi, rows].set(k_new.astype(cache_k.dtype))
        v = cache_v.at[bi, rows].set(v_new.astype(cache_v.dtype))

    ke = _expand_kv(k.astype(q.dtype), h)
    ve = _expand_kv(v.astype(q.dtype), h)
    # f32 scores via preferred_element_type — same accumulation as the
    # blocking prefill's _attn_full, so chunked == blocking bit-for-bit
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, ke,
                        preferred_element_type=jnp.float32) * scale
    # query i (absolute position lens+i) sees key j iff j <= lens + i
    ki = jnp.arange(ke.shape[1])[None, None, :]                  # [1,1,S]
    valid = ki <= positions[:, :, None]                          # [B,C,S]
    scores = jnp.where(valid[:, None], scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", w, ve)
    return dense_apply(p["wo"], out.reshape(b, c, h * dh)), (k, v)


def attn_decode(p: dict, cfg: ModelConfig, x: Array, pos: Array,
                cache_k: Array, cache_v: Array, cache_len: Array,
                *, n_heads: Optional[int] = None,
                n_kv: Optional[int] = None,
                rows: bool = False) -> tuple[Array, tuple[Array, Array]]:
    """One-token decode. x: [B, 1, D]; cache_[kv]: [B, S_max, Hkv, Dh];
    cache_len: [] scalar OR [B] vector of per-sequence valid lengths (the
    serving engine's slot pool uses the vector form; the new token is
    written at index cache_len per sequence).

    ``rows``: read the cache as it is and return the new token's K/V rows
    ([B, 1, Hkv, Dh]) for the caller to write, instead of the updated
    cache; the new token joins the softmax as its own term (the frozen
    form below). For a caller that holds the cache in a larger buffer and
    writes the rows there in place.

    When the cache is sequence-sharded over 'data' (long_500k), the masked
    softmax below reduces over a sharded axis — GSPMD lowers it to the
    flash-decoding partial combine (max/sum all-reduce over 'data').
    """
    h = n_heads or cfg.n_heads
    hkv = n_kv or (cfg.n_kv_heads or h)
    dh = cfg.resolved_head_dim
    b = x.shape[0]
    scale = attn_scale(cfg)

    if cfg.attention_kind == "qk_spiking":
        if cfg.exec_policy.packed:
            out, state = _qk_spiking_apply(p, cfg, x, h, hkv,
                                           return_spike_state=True)
            return out, (state, cache_v)
        out = _qk_spiking_apply(p, cfg, x, h, hkv)
        return out, (cache_k, cache_v)

    lens = jnp.broadcast_to(jnp.asarray(cache_len), (b,))       # [B]
    positions = lens[:, None] if jnp.ndim(pos) <= 1 else pos
    if jnp.ndim(pos) == 0:
        positions = jnp.broadcast_to(pos[None, None], (b, 1))
    q, k_new, v_new = _project_qkv(p, cfg, x, positions, h, hkv)

    if cfg.decode_cp_axis or rows:
        # context-parallel decode (cache SEQUENCE-sharded over an axis):
        # the cache is FROZEN — a dynamic-index write into a seq-sharded
        # buffer makes GSPMD gather the whole cache (measured: 56 GB/step
        # on decode_32k, EXPERIMENTS §Perf C). Instead the new token's K/V
        # joins the softmax as a separate flash-decode term; reductions
        # over the sharded seq dim lower to tiny [B,H] stat all-reduces.
        # q must REPLICATE across the cp axis (it is KB-sized): if it stays
        # head-sharded over 'model' the score einsum cannot shard over seq
        # and GSPMD gathers the whole cache instead. GQA is handled with a
        # GROUPED einsum (q reshaped [B,1,Hkv,G,Dh]) — jnp.repeat of a
        # seq-sharded cache lowers to a broadcast GSPMD can only realize by
        # gathering (measured: 56 GB/step; EXPERIMENTS §Perf C4).
        g = h // hkv
        q5 = shard_act(q, "dp", None, None, None).reshape(b, 1, hkv, g, dh)
        kc = cache_k.astype(q.dtype)                     # [b,S,hkv,dh]
        vc = cache_v.astype(q.dtype)
        s_ctx = jnp.einsum("bqhgd,bkhd->bhgqk", q5, kc,
                           preferred_element_type=jnp.float32) * scale
        valid = (jnp.arange(kc.shape[1])[None, :] < lens[:, None])
        s_ctx = jnp.where(valid[:, None, None, None, :], s_ctx, -1e30)
        s_new = jnp.einsum("bqhgd,bqhd->bhgq", q5, k_new.astype(q.dtype),
                           preferred_element_type=jnp.float32)[..., None] * scale
        m = jnp.maximum(s_ctx.max(axis=-1, keepdims=True), s_new)
        p_ctx = jnp.exp(s_ctx - m)                       # [b,hkv,g,1,S]
        p_new = jnp.exp(s_new - m)[..., 0]               # [b,hkv,g,1]
        denom = p_ctx.sum(axis=-1) + p_new               # [b,hkv,g,1]
        out = jnp.einsum("bhgqk,bkhd->bhgqd", p_ctx.astype(q.dtype), vc)
        out = out + jnp.einsum("bhgq,bqhd->bhgqd", p_new.astype(q.dtype),
                               v_new.astype(q.dtype))
        out = out / denom[..., None].astype(q.dtype)     # [b,hkv,g,1,dh]
        out = out.transpose(0, 3, 1, 2, 4).reshape(b, 1, h * dh)
        out = dense_apply(p["wo"], out)
        return out, ((k_new, v_new) if rows else (cache_k, cache_v))

    # write the new K/V row at index cache_len (per sequence)
    if jnp.ndim(cache_len) == 0:
        k = jax.lax.dynamic_update_slice(cache_k, k_new.astype(cache_k.dtype),
                                         (0, cache_len, 0, 0))
        v = jax.lax.dynamic_update_slice(cache_v, v_new.astype(cache_v.dtype),
                                         (0, cache_len, 0, 0))
    else:
        bi = jnp.arange(b)
        k = cache_k.at[bi, lens].set(k_new[:, 0].astype(cache_k.dtype))
        v = cache_v.at[bi, lens].set(v_new[:, 0].astype(cache_v.dtype))

    ke = _expand_kv(k.astype(q.dtype), h)
    ve = _expand_kv(v.astype(q.dtype), h)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, ke).astype(jnp.float32) * scale
    valid = (jnp.arange(ke.shape[1])[None, :] <= lens[:, None])
    scores = jnp.where(valid[:, None, None, :], scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", w.astype(q.dtype), ve)
    return dense_apply(p["wo"], out.reshape(b, 1, h * dh)), (k, v)


# ----------------------------------------------------- spiking QKTA (paper C4)
def qk_spike_state_width(cfg: ModelConfig) -> int:
    """int32 words per cached packed spike-state row: the masked attention
    map [H*Dh] padded to the 128 lane grid, 32 spikes per word."""
    d = cfg.n_heads * cfg.resolved_head_dim
    return (-(-d // 128) * 128) // 32


def _packed_token_state(out_last: Array) -> Array:
    """[B, D] binary spike map -> [B, 1, 1, ceil(D/128)*4] int32 words —
    the per-token spike state the serving engine caches per slot (packed:
    8x fewer bytes than int8, and popcount over it IS the measured spike
    count the engine's telemetry reports)."""
    from ..core.events import pack_words

    b, d = out_last.shape
    dp = -(-d // 128) * 128
    padded = jnp.pad(out_last.astype(jnp.int32), ((0, 0), (0, dp - d)))
    return pack_words(padded)[:, None, None, :]


def _token_state(st: SpikeTensor, b: int, s: int) -> Array:
    """Last token's masked spike map as packed [B, 1, 1, W] int32 — the
    per-slot state the serving engine caches, extracted without unpacking
    when the map is already packed."""
    if st.is_packed:
        dw = st.data.shape[-1]
        return st.data[:b * s].reshape(b, s, dw)[:, -1][:, None, None, :]
    return _packed_token_state(st.data.reshape(b, s, -1)[:, -1])


def _qk_spiking_apply(p: dict, cfg: ModelConfig, x: Array,
                      h: int, hkv: int, *, return_spike_state: bool = False):
    """QKFormer token attention on LIF spikes (paper Fig 5, on-the-fly form).

    Per head: Q,K spike maps [B,S,h,Dh]; token mask from Q row-sum gates K.
    No RoPE (spike trains carry no phase), no cache (mask is token-local).

    ``cfg.exec_policy`` selects the execution (one body, no format forks):

      * fused policies (deployed serving path) run NEURAL's fused PE
        dataflow for EVERY head count — wq/wk projections + LIF threshold
        are single fused Pallas passes (``ops.dense_lif``; no f32
        pre-activation round-trip), and the QK token mask is applied
        inside the K pass's write-back as a HEAD-BLOCKED mask (the full
        Fig 5 fusion: one row-sum threshold per head; h==1 degenerates to
        the whole-row mask). Grouped KV (hkv < h) expands the K
        projection's WEIGHT columns so the per-query-head mask gates
        in-kernel — no replicated per-token KV tensor. The output
        projection consumes the masked spikes through the event-skipped
        ``ops.matmul``. Forward-exact vs the reference path; a
        differentiable policy (``policy.for_training()`` — what
        ``launch/train.py --spiking --policy fused_dense`` requests)
        additionally routes these ops through their surrogate-gradient
        custom_vjp so the SAME fused forward trains with backprop.
      * a packed policy ships the spike maps between passes bit-packed
        end to end for every head count: the Q operand's per-head row
        sums are in-kernel masked popcounts and the K pass's output
        leaves packed — the masked map never exists dense.

    ``return_spike_state`` additionally returns the LAST token's masked
    spike map packed ([B, 1, 1, W] int32) — the state the serving engine
    caches per slot.
    """
    b, s, _ = x.shape
    dh = cfg.resolved_head_dim
    pol = cfg.exec_policy
    state = None
    if pol.fused:
        # fully fused head-blocked Fig 5 chain: the K pass masks per head
        # on write-back, and under a packed policy the masked map never
        # exists dense
        q_st = ops.dense_lif(p["wq"], x, cfg.lif, policy=pol)
        out_st = ops.dense_lif(p["wk"], x, cfg.lif, q=q_st,
                               qk_threshold=cfg.lif.v_th,
                               heads=(h, dh), kv_heads=hkv, policy=pol)
        proj = ops.matmul(out_st, p["wo"]["w"], policy=pol).astype(x.dtype)
        if return_spike_state:
            state = _token_state(out_st, b, s)
        if "b" in p["wo"]:
            proj = proj + p["wo"]["b"].astype(proj.dtype)
        proj = proj.reshape(b, s, -1)
        return (proj, state) if return_spike_state else proj
    q_cur = dense_apply(p["wq"], x).reshape(b, s, h, dh)
    k_cur = dense_apply(p["wk"], x).reshape(b, s, hkv, dh)
    q = maybe_spike(q_cur, True, cfg.lif)
    k = maybe_spike(k_cur, True, cfg.lif)
    # [B,S,H,Dh] — the QK token mask (4); grouped KV broadcasts the
    # per-query-head mask over each group instead of replicating K
    out = qk_grouped_token_attention(q, k, mode="threshold",
                                     threshold=cfg.lif.v_th,
                                     surrogate=cfg.lif.surrogate,
                                     alpha=cfg.lif.alpha)
    proj = dense_apply(p["wo"], out.reshape(b, s, h * dh))
    if return_spike_state:
        state = _packed_token_state(out.reshape(b, s, h * dh)[:, -1])
        return proj, state
    return proj
