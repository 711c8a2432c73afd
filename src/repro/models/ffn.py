"""Feed-forward layers: dense SwiGLU and expert-parallel MoE.

MoE uses the GShard/MaxText "dropping" formulation — two one-hot einsums
around batched expert matmuls — because it shards cleanly under GSPMD:
tokens grouped on the ('pod','data') axes, experts on 'model' (EP == TP
axis). The combine einsum contracts the expert axis, which GSPMD lowers to
the expected all-reduce over 'model' — that IS the EP combine collective.

The paper's spiking mode (C3) replaces the SiLU gate with a LIF spike: the
hidden activation becomes a binary event map, which is what the event-driven
``spike_matmul`` kernel consumes (block-sparse skip on silent tiles).

Router details follow OLMoE/llama4: softmax router, top-k selection,
optional renormalization, auxiliary load-balance loss (Switch-style) and
router-z loss for logit control.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from .layers import dense_apply, dense_init, maybe_spike
from .sharding import shard_act

Array = jax.Array


# ------------------------------------------------------------- dense SwiGLU
def mlp_init(rng: Array, cfg: ModelConfig, d: Optional[int] = None,
             d_ff: Optional[int] = None) -> dict:
    d = d or cfg.d_model
    f = d_ff or cfg.d_ff
    rg, ru, rd = jax.random.split(rng, 3)
    return {
        "gate": dense_init(rg, d, f, dtype=cfg.param_dtype),
        "up": dense_init(ru, d, f, dtype=cfg.param_dtype),
        "down": dense_init(rd, f, d, dtype=cfg.param_dtype),
    }


def mlp_apply(p: dict, cfg: ModelConfig, x: Array) -> Array:
    g = dense_apply(p["gate"], x)
    u = dense_apply(p["up"], x)
    if cfg.spiking:
        h = maybe_spike(g, True, cfg.lif) * u     # LIF gate: binary event map
    else:
        h = jax.nn.silu(g) * u
    return dense_apply(p["down"], h)


# ----------------------------------------------------------- serving weights
def ffn_serving_params(p: dict, dtype) -> dict:
    """A block's params (one block or a layer stack) with the weights of its
    MLP or MoE in ``dtype``: the forward reads each of them only through
    ``.astype(x.dtype)``, so casting once gives the same arithmetic as
    casting on every call. Every other leaf is the same object."""
    def cast(tree):
        return jax.tree_util.tree_map(
            lambda a: a if a.dtype == dtype else a.astype(dtype), tree)

    out = dict(p)
    if "mlp" in p:
        out["mlp"] = cast(p["mlp"])
    if "moe" in p:
        # all but the router, which router_probs reads in float32
        out["moe"] = {k: v if k == "router" else cast(v)
                      for k, v in p["moe"].items()}
    return out


# --------------------------------------------------------------------- MoE
def moe_init(rng: Array, cfg: ModelConfig) -> dict:
    d, f, e = cfg.d_model, cfg.moe_d_ff or cfg.d_ff, cfg.n_experts
    rr, rg, ru, rd, rs = jax.random.split(rng, 5)
    std = 1.0 / (d ** 0.5)
    p = {
        "router": dense_init(rr, d, e, dtype=jnp.float32),  # router in f32
        "w_gate": jax.random.truncated_normal(rg, -2, 2, (e, d, f), jnp.float32).astype(cfg.param_dtype) * std,
        "w_up": jax.random.truncated_normal(ru, -2, 2, (e, d, f), jnp.float32).astype(cfg.param_dtype) * std,
        "w_down": jax.random.truncated_normal(rd, -2, 2, (e, f, d), jnp.float32).astype(cfg.param_dtype) * (1.0 / f ** 0.5),
    }
    if cfg.n_shared_experts:
        p["shared"] = mlp_init(rs, cfg, d, (cfg.d_ff or f) * cfg.n_shared_experts)
    return p


def moe_capacity(cfg: ModelConfig, group_tokens: int) -> int:
    cap = int(group_tokens * cfg.top_k * cfg.capacity_factor / cfg.n_experts)
    return max(8, min(group_tokens, -(-cap // 8) * 8))   # mult of 8, bounded


def router_probs(p: dict, x: Array) -> Array:
    """[.., D] -> [.., E] f32 softmax router probabilities."""
    logits = x.astype(jnp.float32) @ p["router"]["w"]
    return jax.nn.softmax(logits, axis=-1), logits


def moe_apply(p: dict, cfg: ModelConfig, x: Array) -> tuple[Array, dict]:
    """x: [B, S, D] -> (y, aux_losses).

    Token grouping: each batch row is a dispatch group (G=B, S_g=S) — groups
    stay aligned with the data shards so dispatch never crosses the 'data'
    axis; only the combine reduces over 'model' (EP combine all-reduce).
    """
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    cap = moe_capacity(cfg, s)

    probs, logits = router_probs(p, x)                   # [B,S,E] f32
    topv, topi = jax.lax.top_k(probs, k)                 # [B,S,k]
    if cfg.top_k > 1:
        topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)

    # position of each (token, choice) in its expert's capacity buffer
    onehot = jax.nn.one_hot(topi, e, dtype=jnp.int32)    # [B,S,k,E]
    # rank tokens per expert by arrival order (cumsum over flattened S*k)
    flat = onehot.reshape(b, s * k, e)
    ranks = jnp.cumsum(flat, axis=1) - flat              # [B,S*k,E]
    rank_of_choice = (ranks * flat).sum(-1).reshape(b, s, k)
    keep = rank_of_choice < cap                          # capacity drop mask
    weight = topv * keep.astype(topv.dtype)              # [B,S,k]

    # dispatch one-hot [B,S,E,cap] (bf16 so the einsums hit the MXU)
    pos_onehot = jax.nn.one_hot(jnp.where(keep, rank_of_choice, cap), cap + 1,
                                dtype=x.dtype)[..., :cap]     # [B,S,k,cap]
    disp = jnp.einsum("bske,bskc->bsec",
                      onehot.astype(x.dtype), pos_onehot)     # [B,S,E,cap]
    comb = jnp.einsum("bsk,bske,bskc->bsec",
                      weight.astype(x.dtype), onehot.astype(x.dtype), pos_onehot)

    xe = jnp.einsum("bsd,bsec->becd", x, disp)           # [B,E,cap,D]
    # pin the dispatched tokens EXPERT-sharded: the (sharded-seq) dispatch
    # contraction then lowers to reduce-scatter onto expert shards instead
    # of all-reduce + re-slice (EXPERIMENTS §Perf B2)
    xe = shard_act(xe, "dp", "model", None, None)
    g = jnp.einsum("becd,edf->becf", xe, p["w_gate"].astype(x.dtype))
    u = jnp.einsum("becd,edf->becf", xe, p["w_up"].astype(x.dtype))
    if cfg.spiking:
        h = maybe_spike(g, True, cfg.lif) * u
    else:
        h = jax.nn.silu(g) * u
    ye = jnp.einsum("becf,efd->becd", h, p["w_down"].astype(x.dtype))
    y = jnp.einsum("becd,bsec->bsd", ye, comb)           # EP combine (psum)

    if cfg.n_shared_experts:
        y = y + mlp_apply(p["shared"], cfg, x)

    # Switch aux loss: E * sum_e f_e * P_e  (f = fraction routed, P = mean prob)
    f_e = jnp.mean(onehot.sum(2).astype(jnp.float32), axis=(0, 1))
    p_e = jnp.mean(probs, axis=(0, 1))
    aux = {
        "load_balance": e * jnp.sum(f_e * p_e),
        "router_z": jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2),
        "dropped_frac": 1.0 - jnp.mean(keep.astype(jnp.float32)),
    }
    return y, aux


def moe_apply_dense_ref(p: dict, cfg: ModelConfig, x: Array) -> Array:
    """Oracle: run EVERY expert on every token, combine with (dropless) top-k
    weights. O(E) FLOPs — tests only. Dispatch impl must match this wherever
    no token is capacity-dropped."""
    probs, _ = router_probs(p, x)
    topv, topi = jax.lax.top_k(probs, cfg.top_k)
    if cfg.top_k > 1:
        topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
    w_full = jnp.zeros_like(probs).at[
        jnp.arange(x.shape[0])[:, None, None],
        jnp.arange(x.shape[1])[None, :, None], topi].set(topv)
    g = jnp.einsum("bsd,edf->besf", x, p["w_gate"].astype(x.dtype))
    u = jnp.einsum("bsd,edf->besf", x, p["w_up"].astype(x.dtype))
    if cfg.spiking:
        h = maybe_spike(g, True, cfg.lif) * u
    else:
        h = jax.nn.silu(g) * u
    ye = jnp.einsum("besf,efd->besd", h, p["w_down"].astype(x.dtype))
    y = jnp.einsum("besd,bse->bsd", ye, w_full.astype(x.dtype))
    if cfg.n_shared_experts:
        y = y + mlp_apply(p["shared"], cfg, x)
    return y
