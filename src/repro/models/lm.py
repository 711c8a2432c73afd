"""Unified LM engine for the assigned architecture zoo.

One parameterized decoder stack covers five families:

  dense   (qwen1.5-32b, qwen3-1.7b, qwen2.5-3b, yi-9b) — GQA attn + SwiGLU
  moe     (llama4-scout-17b-a16e, olmoe-1b-7b)         — GQA attn + MoE FFN
  ssm     (mamba2-130m)                                — Mamba2/SSD blocks
  hybrid  (zamba2-7b)                                  — Mamba2 + ONE shared
          attention block applied every ``attn_every`` layers (grouped scan)
          (granite-4.0-h-micro)                        — a ``layer_types``
          pattern of unshared Mamba2 and attention layers, each with its
          own MLP (one scan over whole periods)
  vlm     (phi-3-vision-4.2b)                          — dense decoder with a
          precomputed-patch-embedding prefix (+ optional W2TTFS patch merge)

plus an encoder-decoder (seamless-m4t-large-v2) built from the same blocks.

Execution modes map 1:1 onto the assigned shape grid:
  loss/train_step -> train_4k          (full causal LM step)
  prefill         -> prefill_32k       (logits + cache construction)
  decode_step     -> decode_32k / long_500k (one token against a full cache)

Layers run under ``lax.scan`` over stacked params (cfg.scan_layers) so the
HLO stays one-block-sized regardless of depth — this is what keeps 64-layer
32B configs compilable for a 512-way mesh on a CPU host. Remat policy is
per-config ("none" | "full" | "dots").

The paper's techniques are config flags (see DESIGN §Arch-applicability):
``spiking`` turns FFN gates and QK paths into LIF spike events (C1/C3);
``attention_kind='qk_spiking'`` swaps softmax attention for the on-the-fly
QKFormer token attention (C4) — O(N*Dh), cache-free decode.
"""
from __future__ import annotations

import dataclasses
import functools
import itertools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig, ShapeSpec
from ..core.w2ttfs import window_counts
from .attention import (attn_append, attn_apply, attn_decode, attn_init,
                        attn_prefill)
from .ffn import (ffn_serving_params, mlp_apply, mlp_init, moe_apply,
                  moe_init)
from .layers import (dense_apply, dense_init, embedding_init,
                     embedding_lookup, embedding_logits, maybe_spike,
                     rmsnorm_apply, rmsnorm_init)
from .sharding import shard_act
from .ssm import (mamba_apply, mamba_decode_step, mamba_init,
                  mamba_init_state, ssm_dims)

Array = jax.Array


# ===================================================================== blocks
def _block_kind(cfg: ModelConfig) -> str:
    return {"dense": "attn_mlp", "vlm": "attn_mlp", "moe": "attn_moe",
            "ssm": "mamba", "hybrid": "mamba"}[cfg.family]


# a ``layer_types`` entry -> (its cache and params key, its block kind)
_PATTERN = {"mamba": ("mamba", "mamba"), "attention": ("attn", "attn_mlp")}


def _pattern_runs(cfg: ModelConfig) -> list:
    """(key, block kind, index among the key's runs, length) of each run
    of like layers in one period of ``cfg.layer_types``."""
    runs, seen = [], {}
    for t, grp in itertools.groupby(cfg.layer_types[:cfg.layer_period]):
        key, kind = _PATTERN[t]
        runs.append((key, kind, seen.get(key, 0), len(list(grp))))
        seen[key] = seen.get(key, 0) + 1
    return runs


def block_init(rng: Array, cfg: ModelConfig, kind: Optional[str] = None
               ) -> dict:
    kind = kind or _block_kind(cfg)
    r1, r2 = jax.random.split(rng)
    if kind == "attn_mlp":
        return {"ln1": rmsnorm_init(cfg.d_model, cfg.param_dtype),
                "attn": attn_init(r1, cfg),
                "ln2": rmsnorm_init(cfg.d_model, cfg.param_dtype),
                "mlp": mlp_init(r2, cfg)}
    if kind == "attn_moe":
        return {"ln1": rmsnorm_init(cfg.d_model, cfg.param_dtype),
                "attn": attn_init(r1, cfg),
                "ln2": rmsnorm_init(cfg.d_model, cfg.param_dtype),
                "moe": moe_init(r2, cfg)}
    if kind == "mamba":
        p = {"ln1": rmsnorm_init(cfg.d_model, cfg.param_dtype),
             "mamba": mamba_init(r1, cfg)}
        if cfg.layer_types and cfg.d_ff:     # a pattern layer's own MLP
            p["ln2"] = rmsnorm_init(cfg.d_model, cfg.param_dtype)
            p["mlp"] = mlp_init(r2, cfg)
        return p
    raise ValueError(kind)


def shared_attn_init(rng: Array, cfg: ModelConfig) -> dict:
    """Zamba2's weight-shared attention block (one param set, many sites)."""
    r1, r2 = jax.random.split(rng)
    return {"ln1": rmsnorm_init(cfg.d_model, cfg.param_dtype),
            "attn": attn_init(r1, cfg),
            "ln2": rmsnorm_init(cfg.d_model, cfg.param_dtype),
            "mlp": mlp_init(r2, cfg, d_ff=cfg.d_ff)}


def _zero_aux() -> dict:
    return {"load_balance": jnp.zeros((), jnp.float32),
            "router_z": jnp.zeros((), jnp.float32),
            "dropped_frac": jnp.zeros((), jnp.float32)}


def _res(cfg: ModelConfig, x: Array, h: Array) -> Array:
    """x plus one branch, scaled by the config's residual multiplier."""
    m = cfg.residual_multiplier
    return x + h if m == 1.0 else x + h * m


def _ffn_tail(p: dict, cfg: ModelConfig, x: Array) -> tuple[Array, dict]:
    """The block's second half: its MLP or MoE on the normed stream (a
    Mamba block without an MLP passes x through)."""
    if "mlp" in p:
        y = rmsnorm_apply(p["ln2"], x, cfg.rms_eps)
        return _res(cfg, x, mlp_apply(p["mlp"], cfg, y)), _zero_aux()
    if "moe" in p:
        moe_y, aux = moe_apply(p["moe"], cfg,
                               rmsnorm_apply(p["ln2"], x, cfg.rms_eps))
        return x + moe_y, aux
    return x, _zero_aux()


def block_apply(p: dict, cfg: ModelConfig, x: Array, positions: Array,
                *, causal: bool = True, kind: Optional[str] = None
                ) -> tuple[Array, dict]:
    """Full-sequence block forward (train). Returns (x, moe_aux).

    With ``cfg.seq_shard`` (Megatron-SP) the residual stream lives
    SEQUENCE-SHARDED over 'model'; norms run in the sharded region (they are
    per-token), and each attention/FFN module is entered through an
    all-gather and exited through a reduce-scatter — both explicit, both on
    bf16 activations (left to itself GSPMD gathers f32 weights instead).
    """
    kind = kind or _block_kind(cfg)
    sp = cfg.seq_shard
    x = shard_act(x, "dp", "model" if sp else None, None)
    xn = rmsnorm_apply(p["ln1"], x, cfg.rms_eps)
    if kind in ("attn_mlp", "attn_moe"):
        h = attn_apply(p["attn"], cfg, xn, positions, causal=causal)
    else:  # mamba
        h = mamba_apply(p["mamba"], cfg, xn)
    x, aux = _ffn_tail(p, cfg, _res(cfg, x, h))
    return shard_act(x, "dp", "model" if sp else None, None), aux


def block_prefill(p: dict, cfg: ModelConfig, x: Array, positions: Array,
                  *, kind: Optional[str] = None,
                  n_valid: Optional[Array] = None) -> tuple[Array, Any]:
    """Block forward that also emits its cache entry. ``n_valid``: the
    prompts' true lengths (right pads after them leave SSM state alone)."""
    kind = kind or _block_kind(cfg)
    x = shard_act(x, "dp", None, None)
    xn = rmsnorm_apply(p["ln1"], x, cfg.rms_eps)
    if kind in ("attn_mlp", "attn_moe"):
        h, st = attn_prefill(p["attn"], cfg, xn, positions)
    else:
        h, st = mamba_apply(p["mamba"], cfg, xn, return_state=True,
                            n_valid=n_valid)
    return _ffn_tail(p, cfg, _res(cfg, x, h))[0], st


def block_decode(p: dict, cfg: ModelConfig, x: Array, cache_l: Any,
                 cache_len: Array) -> tuple[Array, Any]:
    kind = _block_kind(cfg)
    xn = rmsnorm_apply(p["ln1"], x, cfg.rms_eps)
    if kind in ("attn_mlp", "attn_moe"):
        h, st = attn_decode(p["attn"], cfg, xn, cache_len, cache_l[0],
                            cache_l[1], cache_len)
    else:
        h, st = mamba_decode_step(p["mamba"], cfg, xn, cache_l)
    return _ffn_tail(p, cfg, _res(cfg, x, h))[0], st


def block_append(p: dict, cfg: ModelConfig, x: Array, cache_l: Any,
                 cache_len: Array, *, kind: Optional[str] = None,
                 n_valid: Optional[Array] = None) -> tuple[Array, Any]:
    """Chunked-prefill block forward: C tokens appended to an existing
    cache entry (the multi-token generalization of ``block_decode``);
    ``n_valid``: the chunk's valid tokens, right pads after them."""
    kind = kind or _block_kind(cfg)
    xn = rmsnorm_apply(p["ln1"], x, cfg.rms_eps)
    if kind in ("attn_mlp", "attn_moe"):
        h, st = attn_append(p["attn"], cfg, xn, cache_l[0], cache_l[1],
                            cache_len)
    else:
        h, st = mamba_apply(p["mamba"], cfg, xn, init_state=cache_l,
                            return_state=True, n_valid=n_valid)
    return _ffn_tail(p, cfg, _res(cfg, x, h))[0], st


def _at(st: Any, g: Array, i: Array) -> Any:
    """Layer [g, i] of a run's state stack [periods, run length, ...]."""
    return jax.tree_util.tree_map(lambda a: a[g, i], st)


def _put(st: Any, g: Array, i: Array, new: Any) -> Any:
    """A run's state stack with layer [g, i] replaced by ``new``."""
    return jax.tree_util.tree_map(
        lambda a, n: jax.lax.dynamic_update_slice(
            a, n.astype(a.dtype)[None, None], (g, i) + (0,) * n.ndim),
        st, new)


def _pattern_decode(cfg: ModelConfig, cache_len: Array, kind: str, p: dict,
                    x: Array, st: Any, g: Array, i: Array):
    """One layer of the pattern's pool decode: a Mamba layer rewrites its
    SSM and conv state in its run's stack; an attention layer reads its KV
    rows where they lie and emits the new token's K/V rows, which
    ``_put_kv_rows`` writes once the scan is over (the KV stacks then keep
    the layout they have outside the scan)."""
    xn = rmsnorm_apply(p["ln1"], x, cfg.rms_eps)
    rows = None
    if kind == "mamba":
        h, new = mamba_decode_step(p["mamba"], cfg, xn, _at(st, g, i))
        st = _put(st, g, i, new)
    else:
        h, rows = attn_decode(p["attn"], cfg, xn, cache_len, st[0][g, i],
                              st[1][g, i], cache_len, rows=True)
    return _ffn_tail(p, cfg, _res(cfg, x, h))[0], st, rows


def _put_kv_rows(layers: dict, rows: dict, cache_len: Array) -> dict:
    """Write each attention layer's new K/V row of every slot at that
    slot's length, in place: one small update per (layer, slot)."""
    def put(stack, new):             # [P, N, B, S, H, D], [P, N, B, 1, H, D]
        n_per, n, b = new.shape[:3]
        lens = jnp.broadcast_to(jnp.asarray(cache_len), (b,))

        def one(t, a):
            g, i, s = t // (n * b), t // b % n, t % b
            return jax.lax.dynamic_update_slice(
                a, new[g, i, s][None, None, None].astype(a.dtype),
                (g, i, s, lens[s], 0, 0))
        return jax.lax.fori_loop(0, n_per * n * b, one, stack)

    return dict(layers, attn=tuple(
        tuple(put(a, r) for a, r in zip(kv, new))
        for kv, new in zip(layers["attn"], rows["attn"])))


def _pad_kv_layers(layers: Any, max_len: int) -> Any:
    """Pad KV leaves (seq axis = -3) to max_len; mamba states untouched."""

    def pad(path, leaf):
        ps = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                      for k in path)
        # int32 leaves are packed qk_spiking spike-state words: one row per
        # token by construction (O(1) in sequence length) — never padded
        if "ssm" in ps or "conv" in ps or leaf.ndim < 4 \
                or leaf.dtype == jnp.int32:
            return leaf
        s = leaf.shape[-3]
        if s >= max_len or s == 0:
            return leaf
        width = [(0, 0)] * leaf.ndim
        width[-3] = (0, max_len - s)
        return jnp.pad(leaf, width)

    return jax.tree_util.tree_map_with_path(pad, layers)


# ================================================================== LM model
class LM:
    """Decoder-only LM over the unified block zoo (all families but encdec)."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    # ------------------------------------------------------------------ init
    def init(self, rng: Array) -> dict:
        cfg = self.cfg
        r_emb, r_blocks, r_head, r_shared, r_vis = jax.random.split(rng, 5)
        params: dict = {
            "embed": embedding_init(r_emb, cfg.vocab_size, cfg.d_model,
                                    cfg.param_dtype),
            "blocks": self._init_blocks(r_blocks),
            "final_norm": rmsnorm_init(cfg.d_model, cfg.param_dtype),
        }
        if not cfg.tie_embeddings:
            params["head"] = dense_init(r_head, cfg.d_model, cfg.vocab_size,
                                        dtype=cfg.param_dtype)
        if cfg.family == "hybrid" and not cfg.layer_types:
            params["shared_attn"] = shared_attn_init(r_shared, cfg)
        if cfg.family == "vlm":
            params["vision_proj"] = dense_init(r_vis, cfg.d_vision,
                                               cfg.d_model,
                                               dtype=cfg.param_dtype)
        return params

    def _init_blocks(self, rng: Array) -> dict:
        """Stacked block params: one stack over all layers, or under a
        ``layer_types`` pattern the layout ``_pattern`` scans."""
        cfg = self.cfg
        if not cfg.layer_types:
            return jax.vmap(lambda r: block_init(r, cfg))(
                jax.random.split(rng, cfg.n_layers))
        n_per = cfg.n_layers // cfg.layer_period
        out: dict = {}
        for key, kind, _, n in _pattern_runs(cfg):
            rng, r = jax.random.split(rng)
            stack = jax.vmap(lambda r, kind=kind: block_init(r, cfg, kind))(
                jax.random.split(r, n_per * n))
            out.setdefault(key, []).append(jax.tree_util.tree_map(
                lambda a: a.reshape(n_per, n, *a.shape[1:]), stack))
        return {k: tuple(v) for k, v in out.items()}

    def serving_params(self, params: dict) -> dict:
        """``params`` as the served programs read them: the FFN weights
        already in ``cfg.dtype``, so no jitted step casts a whole weight
        stack on every call. The outputs are bit-identical; the caller's
        tree is not changed, and every leaf but those is the same object.

        Left as they are: the embedding (the tied readout reads it in
        float32), the norm scales (read in float32), the MoE router (read in
        float32) and the attention weights (under fused policies the event
        kernels accumulate in float32 from float32 tiles)."""
        cfg = self.cfg
        if cfg.param_dtype == cfg.dtype:
            return params
        out = dict(params)
        blocks = params["blocks"]
        if cfg.layer_types:
            out["blocks"] = {k: tuple(ffn_serving_params(r, cfg.dtype)
                                      for r in runs)
                             for k, runs in blocks.items()}
        else:
            out["blocks"] = ffn_serving_params(blocks, cfg.dtype)
        if "shared_attn" in params:
            out["shared_attn"] = ffn_serving_params(params["shared_attn"],
                                                    cfg.dtype)
        return out

    def _pattern(self, blocks: dict, x: Array, layer, states=None
                 ) -> tuple[Array, dict]:
        """The ``layer_types`` stack as ONE scan over whole periods, and
        inside a period one scan per run of like layers, so the HLO holds
        one period. ``blocks``, ``states`` (the per-layer cache, or None)
        and the outputs share one layout, ``{"mamba": runs, "attn":
        runs}``: per type, one entry per run of that type in the period,
        stacked [periods, run length, ...].

        ``layer(kind, p_l, x, run_st, g, i) -> (x, run_st, out)`` runs
        layer ``i`` of period ``g`` of a run: ``run_st`` is the run's whole
        state stack, which the layer may update where it lies (in place,
        when the caller donates the cache); ``out`` is what the layer
        emits (a prefill's new state, a decode's new KV rows), stacked like
        ``blocks``. Weights and state are read at [g, i] of their stacks
        inside the run's scan, one slice per layer. Returns (x, states,
        outputs)."""
        runs = _pattern_runs(self.cfg)

        def period_body(carry, _):
            x, st, g = carry
            st = None if st is None else {k: list(v) for k, v in st.items()}
            outs = {key: [] for key, _, _, _ in runs}
            for key, kind, j, n in runs:
                def layer_body(c, i, key=key, kind=kind, j=j):
                    x, run_st = c
                    p_l = jax.tree_util.tree_map(lambda a: a[g, i],
                                                 blocks[key][j])
                    x, run_st, out = layer(kind, p_l, x, run_st, g, i)
                    return (x, run_st), out

                run_st = None if st is None else st[key][j]
                (x, run_st), out = jax.lax.scan(layer_body, (x, run_st),
                                                jnp.arange(n))
                if st is not None:
                    st[key][j] = run_st
                outs[key].append(out)
            st = None if st is None else {k: tuple(v) for k, v in st.items()}
            return (x, st, g + 1), {k: tuple(v) for k, v in outs.items()}

        n_per = self.cfg.n_layers // self.cfg.layer_period
        (x, states, _), outs = jax.lax.scan(
            period_body, (x, states, jnp.int32(0)), None, length=n_per)
        return x, states, outs

    # ------------------------------------------------------------ embeddings
    def _embed_tokens(self, params: dict, tokens: Array) -> Array:
        """Token embeddings in the compute dtype, times the config's
        embedding multiplier."""
        cfg = self.cfg
        x = embedding_lookup(params["embed"], tokens, cfg.dtype)
        m = cfg.embedding_multiplier
        return x if m == 1.0 else x * m

    def _embed(self, params: dict, batch: dict) -> tuple[Array, Array]:
        """-> (x [B,S,D], positions [B,S])."""
        cfg = self.cfg
        x = self._embed_tokens(params, batch["tokens"])
        if cfg.family == "vlm" and "img_embeds" in batch:
            img = batch["img_embeds"].astype(cfg.dtype)
            if cfg.vision_pool_window > 1:
                img = self._patch_merge(img)
            img = dense_apply(params["vision_proj"], img)
            x = jnp.concatenate([img, x], axis=1)
        b, s, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        return x, positions

    def _patch_merge(self, img: Array) -> Array:
        """W2TTFS patch merge (paper C2 applied to the vision frontend):
        spiking mode pools windows by SPIKE COUNT x unit scale — the WTFC
        datapath; ANN mode mean-pools. img: [B, N, Dv], N = g*g patches."""
        cfg = self.cfg
        b, n, dv = img.shape
        w = cfg.vision_pool_window
        g = int(round(n ** 0.5))
        grid = img.reshape(b, g, g, dv)
        if cfg.spiking:
            spikes = maybe_spike(grid, True, cfg.lif)
            cnt = window_counts(spikes, w)               # [B,g/w,g/w,Dv]
            pooled = cnt.astype(img.dtype) / float(w * w)
        else:
            pooled = grid.reshape(b, g // w, w, g // w, w, dv).mean(axis=(2, 4))
        return pooled.reshape(b, (g // w) ** 2, dv)

    # ----------------------------------------------------------- stack (train)
    def _stack_train(self, params: dict, x: Array, positions: Array) -> tuple[Array, dict]:
        cfg = self.cfg

        def body_plain(x, p_l):
            y, aux = block_apply(p_l, cfg, x, positions)
            return y, aux

        body = self._maybe_remat(body_plain)

        if cfg.layer_types:
            def layer(kind, p_l, x, st, g, i):
                return self._maybe_remat(lambda p, x: block_apply(
                    p, cfg, x, positions, kind=kind)[0])(p_l, x), st, None
            x = self._pattern(params["blocks"], x, layer)[0]
            aux = _zero_aux()           # no MoE in a pattern
        elif cfg.family == "hybrid":
            x, aux = self._hybrid_train(params, x, positions, body)
        elif cfg.scan_layers:
            def scan_body(carry, p_l):
                y, aux = body(carry, p_l)
                return y, aux
            x, auxs = jax.lax.scan(scan_body, x, params["blocks"])
            aux = jax.tree_util.tree_map(jnp.sum, auxs)
        else:
            aux = _zero_aux()
            for i in range(cfg.n_layers):
                p_l = jax.tree_util.tree_map(lambda a: a[i], params["blocks"])
                x, a = body(x, p_l)
                aux = jax.tree_util.tree_map(jnp.add, aux, a)
        return rmsnorm_apply(params["final_norm"], x, cfg.rms_eps), aux

    def _hybrid_train(self, params, x, positions, body):
        """Zamba2 grouped scan: shared attention block before each group of
        ``attn_every`` mamba layers. n_layers must divide into groups."""
        cfg = self.cfg
        k = cfg.attn_every
        ng = cfg.n_layers // k
        shared = params["shared_attn"]
        blocks_g = jax.tree_util.tree_map(
            lambda a: a.reshape(ng, k, *a.shape[1:]), params["blocks"])

        def attn_site(x):
            h = attn_apply(shared["attn"], cfg,
                           rmsnorm_apply(shared["ln1"], x, cfg.rms_eps),
                           positions, causal=True)
            x = x + h
            y = rmsnorm_apply(shared["ln2"], x, cfg.rms_eps)
            return x + mlp_apply(shared["mlp"], cfg, y)

        def group_body(carry, p_g):
            x = attn_site(carry)
            x, auxs = jax.lax.scan(lambda c, p: body(c, p), x, p_g)
            return x, jax.tree_util.tree_map(jnp.sum, auxs)

        x, auxs = jax.lax.scan(group_body, x, blocks_g)
        return x, jax.tree_util.tree_map(jnp.sum, auxs)

    def _maybe_remat(self, fn):
        cfg = self.cfg
        if cfg.remat == "full":
            return jax.checkpoint(fn,
                                  policy=jax.checkpoint_policies.nothing_saveable)
        if cfg.remat == "dots":
            return jax.checkpoint(
                fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
        return fn

    # -------------------------------------------------------------- readout
    def _logits(self, params: dict, x: Array) -> Array:
        if self.cfg.tie_embeddings:
            logits = embedding_logits(params["embed"], x)
        else:
            logits = dense_apply(params["head"], x.astype(jnp.float32))
        scale = self.cfg.logits_scaling
        return logits if scale == 1.0 else logits / scale

    # ------------------------------------------------------------------ loss
    def loss(self, params: dict, batch: dict) -> tuple[Array, dict]:
        """Causal next-token CE (+ MoE aux). batch['tokens']: [B, S]."""
        cfg = self.cfg
        x, positions = self._embed(params, batch)
        x, aux = self._stack_train(params, x, positions)
        # predict token t+1 from position t (text positions only for vlm)
        n_pred = batch["tokens"].shape[1] - 1
        hs = x[:, -n_pred - 1:-1, :]
        targets = batch["tokens"][:, 1:]
        logits = self._logits(params, hs)
        logits = shard_act(logits, "dp", None, "model")
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        loss = jnp.mean(nll)
        metrics = {"nll": loss}
        if cfg.family == "moe":
            loss = (loss + cfg.router_aux_weight * aux["load_balance"]
                    + 1e-3 * aux["router_z"])
            metrics.update({k: v for k, v in aux.items()})
        metrics["loss"] = loss
        return loss, metrics

    # --------------------------------------------------------------- prefill
    def prefill(self, params: dict, batch: dict,
                return_all_logits: bool = False,
                max_len: int = 0) -> tuple[Array, Any]:
        """Full-context forward -> (last-position logits [B,V], cache).
        ``return_all_logits`` gives [B,S,V] (serving engines pick the last
        REAL token's position when prompts are right-padded).
        ``max_len`` > S pads the KV cache with headroom so decode_step can
        append new tokens directly. ``batch["lengths"]`` ([B], optional)
        gives right-padded prompts' true lengths: the pads then leave the
        SSM state alone (attention pads are causal-inert anyway)."""
        cfg = self.cfg
        x, positions = self._embed(params, batch)
        n_valid = batch.get("lengths")

        if cfg.layer_types:
            def layer(kind, p_l, x, st, g, i):
                x, out = block_prefill(p_l, cfg, x, positions, kind=kind,
                                       n_valid=n_valid)
                return x, st, out
            x, _, cache = self._pattern(params["blocks"], x, layer)
        elif cfg.family == "hybrid":
            x, cache = self._hybrid_prefill(params, x, positions, n_valid)
        elif cfg.scan_layers:
            def scan_body(carry, p_l):
                y, c = block_prefill(p_l, cfg, carry, positions,
                                     n_valid=n_valid)
                return y, c
            x, cache = jax.lax.scan(scan_body, x, params["blocks"])
        else:
            entries = []
            for i in range(cfg.n_layers):
                p_l = jax.tree_util.tree_map(lambda a: a[i], params["blocks"])
                x, c = block_prefill(p_l, cfg, x, positions, n_valid=n_valid)
                entries.append(c)
            cache = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *entries)
        x = rmsnorm_apply(params["final_norm"], x, cfg.rms_eps)
        if return_all_logits:
            logits = self._logits(params, x)
        else:
            logits = self._logits(params, x[:, -1:, :])[:, 0, :]
        if max_len:
            cache = _pad_kv_layers(cache, max_len)
        cache = {"layers": cache,
                 "len": jnp.array(positions.shape[1], jnp.int32)}
        return logits, cache

    def _hybrid_prefill(self, params, x, positions, n_valid=None):
        cfg = self.cfg
        k = cfg.attn_every
        ng = cfg.n_layers // k
        shared = params["shared_attn"]
        blocks_g = jax.tree_util.tree_map(
            lambda a: a.reshape(ng, k, *a.shape[1:]), params["blocks"])

        def group_body(carry, p_g):
            x = carry
            h, kv = attn_prefill(shared["attn"], cfg,
                                 rmsnorm_apply(shared["ln1"], x, cfg.rms_eps),
                                 positions)
            x = x + h
            x = x + mlp_apply(shared["mlp"], cfg,
                              rmsnorm_apply(shared["ln2"], x, cfg.rms_eps))
            x, states = jax.lax.scan(
                lambda c, p: block_prefill(p, cfg, c, positions,
                                           n_valid=n_valid), x, p_g)
            return x, {"attn": kv, "mamba": states}

        x, cache = jax.lax.scan(group_body, x, blocks_g)
        return x, cache

    # ----------------------------------------------------------- decode step
    def decode_step(self, params: dict, tokens: Array, cache: dict
                    ) -> tuple[Array, dict]:
        """One token for every sequence. tokens: [B, 1] int32.
        cache['len'] may be a scalar or a per-sequence [B] vector (slot
        pools in the serving engine)."""
        cfg = self.cfg
        cache_len = cache["len"]
        x = self._embed_tokens(params, tokens)
        x = shard_act(x, "dp", None, None)

        if cfg.layer_types:
            x, layers, rows = self._pattern(
                params["blocks"], x,
                functools.partial(_pattern_decode, cfg, cache_len),
                cache["layers"])
            layers = _put_kv_rows(layers, rows, cache_len)
        elif cfg.family == "hybrid":
            x, layers = self._hybrid_decode(params, x, cache)
        elif cfg.scan_layers:
            def scan_body(carry, inp):
                p_l, c_l = inp
                y, nc = block_decode(p_l, cfg, carry, c_l, cache_len)
                return y, nc
            x, layers = jax.lax.scan(scan_body, x,
                                     (params["blocks"], cache["layers"]))
        else:
            entries = []
            for i in range(cfg.n_layers):
                p_l = jax.tree_util.tree_map(lambda a: a[i], params["blocks"])
                c_l = jax.tree_util.tree_map(lambda a: a[i], cache["layers"])
                x, nc = block_decode(p_l, cfg, x, c_l, cache_len)
                entries.append(nc)
            layers = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *entries)
        x = rmsnorm_apply(params["final_norm"], x, cfg.rms_eps)
        logits = self._logits(params, x)[:, 0, :]
        return logits, {"layers": layers, "len": cache_len + 1}

    def _hybrid_decode(self, params, x, cache):
        cfg = self.cfg
        k = cfg.attn_every
        ng = cfg.n_layers // k
        shared = params["shared_attn"]
        cache_len = cache["len"]
        blocks_g = jax.tree_util.tree_map(
            lambda a: a.reshape(ng, k, *a.shape[1:]), params["blocks"])

        def group_body(carry, inp):
            x = carry
            p_g, c_g = inp
            h, (ck, cv) = attn_decode(
                shared["attn"], cfg,
                rmsnorm_apply(shared["ln1"], x, cfg.rms_eps),
                cache_len, c_g["attn"][0], c_g["attn"][1], cache_len)
            x = x + h
            x = x + mlp_apply(shared["mlp"], cfg,
                              rmsnorm_apply(shared["ln2"], x, cfg.rms_eps))
            x, states = jax.lax.scan(
                lambda c, pc: block_decode(pc[0], cfg, c, pc[1], cache_len),
                x, (p_g, c_g["mamba"]))
            return x, {"attn": (ck, cv), "mamba": states}

        x, layers = jax.lax.scan(group_body, x, (blocks_g, cache["layers"]))
        return x, layers

    # -------------------------------------------------------- chunked prefill
    def prefill_chunk(self, params: dict, tokens: Array, cache: dict,
                      n_valid: Optional[Array] = None) -> tuple[Array, dict]:
        """Continued prefill: C tokens appended to an existing cache.

        tokens: [B, C] int32; cache: an ``init_cache``-layout pytree whose
        ``cache['len']`` (scalar or [B]) is the number of positions already
        prefilled. Returns (all-position logits [B, C, V], updated cache
        with len advanced by C). Feeding a prompt through this in chunks is
        bit-identical to one blocking ``prefill`` pass — the serving
        engine's elastic-FIFO prefill unit (decode ticks interleave between
        chunks, so one long prompt cannot stall the decode pipeline).
        ``n_valid`` (scalar or [B], optional): the chunk's valid tokens;
        the right pads after them leave the SSM state alone.
        """
        cfg = self.cfg
        cache_len = cache["len"]
        x = self._embed_tokens(params, tokens)
        x = shard_act(x, "dp", None, None)

        if cfg.layer_types:
            def layer(kind, p_l, x, st, g, i):
                x, new = block_append(p_l, cfg, x, _at(st, g, i), cache_len,
                                      kind=kind, n_valid=n_valid)
                return x, _put(st, g, i, new), None
            x, layers, _ = self._pattern(params["blocks"], x, layer,
                                         cache["layers"])
        elif cfg.family == "hybrid":
            x, layers = self._hybrid_append(params, x, cache, n_valid)
        elif cfg.scan_layers:
            def scan_body(carry, inp):
                p_l, c_l = inp
                y, nc = block_append(p_l, cfg, carry, c_l, cache_len,
                                     n_valid=n_valid)
                return y, nc
            x, layers = jax.lax.scan(scan_body, x,
                                     (params["blocks"], cache["layers"]))
        else:
            entries = []
            for i in range(cfg.n_layers):
                p_l = jax.tree_util.tree_map(lambda a: a[i], params["blocks"])
                c_l = jax.tree_util.tree_map(lambda a: a[i], cache["layers"])
                x, nc = block_append(p_l, cfg, x, c_l, cache_len,
                                     n_valid=n_valid)
                entries.append(nc)
            layers = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *entries)
        x = rmsnorm_apply(params["final_norm"], x, cfg.rms_eps)
        logits = self._logits(params, x)
        return logits, {"layers": layers, "len": cache_len + tokens.shape[1]}

    def _hybrid_append(self, params, x, cache, n_valid=None):
        cfg = self.cfg
        k = cfg.attn_every
        ng = cfg.n_layers // k
        shared = params["shared_attn"]
        cache_len = cache["len"]
        blocks_g = jax.tree_util.tree_map(
            lambda a: a.reshape(ng, k, *a.shape[1:]), params["blocks"])

        def group_body(carry, inp):
            x = carry
            p_g, c_g = inp
            h, (ck, cv) = attn_append(
                shared["attn"], cfg,
                rmsnorm_apply(shared["ln1"], x, cfg.rms_eps),
                c_g["attn"][0], c_g["attn"][1], cache_len)
            x = x + h
            x = x + mlp_apply(shared["mlp"], cfg,
                              rmsnorm_apply(shared["ln2"], x, cfg.rms_eps))
            x, states = jax.lax.scan(
                lambda c2, pc: block_append(pc[0], cfg, c2, pc[1], cache_len,
                                            n_valid=n_valid),
                x, (p_g, c_g["mamba"]))
            return x, {"attn": (ck, cv), "mamba": states}

        x, layers = jax.lax.scan(group_body, x, (blocks_g, cache["layers"]))
        return x, layers

    # ------------------------------------------------------------ cache spec
    def init_cache(self, batch_size: int, max_len: int) -> dict:
        """Zero cache pytree (ShapeDtypeStruct-compatible via eval_shape)."""
        cfg = self.cfg
        dh = cfg.resolved_head_dim
        hkv = cfg.n_kv_heads or cfg.n_heads
        kv_dtype = (jnp.float8_e4m3fn if cfg.kv_dtype == "f8_e4m3"
                    else cfg.dtype)

        def attn_entry(lead):
            if cfg.attention_kind == "qk_spiking":
                empty = jnp.zeros((lead, batch_size, 0, hkv, dh), kv_dtype)
                if cfg.exec_policy.packed:
                    # per-slot spike state, BIT-PACKED (32 spikes/int32
                    # word): one row of masked-attention spikes per layer —
                    # O(1) in sequence length, 8x smaller than int8
                    from .attention import qk_spike_state_width
                    words = jnp.zeros(
                        (lead, batch_size, 1, 1, qk_spike_state_width(cfg)),
                        jnp.int32)
                    return (words, empty)
                # two buffers, not one twice: the engine donates the pool
                return (empty, jnp.zeros_like(empty))
            shp = (lead, batch_size, max_len, hkv, dh)
            return (jnp.zeros(shp, kv_dtype), jnp.zeros(shp, kv_dtype))

        def mamba_entry(lead):
            st = mamba_init_state(cfg, batch_size, dtype=cfg.dtype)
            return jax.tree_util.tree_map(
                lambda a: jnp.zeros((lead, *a.shape), a.dtype), st)

        if cfg.layer_types:
            # two kinds of state side by side: SSM and conv state per Mamba
            # layer (O(1) in length), a KV cache per attention layer, in
            # ``_pattern``'s layout
            n_per = cfg.n_layers // cfg.layer_period
            entry = {"mamba": mamba_entry, "attn": attn_entry}
            layers: dict = {}
            for key, _, _, n in _pattern_runs(cfg):
                layers.setdefault(key, []).append(jax.tree_util.tree_map(
                    lambda a: a.reshape(n_per, n, *a.shape[1:]),
                    entry[key](n_per * n)))
            layers = {k: tuple(v) for k, v in layers.items()}
        elif cfg.family in ("dense", "moe", "vlm"):
            layers = attn_entry(cfg.n_layers)
        elif cfg.family == "ssm":
            layers = mamba_entry(cfg.n_layers)
        elif cfg.family == "hybrid":
            ng = cfg.n_layers // cfg.attn_every
            att = attn_entry(ng)
            mam = mamba_entry(cfg.n_layers)
            mam = jax.tree_util.tree_map(
                lambda a: a.reshape(ng, cfg.attn_every, *a.shape[1:]), mam)
            layers = {"attn": att, "mamba": mam}
        else:
            raise ValueError(cfg.family)
        # len = max_len - 1: the cache is "full", the next token writes the
        # final slot — so a decode step attends to exactly ``max_len`` keys.
        return {"layers": layers,
                "len": jnp.array(max(max_len - 1, 0), jnp.int32)}

    # ------------------------------------------------------------- input spec
    def input_specs(self, shape: ShapeSpec) -> dict:
        """ShapeDtypeStruct stand-ins for the step function being lowered."""
        cfg = self.cfg
        b = shape.global_batch
        sds = jax.ShapeDtypeStruct
        if shape.kind == "train":
            batch = {"tokens": sds((b, self._text_len(shape.seq_len)), jnp.int32)}
            if cfg.family == "vlm":
                batch["img_embeds"] = sds(
                    (b, cfg.n_img_tokens, cfg.d_vision), jnp.bfloat16)
            return {"batch": batch}
        if shape.kind == "prefill":
            batch = {"tokens": sds((b, self._text_len(shape.seq_len)), jnp.int32)}
            if cfg.family == "vlm":
                batch["img_embeds"] = sds(
                    (b, cfg.n_img_tokens, cfg.d_vision), jnp.bfloat16)
            return {"batch": batch}
        # decode: one new token against a seq_len cache
        cache = jax.eval_shape(lambda: self.init_cache(b, shape.seq_len))
        return {"tokens": sds((b, 1), jnp.int32), "cache": cache}

    def _text_len(self, seq_len: int) -> int:
        cfg = self.cfg
        if cfg.family == "vlm":
            n_img = cfg.n_img_tokens
            if cfg.vision_pool_window > 1:
                n_img //= cfg.vision_pool_window ** 2
            return seq_len - n_img
        return seq_len
