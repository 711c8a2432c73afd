"""Config schema for the model zoo + shape grid.

Every assigned architecture is a ``ModelConfig``; the paper's SNN features
(spiking mode, QK attention, quantization — C1..C4) are first-class flags on
the same config, so any arch can be run as an ANN baseline or a spiking
variant.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax.numpy as jnp

from ..core.lif import LIFConfig
from ..core.quant import QuantConfig
from ..ops.compat import legacy_flags_policy
from ..ops.policy import REFERENCE, ExecutionPolicy


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                      # dense | moe | ssm | hybrid | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int = 0
    n_kv_heads: int = 0
    d_ff: int = 0
    vocab_size: int = 0
    head_dim: int = 0                # 0 -> d_model // n_heads
    qkv_bias: bool = False
    qk_norm: bool = False
    rope_theta: float = 1e6
    rms_eps: float = 1e-6
    tie_embeddings: bool = False
    # --- MoE ---
    n_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0
    n_shared_experts: int = 0        # llama4-style always-on shared expert
    moe_group_size: int = 512        # GShard dispatch group (tokens)
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.01
    # --- SSM (mamba2 / SSD) ---
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_headdim: int = 64
    ssm_conv: int = 4
    ssm_chunk: int = 256
    ssm_ngroups: int = 1
    # --- hybrid (zamba2) ---
    attn_every: int = 0              # shared attention applied every k layers
    # --- hybrid by layer pattern (granitemoehybrid's ``layer_types``) ---
    # one "mamba" | "attention" entry per layer, each layer with its own
    # weights and, when d_ff > 0, its own SwiGLU MLP; () = no pattern
    layer_types: tuple = ()
    # --- published multipliers (granitemoehybrid); defaults change nothing
    position_embedding_type: str = "rope"   # rope | nope (no position code)
    attention_multiplier: float = 0.0       # softmax scale; 0 = head_dim**-0.5
    embedding_multiplier: float = 1.0       # embeddings scaled on lookup
    residual_multiplier: float = 1.0        # each branch scaled before its add
    logits_scaling: float = 1.0             # read-out logits divided by this
    # --- enc-dec (seamless-m4t) ---
    n_enc_layers: int = 0
    d_src: int = 0                   # precomputed frontend embedding dim
    # --- vlm (phi-3-vision) ---
    n_img_tokens: int = 0
    d_vision: int = 0
    vision_pool_window: int = 0      # >0: W2TTFS patch pooling (C2) applies
    # --- paper technique flags ---
    spiking: bool = False            # LIF activations (C3), KD-student mode
    attention_kind: str = "softmax"  # softmax | qk_spiking (C4)
    # policy: how the qk_spiking path executes (repro.ops.ExecutionPolicy
    # or a preset name). "reference" (the None default) is the pure-jnp
    # path; "fused_dense" routes the LIF projections and binary-activation
    # matmuls through the fused-PE / spike_matmul Pallas kernels
    # (deployed inference); "fused_packed" additionally ships every spike
    # tensor bit-packed (32/int32 lane + popcount vld_cnt, ~8x fewer spike
    # bytes) and caches the per-token spike state packed — all three are
    # bit-identical in emitted spikes. Training works under ANY of them:
    # a differentiable policy (``for_training()`` / a "+grad" preset, what
    # launch/train.py --policy requests) keeps the chosen forward and
    # swaps in the surrogate-gradient custom_vjp backward. Read via
    # ``cfg.exec_policy``.
    policy: Optional[Any] = None     # ExecutionPolicy | preset name | None
    # deprecated flag pair -> policy (repro.ops.compat translates + warns)
    use_event_kernels: Optional[bool] = None
    spike_format: Optional[str] = None
    lif: LIFConfig = LIFConfig()
    quant: QuantConfig = QuantConfig()
    # --- numerics / perf knobs (hillclimb surface) ---
    dtype: Any = jnp.bfloat16        # activation/compute dtype
    param_dtype: Any = jnp.float32
    remat: str = "none"              # none | full | dots
    attn_q_block: int = 1024
    attn_kv_block: int = 1024
    flash_threshold: int = 8192      # use chunked attention above this seq len
    scan_layers: bool = True
    # dp_over_model: batch also shards over the 'model' axis (pure-DP/FSDP
    # regime for small archs — weights become ZeRO-3 shards gathered on use)
    dp_over_model: bool = False
    loss_chunk: int = 0              # >0: compute CE over seq chunks (memory)
    # seq_shard: Megatron-SP — activations at block boundaries shard the
    # SEQUENCE dim over 'model'; GSPMD turns the TP all-reduce into
    # reduce-scatter + all-gather and the saved scan carry shrinks /TP
    seq_shard: bool = False
    # decode_cp_axis: shard the decode KV cache's SEQUENCE dim over this
    # mesh axis ('model' pairs with GQA kv-heads that don't divide TP;
    # 'data' is the long-context batch=1 setting). "" = batch-sharded cache.
    decode_cp_axis: str = ""
    # kv_dtype: "" = activation dtype; "f8_e4m3" stores the KV cache in FP8
    # (2x decode HBM traffic cut — the paper's FP8 deployment theme applied
    # to serving)
    kv_dtype: str = ""

    def __post_init__(self):
        # validate + warn on the deprecated flag pair ONCE at construction
        # (dataclasses.replace round-trips re-run this, which is correct:
        # each construction that still passes legacy flags is a legacy use)
        resolved = legacy_flags_policy(
            "ModelConfig", self.policy, self.use_event_kernels,
            self.spike_format)
        if self.policy is not None:
            # normalize preset names so configs hash/compare consistently
            # (they key jit caches in the serving engine)
            object.__setattr__(self, "policy", resolved)
        if self.layer_types and (
                len(self.layer_types) != self.n_layers
                or set(self.layer_types) - {"mamba", "attention"}):
            raise ValueError(
                f"layer_types needs one 'mamba' or 'attention' per layer "
                f"({self.n_layers}), got {self.layer_types}")

    @property
    def exec_policy(self) -> ExecutionPolicy:
        """The resolved ExecutionPolicy (legacy flags translated; default
        "reference")."""
        pol = legacy_flags_policy(
            "ModelConfig", self.policy, self.use_event_kernels,
            self.spike_format, warn=False)
        return pol if pol is not None else REFERENCE

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or (self.d_model // max(self.n_heads, 1))

    @property
    def prefill_chunk_align(self) -> int:
        """Chunked-prefill granularity this family supports while staying
        bit-identical to a blocking prefill. Attention pads are causal-inert
        so any chunk size works; the SSD scan's intra-chunk cumsums change
        with the chunk partition, so ssm/hybrid chunks must land on
        ``ssm_chunk`` boundaries for the chunked scan to decompose exactly
        into the blocking one."""
        return self.ssm_chunk if self.family in ("ssm", "hybrid") else 1

    @property
    def layer_period(self) -> int:
        """Layers in one period of ``layer_types``: the shortest prefix that
        repeats to the whole pattern (the pattern model scans whole
        periods, so its HLO stays one period in size)."""
        t, n = self.layer_types, len(self.layer_types)
        return next(p for p in range(1, n + 1)
                    if n % p == 0 and t == t[:p] * (n // p))

    @property
    def d_inner(self) -> int:        # ssm inner width
        return self.ssm_expand * self.d_model

    @property
    def ssm_nheads(self) -> int:
        return self.d_inner // self.ssm_headdim


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    name: str                        # train_4k | prefill_32k | decode_32k | long_500k
    kind: str                        # train | prefill | decode
    seq_len: int
    global_batch: int


SHAPES: dict[str, ShapeSpec] = {
    "train_4k": ShapeSpec("train_4k", "train", 4_096, 256),
    "prefill_32k": ShapeSpec("prefill_32k", "prefill", 32_768, 32),
    "decode_32k": ShapeSpec("decode_32k", "decode", 32_768, 128),
    "long_500k": ShapeSpec("long_500k", "decode", 524_288, 1),
}

# archs whose attention is quadratic-full -> long_500k is skipped (brief rule)
SUBQUADRATIC_FAMILIES = ("ssm", "hybrid")


def cells_for(cfg: ModelConfig) -> list[str]:
    """The shape cells that apply to an architecture (skips recorded)."""
    out = ["train_4k", "prefill_32k", "decode_32k"]
    if cfg.family in SUBQUADRATIC_FAMILIES or cfg.attention_kind == "qk_spiking":
        out.append("long_500k")
    return out
