"""Public wrapper: padding + vld_cnt (PipeSDA analogue) + kernel dispatch."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...core.events import (PackedSpikes, block_count_map_2d, compact_kmap,
                            pad_to_blocks, vld_or_compute,
                            word_occupancy_map_dense)
from ..contract import KernelContract, declare, matmul_vmem
from .backward import (spike_matmul_dw_gated_pallas, spike_matmul_dw_pallas,
                       spike_matmul_dx_pallas)
from .spike_matmul import spike_matmul_gated_pallas, spike_matmul_pallas

Array = jax.Array

CONTRACT = declare(KernelContract(
    family="spike_matmul", ops=("matmul",),
    skips=("dense", "gated", "two_level"), grad=True,
    grad_ops=("matmul",),
    vmem_bytes=matmul_vmem))

# byte-skip strategies shared by spike_matmul and fused_pe:
#   dense     — full streaming, @pl.when skips MXU only (the PR-5 behaviour)
#   gated     — compacted-grid tile streaming: silent blocks never DMA'd
#   two_level — gated + word-occupancy bitmap elides silent 32-col stripes
SKIP_MODES = ("dense", "gated", "two_level")


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def check_block_contract(ps: PackedSpikes, block_m: int, block_k: int,
                         what: str = "packed operand") -> None:
    """The packed-operand block-shape contract: a PackedSpikes pins its tile
    grid at pack time; the consuming kernel must tile identically or its
    vld_cnt map is routing garbage. The words themselves enter kernels a
    whole ``block_m``-row block at a time (``kernels.words``), so
    ``block_m`` also fixes their row padding; ``block_k`` matters only to
    the vld_cnt/occ maps."""
    if (ps.block_m, ps.block_k) != (block_m, block_k):
        raise ValueError(
            f"{what} was packed on (block_m={ps.block_m}, "
            f"block_k={ps.block_k}) but the kernel is tiling on "
            f"(block_m={block_m}, block_k={block_k}). A packed tensor's "
            f"vld_cnt/occ maps are only valid at its own block sizes — "
            f"re-pack it, or pass matching block sizes.")


def check_skip(skip: str) -> None:
    if skip not in SKIP_MODES:
        raise ValueError(f"skip={skip!r} not in {SKIP_MODES}")


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k",
                                             "skip", "interpret"))
def spike_matmul(x: Array | PackedSpikes, w: Array, *,
                 vld_cnt: Array | None = None,
                 block_m: int = 128,
                 block_n: int = 128, block_k: int = 128,
                 skip: str = "dense",
                 interpret: bool | None = None) -> Array:
    """Event-driven spike matmul. x: [M,K] {0,1} (any dtype) or a
    ``PackedSpikes`` (bit-packed HBM format); w: [K,N].

    Pads to block multiples, computes the per-block event-count map (the
    PipeSDA routing metadata), and invokes the Pallas kernel. On CPU the
    kernel body runs in interpret mode (used by the allclose tests).

    ``vld_cnt``: optional precomputed [M/bm, K/bk] count map — pass the
    ``vld_next`` emitted by a previous ``fused_pe`` layer (same block sizes)
    to skip the metadata reduction pass over ``x`` entirely. A PackedSpikes
    operand carries both payload and metadata, so neither padding nor a
    count pass happens: words stream to VMEM (8x fewer HBM bytes) and
    K-tiles are unpacked right before the MXU.

    ``skip``: byte-skip strategy (``SKIP_MODES``). ``"gated"`` walks a
    compacted non-silent block list so silent tiles are never fetched from
    HBM; ``"two_level"`` additionally elides silent 32-column stripes inside
    active tiles via the word-occupancy bitmap. ``"dense"`` keeps the full
    stream (right for low-sparsity inputs — no routing overhead).
    """
    check_skip(skip)
    if interpret is None:
        interpret = not _on_tpu()
    if isinstance(x, PackedSpikes):
        check_block_contract(x, block_m, block_k, "spike_matmul x")
        m0, k0 = x.shape[-2:]
        assert len(x.shape) == 2, "spike_matmul takes a 2-D packed operand"
        n0 = w.shape[1]
        wp = pad_to_blocks(w, block_k, block_n)
        kp = x.words.shape[-1] * 32
        if wp.shape[0] < kp:      # logical K padded up to the word grid
            wp = jnp.pad(wp, ((0, kp - wp.shape[0]), (0, 0)))
        vld = x.vld_cnt if vld_cnt is None else vld_cnt
        if skip == "dense":
            out = spike_matmul_pallas(
                x.words, wp, vld,
                block_m=block_m, block_n=block_n, block_k=block_k,
                packed_in=True, interpret=interpret)
        else:
            nact, kmap = compact_kmap(vld)
            occ = x.with_occ().occ if skip == "two_level" else None
            out = spike_matmul_gated_pallas(
                x.words, wp, nact, kmap, occ,
                block_m=block_m, block_n=block_n, block_k=block_k,
                packed_in=True, two_level=(skip == "two_level"),
                interpret=interpret)
        return out[:m0, :n0]
    m0, k0 = x.shape
    n0 = w.shape[1]
    xi = pad_to_blocks(x.astype(jnp.int8), block_m, block_k)
    wp = pad_to_blocks(w, block_k, block_n)
    vld = vld_or_compute(xi, vld_cnt, block_m, block_k)
    if skip == "dense":
        out = spike_matmul_pallas(xi, wp, vld, block_m=block_m,
                                  block_n=block_n, block_k=block_k,
                                  interpret=interpret)
    else:
        nact, kmap = compact_kmap(vld)
        occ = (word_occupancy_map_dense(xi, block_m, block_k)
               if skip == "two_level" else None)
        out = spike_matmul_gated_pallas(
            xi, wp, nact, kmap, occ,
            block_m=block_m, block_n=block_n, block_k=block_k,
            two_level=(skip == "two_level"), interpret=interpret)
    return out[:m0, :n0]


@functools.partial(jax.jit, static_argnames=("surrogate", "alpha", "v_th",
                                             "block_m", "block_n", "block_k",
                                             "interpret"))
def spike_matmul_dx(g: Array, w: Array, v: Array | None = None, *,
                    surrogate: str = "atan", alpha: float = 2.0,
                    v_th: float = 1.0,
                    block_m: int = 128, block_n: int = 128,
                    block_k: int = 128,
                    interpret: bool | None = None):
    """Backward data-gradient: ``dx = (g ⊙ surr'(v - v_th)) @ wᵀ``.

    ``g``: [M, N] cotangent; ``w``: [K, N]; ``v``: optional [M, N] membrane
    pre-activations cached by the fused forward — when given, the surrogate
    pseudo-derivative factor is fused into the kernel's VMEM pass and the
    resulting ``dv`` is emitted as a by-product (the operand the
    weight-gradient, bias-gradient and residual-gradient all share). When
    omitted the backward is a plain transposed linear (dv = g).

    Returns ``(dx [M, K], dv [M, N])``.
    """
    if interpret is None:
        interpret = not _on_tpu()
    m0, n0 = g.shape
    k0 = w.shape[0]
    gp = pad_to_blocks(g.astype(jnp.float32), block_m, block_n)
    wp = pad_to_blocks(w, block_k, block_n)
    vp = (None if v is None
          else pad_to_blocks(v.astype(jnp.float32), block_m, block_n))
    dx, dv = spike_matmul_dx_pallas(
        gp, wp, vp, surrogate=surrogate, alpha=alpha, v_th=v_th,
        block_m=block_m, block_n=block_n, block_k=block_k,
        interpret=interpret)
    return dx[:m0, :k0], dv[:m0, :n0]


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k",
                                             "skip", "interpret"))
def spike_matmul_dw(x: Array | PackedSpikes, g: Array, *,
                    vld_cnt: Array | None = None,
                    block_m: int = 128, block_n: int = 128,
                    block_k: int = 128, skip: str = "dense",
                    interpret: bool | None = None) -> Array:
    """Backward weight-gradient: ``dw = xᵀ @ g``, event-skipped on x.

    ``x`` is the forward's spike operand — dense {0,1} [M, K] or a
    ``PackedSpikes`` whose words stream straight to VMEM (no dense unpack
    round trip through HBM). Silent (m, k) tiles were silent on the way
    forward and stay silent here: ``skip`` applies the same byte-skip
    ladder as the forward, along the TRANSPOSED axis (``"gated"`` walks
    ``compact_kmap(vldᵀ)``; ``"two_level"`` additionally elides silent
    32-row output stripes via the occ bitmap). ``g``: [M, N] cotangent.
    """
    check_skip(skip)
    if interpret is None:
        interpret = not _on_tpu()
    if isinstance(x, PackedSpikes):
        check_block_contract(x, block_m, block_k, "spike_matmul_dw x")
        m0, k0 = x.shape[-2:]
        assert len(x.shape) == 2, "spike_matmul_dw takes a 2-D packed operand"
        n0 = g.shape[1]
        gp = pad_to_blocks(g.astype(jnp.float32), block_m, block_n)
        vld = x.vld_cnt if vld_cnt is None else vld_cnt
        if skip == "dense":
            dw = spike_matmul_dw_pallas(
                x.words, gp, vld,
                block_m=block_m, block_n=block_n, block_k=block_k,
                packed_in=True, interpret=interpret)
        else:
            nact_t, mmap = compact_kmap(vld.T)
            occ = x.with_occ().occ if skip == "two_level" else None
            dw = spike_matmul_dw_gated_pallas(
                x.words, gp, nact_t, mmap, occ,
                block_m=block_m, block_n=block_n, block_k=block_k,
                packed_in=True, two_level=(skip == "two_level"),
                interpret=interpret)
        return dw[:k0, :n0]
    m0, k0 = x.shape
    n0 = g.shape[1]
    xi = pad_to_blocks(x.astype(jnp.int8), block_m, block_k)
    gp = pad_to_blocks(g.astype(jnp.float32), block_m, block_n)
    vld = vld_or_compute(xi, vld_cnt, block_m, block_k)
    if skip == "dense":
        dw = spike_matmul_dw_pallas(
            xi, gp, vld, block_m=block_m, block_n=block_n,
            block_k=block_k, interpret=interpret)
    else:
        nact_t, mmap = compact_kmap(vld.T)
        occ = (word_occupancy_map_dense(xi, block_m, block_k)
               if skip == "two_level" else None)
        dw = spike_matmul_dw_gated_pallas(
            xi, gp, nact_t, mmap, occ,
            block_m=block_m, block_n=block_n, block_k=block_k,
            two_level=(skip == "two_level"), interpret=interpret)
    return dw[:k0, :n0]


def block_sparsity(x: Array, block_m: int = 128, block_k: int = 128) -> Array:
    """Fraction of SKIPPED (all-silent) blocks — the FLOPs saved by the
    event path on this input (reported by Table II/III benchmarks)."""
    xi = pad_to_blocks(x.astype(jnp.int8), block_m, block_k)
    vld = block_count_map_2d(xi, block_m, block_k)
    return jnp.mean((vld == 0).astype(jnp.float32))
