"""Event-skipped Pallas backward for the spike matmul family.

The backward of a spiking linear layer is two transposed contractions:

  dx = dv @ wᵀ          with dv = g ⊙ surr'(v_mem - v_th)
  dw = xᵀ @ dv

The FIRST is dense in the cotangent but lets the surrogate pseudo-
derivative factor fuse into the same VMEM pass that feeds the MXU — one
sweep produces both ``dx`` and the ``dv`` operand the weight-gradient
needs (no separate elementwise pass over [M, N]).

The SECOND is exactly as event-sparse as the forward: ``x`` is the spike
operand, so every (m, k) tile that was silent on the way forward is silent
in ``xᵀ @ dv`` too. The same skip ladder applies — ``dense`` gates the MXU
via the vld count map, ``gated`` walks a COMPACTED active-block list along
the transposed axis (``compact_kmap(vldᵀ)``) so silent tiles are never
DMA'd, and ``two_level`` additionally elides silent 32-column k-stripes
via the word-occupancy bitmap (a silent stripe of x contributes nothing to
output rows [c*32, (c+1)*32)). Packed spike words stream as-is, a row
block at a time (``kernels.words``): the K-tile is unpacked in VMEM
already transposed, right before the MXU issue — no dense
unpack-then-matmul round trip through HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.surrogate import surrogate_grad
from ...core.events import LANE_BITS
from ..gating import accum_tile_t
from ..words import transpose_words, x_operand_spec

Array = jax.Array


def _make_dx_kernel(with_surrogate: bool, surrogate: str, alpha: float,
                    v_th: float):
    def kernel(*refs):
        if with_surrogate:
            g_ref, w_ref, v_ref, dx_ref, dv_ref = refs
        else:
            g_ref, w_ref, dx_ref = refs
        j = pl.program_id(2)

        @pl.when(j == 0)
        def _init():
            dx_ref[...] = jnp.zeros_like(dx_ref)

        g = g_ref[...].astype(jnp.float32)
        if with_surrogate:
            # the surrogate factor fused into the transpose sweep: this
            # tile's dv never exists as a separate [M, N] elementwise pass
            dv = g * surrogate_grad(v_ref[...].astype(jnp.float32) - v_th,
                                    surrogate, alpha)
            dv_ref[...] = dv
        else:
            dv = g
        w = w_ref[...].astype(jnp.float32)
        dx_ref[...] += jnp.dot(dv, w.T, preferred_element_type=jnp.float32)

    return kernel


@functools.partial(jax.jit,
                   static_argnames=("surrogate", "alpha", "v_th", "block_m",
                                    "block_n", "block_k", "interpret"))
def spike_matmul_dx_pallas(g: Array, w: Array, v: Array | None = None, *,
                           surrogate: str = "atan", alpha: float = 2.0,
                           v_th: float = 1.0, block_m: int = 128,
                           block_n: int = 128, block_k: int = 128,
                           interpret: bool = False):
    """dx = (g ⊙ surr'(v - v_th)) @ wᵀ, accumulated over the N grid axis.

    g: [M, N] f32 cotangent; w: [K, N]; v: optional [M, N] membrane
    pre-activations (omit for a plain linear backward — dv degenerates to
    g). Returns ``(dx [M, K], dv [M, N])``; without ``v`` the second output
    is ``g`` itself.
    """
    m, n = g.shape
    k = w.shape[0]
    assert w.shape[1] == n and m % block_m == 0 and n % block_n == 0 \
        and k % block_k == 0, (g.shape, w.shape, block_m, block_n, block_k)
    with_surrogate = v is not None
    grid = (m // block_m, k // block_k, n // block_n)

    g_spec = pl.BlockSpec((block_m, block_n), lambda i, kk, j: (i, j))
    w_spec = pl.BlockSpec((block_k, block_n), lambda i, kk, j: (kk, j))
    in_specs = [g_spec, w_spec]
    out_specs = [pl.BlockSpec((block_m, block_k), lambda i, kk, j: (i, kk))]
    out_shape = [jax.ShapeDtypeStruct((m, k), jnp.float32)]
    operands = [g, w]
    if with_surrogate:
        assert v.shape == (m, n), (v.shape, g.shape)
        in_specs.append(pl.BlockSpec((block_m, block_n),
                                     lambda i, kk, j: (i, j)))
        # each (i, j) dv block is rewritten once per k step — idempotent
        out_specs.append(pl.BlockSpec((block_m, block_n),
                                      lambda i, kk, j: (i, j)))
        out_shape.append(jax.ShapeDtypeStruct((m, n), jnp.float32))
        operands.append(v)

    out = pl.pallas_call(
        _make_dx_kernel(with_surrogate, surrogate, alpha, v_th),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(*operands)
    if with_surrogate:
        return out[0], out[1]
    return out[0], g


def _make_dw_kernel(wpb: int | None):
    def kernel(vld_ref, x_ref, g_ref, o_ref, *scratch):
        kb = pl.program_id(0)
        mb = pl.program_id(2)

        @pl.when(mb == 0)
        def _init():
            o_ref[...] = jnp.zeros_like(o_ref)

        @pl.when(vld_ref[mb, kb] > 0)    # event skip: silent block -> no MXU
        def _accum():
            src = x_ref
            if wpb is not None:          # a new row block every step
                src = scratch[0]
                transpose_words(x_ref, src)
            accum_tile_t(o_ref, src, g_ref, wpb=wpb, kb=kb)

    return kernel


@functools.partial(jax.jit,
                   static_argnames=("block_m", "block_n", "block_k",
                                    "packed_in", "interpret"))
def spike_matmul_dw_pallas(x: Array, g: Array, vld_cnt: Array, *,
                           block_m: int = 128, block_n: int = 128,
                           block_k: int = 128, packed_in: bool = False,
                           interpret: bool = False) -> Array:
    """dw = xᵀ @ g with the forward's vld map gating the MXU.

    x: [M, K] int8 spikes (or [M, K/32] int32 words with ``packed_in``);
    g: [M, N] f32 cotangent; vld_cnt: [M/bm, K/bk] int32 block counts —
    the SAME metadata the forward streamed, reused for free.
    """
    m = x.shape[0]
    k = x.shape[1] * LANE_BITS if packed_in else x.shape[1]
    n = g.shape[1]
    assert g.shape[0] == m and m % block_m == 0 and k % block_k == 0 \
        and n % block_n == 0, (x.shape, g.shape, block_m, block_n, block_k)
    x_spec, scratch, wpb = x_operand_spec(x, packed_in, block_m, block_k,
                                          lambda kk, j, i, vld: (i, kk))
    grid = (k // block_k, n // block_n, m // block_m)
    return pl.pallas_call(
        _make_dw_kernel(wpb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                x_spec,
                pl.BlockSpec((block_m, block_n),
                             lambda kk, j, i, vld: (i, j)),
            ],
            out_specs=pl.BlockSpec((block_k, block_n),
                                   lambda kk, j, i, vld: (kk, j)),
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((k, n), jnp.float32),
        interpret=interpret,
    )(vld_cnt, x, g)


def _make_dw_gated_kernel(wpb: int | None, two_level: bool):
    def kernel(*refs):
        if two_level:
            nact_ref, mmap_ref, occ_ref, x_ref, g_ref, o_ref, *scratch = refs
        else:
            nact_ref, mmap_ref, x_ref, g_ref, o_ref, *scratch = refs
        kb = pl.program_id(0)
        s = pl.program_id(2)

        @pl.when(s == 0)
        def _init():
            o_ref[...] = jnp.zeros_like(o_ref)

        # steps past nact_t[kb] revisit the last active m-block index, so
        # the BlockSpec never changes -> no DMA; the predicate skips the MXU
        @pl.when(s < nact_ref[kb])
        def _accum():
            src = x_ref
            if wpb is not None:
                src = scratch[0]
                transpose_words(x_ref, src)
            accum_tile_t(o_ref, src, g_ref, wpb=wpb, kb=kb,
                         occ_bits=(occ_ref[mmap_ref[kb, s], kb] if two_level
                                   else None))

    return kernel


@functools.partial(jax.jit,
                   static_argnames=("block_m", "block_n", "block_k",
                                    "packed_in", "two_level", "interpret"))
def spike_matmul_dw_gated_pallas(x: Array, g: Array, nact_t: Array,
                                 mmap: Array, occ: Array | None = None, *,
                                 block_m: int = 128, block_n: int = 128,
                                 block_k: int = 128, packed_in: bool = False,
                                 two_level: bool = False,
                                 interpret: bool = False) -> Array:
    """Gated dw = xᵀ @ g: the m grid axis walks ``mmap[kb, s]`` — the
    compacted list of non-silent M-block indices for k-column ``kb``, i.e.
    ``compact_kmap`` applied to the TRANSPOSED vld map — so silent spike
    tiles and their cotangent tiles are never DMA'd. With ``two_level``,
    the word-occupancy bitmap additionally elides silent 32-row output
    stripes inside active tiles.

    x: [M,K] int8 (or [M,K/32] int32 words with ``packed_in``); g: [M,N]
    f32; nact_t: [K/bk] int32; mmap: [K/bk, M/bm] int32; occ: [M/bm, K/bk].
    """
    m = x.shape[0]
    k = x.shape[1] * LANE_BITS if packed_in else x.shape[1]
    n = g.shape[1]
    assert g.shape[0] == m and m % block_m == 0 and k % block_k == 0 \
        and n % block_n == 0, (x.shape, g.shape, block_m, block_n, block_k)
    if two_level:
        assert occ is not None, "two_level gating needs the occ bitmap"
        npf = 3
        scalars = (nact_t, mmap, occ)
    else:
        npf = 2
        scalars = (nact_t, mmap)

    def x_idx(kk, j, s, nact_ref, mmap_ref, *rest):
        return (mmap_ref[kk, s], kk)

    def g_idx(kk, j, s, nact_ref, mmap_ref, *rest):
        return (mmap_ref[kk, s], j)

    x_spec, scratch, wpb = x_operand_spec(x, packed_in, block_m, block_k,
                                          x_idx)
    grid = (k // block_k, n // block_n, m // block_m)
    return pl.pallas_call(
        _make_dw_gated_kernel(wpb, two_level),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=npf,
            grid=grid,
            in_specs=[
                x_spec,
                pl.BlockSpec((block_m, block_n), g_idx),
            ],
            out_specs=pl.BlockSpec((block_k, block_n),
                                   lambda kk, j, s, *refs: (kk, j)),
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((k, n), jnp.float32),
        interpret=interpret,
    )(*scalars, x, g)
