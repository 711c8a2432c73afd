"""Event-driven spike matmul kernel (paper C3 adapted to the MXU).

The FPGA design gates single MACs on spike events; a systolic MXU cannot —
the granularity that pays on TPU is the VMEM BLOCK. PipeSDA's event lists
become a per-(m,k)-tile spike-count map ``vld_cnt`` (computed once, scalar-
prefetched into SMEM); ``@pl.when(vld_cnt > 0)`` then skips the whole
block: no VMEM->MXU issue, no FLOPs, for silent tiles. The elastic-FIFO
data-driven outer level is the Pallas grid itself (blocks stream through
VMEM as operands become resident).

  x  : [M, K] int8  spikes (0/1)           — activations
       or, with ``packed_in``, [M, K/32] int32 bit-packed words (the
       event-compressed HBM format, ``core.events.PackedSpikes``),
       streamed a row block at a time (``kernels.words``): the K-tile is
       unpacked in VMEM right before the MXU, so the 8x-smaller
       representation is what crosses HBM
  w  : [K, N] bf16/f32 weights
  out: [M, N] f32 = x @ w, accumulated over the K grid axis

Block shapes default to MXU-aligned (128, 128, 128); the count map has one
scalar per (M-block, K-block).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.events import LANE_BITS
from ..gating import accum_tile
from ..words import transpose_words, x_operand_spec

Array = jax.Array


def _make_kernel(wpb: int | None):
    def kernel(vld_ref, x_ref, w_ref, o_ref, *scratch):
        i = pl.program_id(0)
        k = pl.program_id(2)
        src = scratch[0] if wpb is not None else x_ref

        @pl.when(k == 0)
        def _init():
            o_ref[...] = jnp.zeros_like(o_ref)
            if wpb is not None:          # the row block's words, transposed
                transpose_words(x_ref, src)

        @pl.when(vld_ref[i, k] > 0)      # event skip: silent block -> no MXU
        def _accum():
            accum_tile(o_ref, src, w_ref, wpb=wpb, kb=k)

    return kernel


@functools.partial(jax.jit,
                   static_argnames=("block_m", "block_n", "block_k",
                                    "packed_in", "interpret"))
def spike_matmul_pallas(x: Array, w: Array, vld_cnt: Array, *,
                        block_m: int = 128, block_n: int = 128,
                        block_k: int = 128, packed_in: bool = False,
                        interpret: bool = False) -> Array:
    """x: [M,K] int8 (or [M,K/32] int32 words with ``packed_in``);
    w: [K,N]; vld_cnt: [M/bm, K/bk] int32 block counts."""
    m = x.shape[0]
    k2, n = w.shape
    k = x.shape[1] * LANE_BITS if packed_in else x.shape[1]
    assert k == k2 and m % block_m == 0 and k % block_k == 0 \
        and n % block_n == 0, (x.shape, w.shape, block_m, block_n, block_k)
    x_spec, scratch, wpb = x_operand_spec(x, packed_in, block_m, block_k,
                                          lambda i, j, kk, vld: (i, kk))
    grid = (m // block_m, n // block_n, k // block_k)
    return pl.pallas_call(
        _make_kernel(wpb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                # index maps receive the prefetched scalar ref as a trailing arg
                x_spec,
                pl.BlockSpec((block_k, block_n), lambda i, j, kk, vld: (kk, j)),
            ],
            out_specs=pl.BlockSpec((block_m, block_n),
                                   lambda i, j, kk, vld: (i, j)),
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
    )(vld_cnt, x, w)


def _make_gated_kernel(wpb: int | None, two_level: bool):
    def kernel(*refs):
        if two_level:
            nact_ref, kmap_ref, occ_ref, x_ref, w_ref, o_ref, *scratch = refs
        else:
            nact_ref, kmap_ref, x_ref, w_ref, o_ref, *scratch = refs
        i = pl.program_id(0)
        s = pl.program_id(2)
        kb = kmap_ref[i, s]
        src = scratch[0] if wpb is not None else x_ref

        @pl.when(s == 0)
        def _init():
            o_ref[...] = jnp.zeros_like(o_ref)
            if wpb is not None:
                transpose_words(x_ref, src)

        # steps past nact[i] revisit the last active block index, so the
        # BlockSpec never changes -> no DMA; this predicate skips the MXU
        @pl.when(s < nact_ref[i])
        def _accum():
            accum_tile(o_ref, src, w_ref, wpb=wpb, kb=kb,
                       occ_bits=occ_ref[i, kb] if two_level else None)

    return kernel


@functools.partial(jax.jit,
                   static_argnames=("block_m", "block_n", "block_k",
                                    "packed_in", "two_level", "interpret"))
def spike_matmul_gated_pallas(x: Array, w: Array, nact: Array, kmap: Array,
                              occ: Array | None = None, *,
                              block_m: int = 128, block_n: int = 128,
                              block_k: int = 128, packed_in: bool = False,
                              two_level: bool = False,
                              interpret: bool = False) -> Array:
    """vld-gated tile streaming: the k grid axis walks ``kmap[i, s]`` — the
    COMPACTED list of non-silent k-block indices for m-row ``i`` (from
    ``core.events.compact_kmap``) — so silent blocks' weight tiles and spike
    words are never DMA'd: tail grid steps map to the previously-fetched
    block and Pallas elides the transfer. With ``two_level``, the per-block
    word-occupancy bitmap ``occ`` additionally skips silent 32-column
    stripes inside active blocks (irregular sparsity).

    x: [M,K] int8 (or [M,K/32] int32 words with ``packed_in``); w: [K,N];
    nact: [M/bm] int32; kmap: [M/bm, K/bk] int32; occ: [M/bm, K/bk] int32.
    """
    m = x.shape[0]
    k2, n = w.shape
    k = x.shape[1] * LANE_BITS if packed_in else x.shape[1]
    assert k == k2 and m % block_m == 0 and k % block_k == 0 \
        and n % block_n == 0, (x.shape, w.shape, block_m, block_n, block_k)
    if two_level:
        assert occ is not None, "two_level gating needs the occ bitmap"
        npf = 3
        scalars = (nact, kmap, occ)
    else:
        npf = 2
        scalars = (nact, kmap)

    def x_idx(i, j, s, nact_ref, kmap_ref, *rest):
        return (i, kmap_ref[i, s])

    def w_idx(i, j, s, nact_ref, kmap_ref, *rest):
        return (kmap_ref[i, s], j)

    x_spec, scratch, wpb = x_operand_spec(x, packed_in, block_m, block_k,
                                          x_idx)
    grid = (m // block_m, n // block_n, k // block_k)
    return pl.pallas_call(
        _make_gated_kernel(wpb, two_level),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=npf,
            grid=grid,
            in_specs=[
                x_spec,
                pl.BlockSpec((block_k, block_n), w_idx),
            ],
            out_specs=pl.BlockSpec((block_m, block_n),
                                   lambda i, j, s, *refs: (i, j)),
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
    )(*scalars, x, w)
