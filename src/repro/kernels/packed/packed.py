"""Pallas pack / unpack primitives for the bit-packed spike format.

Event compression (ExSpike, arXiv 2606.20414) applied at TPU block
granularity: a [M, K] spike map becomes int32 words along K — 32 spikes per
lane — and the PACK KERNEL emits the block-aligned ``vld_cnt`` map in the
SAME grid pass, counting the events of the tile it just packed. That closes
the metadata hole the dense pipeline had: ``block_count_map_2d`` re-read the
whole dense tensor from HBM just to count events; here the count falls out
of the compression pass for free (one read of x, one 1/8-size write, one
tiny map write).

Bit layout (shared contract with ``core.events`` and the packed operand
paths of spike_matmul / fused_pe): word j covers columns [j*32, (j+1)*32),
bit b = column j*32 + b. Shapes must be pre-padded to the (block_m, block_k)
grid; block_k % 32 == 0 so VMEM tiles land on word boundaries.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.events import LANE_BITS
from ..words import pack_tile_t, row_spec, tile_bits_t, transpose_words

Array = jax.Array


def _pack_kernel(x_ref, w_ref, cnt_ref, occ_ref, wt_ref):
    i = pl.program_id(0)
    t = pl.program_id(1)
    x = x_ref[...]
    words_t = pack_tile_t(x)                      # [block_k/32, block_m]
    wpb = words_t.shape[0]
    wt_ref[pl.ds(t * wpb, wpb), :] = words_t
    # counted at pack time: the vld_cnt metadata is a reduction of data
    # already in VMEM — no second HBM pass ever builds it
    cnt_ref[i, t] = jnp.sum((x != 0).astype(jnp.float32)).astype(jnp.int32)
    # second compression level, same pass: word-COLUMN occupancy bitmap
    # (bit c set iff any row's word c is nonzero) — the two_level kernels
    # use it to elide silent 32-column stripes inside active blocks
    occ = jnp.int32(0)
    for c in range(wpb):
        hit = jnp.max((words_t[c:c + 1, :] != 0).astype(jnp.float32)) > 0
        occ = occ + jnp.where(hit, jnp.left_shift(jnp.int32(1), c), 0)
    occ_ref[i, t] = occ

    @pl.when(t == pl.num_programs(1) - 1)         # row block complete
    def _store():
        w_ref[...] = wt_ref[...].T


def _unpack_kernel(w_ref, o_ref, wt_ref):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _load():
        transpose_words(w_ref, wt_ref)

    wpb = o_ref.shape[1] // LANE_BITS
    o_ref[...] = tile_bits_t(wt_ref, t, wpb).T.astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_m", "block_k", "interpret"))
def pack_spikes_pallas(x: Array, *, block_m: int = 128, block_k: int = 128,
                       interpret: bool = False
                       ) -> tuple[Array, Array, Array]:
    """x: [M, K] spikes (any dtype; nonzero == event), block-aligned.

    Returns (words int32 [M, K/32], vld_cnt int32 [M/bm, K/bk], occ int32
    [M/bm, K/bk] word-occupancy bitmaps) from ONE grid pass. Each row
    block's words collect in a transposed VMEM scratch and leave whole
    (``kernels.words``); the per-block maps are SMEM outputs.
    """
    m, k = x.shape
    assert m % block_m == 0 and k % block_k == 0, (x.shape, block_m, block_k)
    assert block_k % LANE_BITS == 0, block_k
    assert block_k // LANE_BITS <= LANE_BITS, \
        (block_k, "occ bitmap needs block_k <= 1024")
    grid = (m // block_m, k // block_k)
    n_words = k // LANE_BITS
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        _pack_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_m, block_k), lambda i, j: (i, j))],
        out_specs=[row_spec(block_m, n_words, lambda i, j: i), smem, smem],
        out_shape=[
            jax.ShapeDtypeStruct((m, n_words), jnp.int32),
            jax.ShapeDtypeStruct((m // block_m, k // block_k), jnp.int32),
            jax.ShapeDtypeStruct((m // block_m, k // block_k), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((n_words, block_m), jnp.int32)],
        interpret=interpret,
    )(x)


@functools.partial(jax.jit,
                   static_argnames=("block_m", "block_k", "dtype",
                                    "interpret"))
def unpack_spikes_pallas(words: Array, *, block_m: int = 128,
                         block_k: int = 128, dtype=jnp.int8,
                         interpret: bool = False) -> Array:
    """words: [M, K/32] int32 -> [M, K] dense spikes (0/1)."""
    m, w = words.shape
    wpb = block_k // LANE_BITS
    assert m % block_m == 0 and w % wpb == 0, (words.shape, block_m, block_k)
    grid = (m // block_m, w // wpb)
    return pl.pallas_call(
        _unpack_kernel,
        grid=grid,
        in_specs=[row_spec(block_m, w, lambda i, j: i)],
        out_specs=pl.BlockSpec((block_m, block_k), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, w * LANE_BITS), dtype),
        scratch_shapes=[pltpu.VMEM((w, block_m), jnp.int32)],
        interpret=interpret,
    )(words)
