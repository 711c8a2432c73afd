"""Shared in-kernel tile accumulation for the sparsity-adaptive kernels.

Both vld-gated kernels (``spike_matmul`` and ``fused_pe``) land on the same
inner step: accumulate one (block_m x block_k) x-tile against one
(block_k x block_n) w-tile into a f32 accumulator — either the whole tile
in one MXU issue, or (two-level compression, ExSpike's irregular-sparsity
layer) stripe-by-stripe, where a "stripe" is one packed int32 word-column =
32 dense k-columns, and silent stripes are elided via the ``occ`` bitmap
from ``core.events.word_occupancy_map``.

The stripe loop is a PYTHON loop over the tile's word-columns (block_k/32
iterations, unrolled at trace time) with a ``pl.when`` per stripe, so the
skip is a predicated branch — cheap on silent stripes, and the sub-dots
stay MXU-shaped at (block_m, 32) @ (32, block_n).

A packed x arrives as the row block's TRANSPOSED words (``kernels.words``):
``x_ref`` is then the ``(K/32, block_m)`` scratch and ``kb`` the k-block
to read from it.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core.events import LANE_BITS
from .words import dot_t, stripe_bits_t, tile_bits_t


def _occupied(occ_bits, c: int):
    # arithmetic >> keeps bit 31 extractable (the &1 masks the sign fill)
    return jnp.bitwise_and(jnp.right_shift(occ_bits, c), 1) != 0


def accum_tile(o_ref, x_ref, w_ref, *, wpb: int | None = None, kb=None,
               occ_bits=None) -> None:
    """o_ref += x_tile @ w_tile.

    ``x_ref``: (block_m, block_k) dense spikes, or — with ``wpb`` (words
    per k-block) set — the transposed packed words of the row block, read
    at k-block ``kb``. ``w_ref``: (block_k, block_n). ``occ_bits``:
    optional int32 scalar — the word-occupancy bitmap for THIS tile; when
    given, only occupied 32-column stripes touch the MXU.
    """
    if occ_bits is None:
        if wpb is not None:            # decompress the K-tile in VMEM
            o_ref[...] += dot_t(tile_bits_t(x_ref, kb, wpb), w_ref[...])
        else:
            x = x_ref[...].astype(jnp.float32)
            o_ref[...] += jnp.dot(x, w_ref[...].astype(jnp.float32),
                                  preferred_element_type=jnp.float32)
        return

    n_stripes = wpb if wpb is not None else x_ref.shape[-1] // LANE_BITS
    assert n_stripes <= LANE_BITS, (n_stripes,
                                    "occ bitmap covers <= 32 word-columns")
    for c in range(n_stripes):
        @pl.when(_occupied(occ_bits, c))
        def _stripe(c=c):
            ws = w_ref[c * LANE_BITS:(c + 1) * LANE_BITS, :]
            if wpb is not None:
                o_ref[...] += dot_t(stripe_bits_t(x_ref, kb, wpb, c), ws)
            else:
                xs = x_ref[:, c * LANE_BITS:(c + 1) * LANE_BITS]
                o_ref[...] += jnp.dot(xs.astype(jnp.float32),
                                      ws.astype(jnp.float32),
                                      preferred_element_type=jnp.float32)


def accum_tile_t(o_ref, x_ref, g_ref, *, wpb: int | None = None, kb=None,
                 occ_bits=None) -> None:
    """o_ref += x_tileᵀ @ g_tile — the weight-gradient contraction.

    ``x_ref``: (block_m, block_k) dense spikes, or the transposed packed
    words of the row block read at k-block ``kb`` (``wpb`` set).
    ``g_ref``: (block_m, block_n) f32 cotangent. ``o_ref``: (block_k,
    block_n). ``occ_bits``: optional word-occupancy bitmap for THIS
    x-tile; a silent 32-column k-stripe of x contributes nothing to output
    ROWS [c*32, (c+1)*32), so the stripe's (32, block_m) @ (block_m,
    block_n) sub-dot is elided entirely.
    """
    g = g_ref[...].astype(jnp.float32)
    if occ_bits is None:
        if wpb is not None:
            xt = tile_bits_t(x_ref, kb, wpb)
        else:
            xt = x_ref[...].astype(jnp.float32).T
        o_ref[...] += jnp.dot(xt, g, preferred_element_type=jnp.float32)
        return

    n_stripes = wpb if wpb is not None else x_ref.shape[-1] // LANE_BITS
    assert n_stripes <= LANE_BITS, (n_stripes,
                                    "occ bitmap covers <= 32 word-columns")
    for c in range(n_stripes):
        @pl.when(_occupied(occ_bits, c))
        def _stripe(c=c):
            if wpb is not None:
                xst = stripe_bits_t(x_ref, kb, wpb, c)
            else:
                xst = x_ref[:, c * LANE_BITS:(c + 1) * LANE_BITS].astype(
                    jnp.float32).T
            o_ref[c * LANE_BITS:(c + 1) * LANE_BITS, :] += jnp.dot(
                xst, g, preferred_element_type=jnp.float32)
