"""In-kernel views of the bit-packed spike words, in forms Mosaic compiles.

The HBM format (``core.events``) is words ``[M, K/32]`` int32, bit ``b``
of word ``j`` = column ``32*j + b``. One K-tile of ``block_k`` columns is
``block_k/32`` words — 4 at the default 128 — and a block that narrow
breaks the TPU's (8, 128) tiling, so no BlockSpec may address it alone.

Every kernel therefore takes packed words a ROW BLOCK at a time: block
``(block_m, K/32)``, which spans the array's last dim and is legal at any
width (``row_spec``). Inside, the row block is transposed once into a
``(K/32, block_m)`` VMEM scratch (``transpose_words``). There a K-tile is
a sublane slice of ``block_k/32`` rows at a dynamic offset, and bits
expand and collapse along sublanes:

  * ``tile_bits_t``  — K-tile ``t`` as the TRANSPOSED 0/1 tile
    ``[block_k, block_m]`` f32 (word row ``c`` -> rows ``32c..32c+31``);
  * ``stripe_bits_t`` — one 32-column stripe of it (two_level gating);
  * ``pack_tile_t``  — a ``[block_m, block_n]`` spike tile -> its
    ``[block_n/32, block_m]`` transposed words.

Matmuls take the transposed tile directly (``dot_t``): contracting dim 0
of both operands is ``x @ w``. The same code runs in interpret mode.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.events import LANE_BITS


def row_spec(block_m: int, n_words: int, index_map) -> pl.BlockSpec:
    """BlockSpec for a packed operand: all ``n_words`` words of one row
    block (``index_map`` returns the row-block index)."""
    return pl.BlockSpec((block_m, n_words),
                        lambda *a: (index_map(*a), 0))


def x_operand_spec(x, packed_in: bool, block_m: int, block_k: int, x_idx):
    """x BlockSpec + scratch: dense tiles, or (packed) the row block's
    words whole, transposed once per accumulation into a VMEM scratch."""
    if not packed_in:
        return pl.BlockSpec((block_m, block_k), x_idx), [], None
    assert x.dtype == jnp.int32 and block_k % LANE_BITS == 0
    n_words = x.shape[1]
    return (row_spec(block_m, n_words, lambda *a: x_idx(*a)[0]),
            [pltpu.VMEM((n_words, block_m), jnp.int32)],
            block_k // LANE_BITS)


def transpose_words(words_ref, wt_ref) -> None:
    """(block_m, W) words -> (W, block_m) scratch."""
    wt_ref[...] = words_ref[...].T


def _expand_rows(rows):
    """(r, bm) int32 words -> (32*r, bm) f32 bits, row 32c+b = bit b of
    word row c."""
    r, bm = rows.shape
    shifts = jax.lax.broadcasted_iota(jnp.int32, (LANE_BITS, bm), 0)
    parts = [jnp.bitwise_and(jnp.right_shift(
        jnp.broadcast_to(rows[c:c + 1, :], (LANE_BITS, bm)), shifts), 1)
        for c in range(r)]
    bits = parts[0] if r == 1 else jnp.concatenate(parts, axis=0)
    return bits.astype(jnp.float32)


def tile_bits_t(wt_ref, t, wpb: int):
    """K-tile ``t`` (``wpb`` words per row) of the transposed words as the
    transposed 0/1 tile ``[32*wpb, block_m]`` f32."""
    return _expand_rows(wt_ref[pl.ds(t * wpb, wpb), :])


def stripe_bits_t(wt_ref, t, wpb: int, c: int):
    """32-column stripe ``c`` of K-tile ``t``: ``[32, block_m]`` f32."""
    return _expand_rows(wt_ref[pl.ds(t * wpb + c, 1), :])


def pack_tile_t(spk):
    """[block_m, block_n] 0/nonzero tile -> [block_n/32, block_m] int32
    transposed words. Distinct powers of two sum to their OR; bit 31
    wraps to the sign bit (modular int32 adds)."""
    bm, bn = spk.shape
    st = (spk != 0).astype(jnp.float32).T.astype(jnp.int32)
    st = st.reshape(bn // LANE_BITS, LANE_BITS, bm)
    shifts = jax.lax.broadcasted_iota(jnp.int32, st.shape, 1)
    return jnp.sum(jnp.left_shift(st, shifts), axis=1)


def dot_t(xt, w):
    """``xtᵀ @ w`` for a transposed f32 tile ``xt`` [k, m] and ``w``
    [k, n]."""
    return jax.lax.dot_general(xt, w.astype(jnp.float32),
                               (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)
