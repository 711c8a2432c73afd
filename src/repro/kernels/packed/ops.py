"""Public wrappers for the packed spike format: padding, leading-dim
handling, and interpret-mode dispatch.

``pack_spikes``   — spikes (any leading dims) -> PackedSpikes in one pass.
``unpack_spikes`` — PackedSpikes -> dense int8 at the LOGICAL shape.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...core.events import PackedSpikes, pad_to_blocks
from ..contract import KernelContract, declare, pack_vmem
from .packed import pack_spikes_pallas, unpack_spikes_pallas

Array = jax.Array

# im2col/pool ride on this family's contract: they are pure event-format
# data movement (word-level patch extraction / bitwise-OR pooling) with no
# reference-vs-fused numeric fork, registered alongside pack/unpack.
CONTRACT = declare(KernelContract(
    family="packed", ops=("pack", "unpack", "im2col", "pool"),
    grad_ops=("im2col", "pool"), emits_spikes=True, vmem_bytes=pack_vmem))


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _over_leading(fn, x: Array):
    """Run a 2-D-core pallas wrapper over arbitrary leading dims. Both
    kernels work row block by row block and each leading slice is already
    padded to whole row blocks, so the slices stack along M: one call, and
    every output splits back along its first axis."""
    if x.ndim == 2:
        return fn(x)
    lead = x.shape[:-2]
    out = fn(x.reshape(-1, x.shape[-1]))
    return jax.tree_util.tree_map(
        lambda a: a.reshape(*lead, -1, a.shape[-1]), out)


@functools.partial(jax.jit, static_argnames=("block_m", "block_k",
                                             "interpret"))
def pack_spikes(x: Array, *, block_m: int = 128, block_k: int = 128,
                interpret: bool | None = None) -> PackedSpikes:
    """Compress a spike tensor [..., M, K] (nonzero == event) into the
    packed HBM format. Pads the core dims to the block grid, packs 32
    spikes per int32 lane, and derives the block vld_cnt map by popcount —
    all in one Pallas pass over x."""
    if interpret is None:
        interpret = not _on_tpu()
    xp = pad_to_blocks(x, block_m, block_k)
    words, vld, occ = _over_leading(
        lambda t: pack_spikes_pallas(t, block_m=block_m, block_k=block_k,
                                     interpret=interpret), xp)
    return PackedSpikes(words, vld, tuple(x.shape), block_m, block_k, occ)


@functools.partial(jax.jit, static_argnames=("dtype", "interpret"))
def unpack_spikes(ps: PackedSpikes, *, dtype=jnp.int8,
                  interpret: bool | None = None) -> Array:
    """Decompress back to the dense spike map at the logical (pre-padding)
    shape. Bit-exact inverse of ``pack_spikes`` for binary inputs."""
    if interpret is None:
        interpret = not _on_tpu()
    dense = _over_leading(
        lambda t: unpack_spikes_pallas(t, block_m=ps.block_m,
                                       block_k=ps.block_k, dtype=dtype,
                                       interpret=interpret), ps.words)
    sl = tuple(slice(0, d) for d in ps.shape[-2:])
    return dense[(..., *sl)]
