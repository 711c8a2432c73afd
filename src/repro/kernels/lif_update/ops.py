"""Public wrapper: shape-flattening + padding for the fused LIF update."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..contract import KernelContract, declare
from .lif_update import lif_update_pallas

Array = jax.Array

# bytes per element of one grid step: current f32 + v f32 + s int8 in,
# spikes int8 + v f32 out
_ROW_BYTES = 4 + 4 + 1 + 1 + 4
# one step's tiles stay under this, so the double-buffered sweep fits the
# default scoped VMEM at any feature width
_TILE_BYTES = 2 * 2**20


def _rows(block: int, d: int) -> int:
    """Rows per grid step: ``block``, cut for wide rows to a multiple of 32
    (the int8 sublane tile) whose tiles fit ``_TILE_BYTES``."""
    return min(block, max(32, _TILE_BYTES // (d * _ROW_BYTES) // 32 * 32))


CONTRACT = declare(KernelContract(
    family="lif_update", ops=("lif",), formats=("dense",), grad=True,
    # elementwise row-block sweep over a (rows, D) tile, D the corpus'
    # widest feature dim
    vmem_bytes=lambda bm, bn, bk, packed: _rows(256, bn) * bn * _ROW_BYTES))


@functools.partial(jax.jit, static_argnames=("tau", "v_th", "soft_reset",
                                             "block", "interpret"))
def lif_update(current: Array, v_prev: Array, s_prev: Array, *,
               tau: float = 0.5, v_th: float = 1.0, soft_reset: bool = False,
               block: int = 256, interpret: bool | None = None
               ) -> tuple[Array, Array]:
    """Fused LIF step over arbitrarily-shaped tensors.

    Returns (spikes int8, v_next f32) with the input shape.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    shape = current.shape
    d = shape[-1]
    x = current.reshape(-1, d)
    v = v_prev.reshape(-1, d)
    s = s_prev.reshape(-1, d)
    m = x.shape[0]
    bb = min(_rows(block, d), m)
    pad = (-m) % bb
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
        v = jnp.pad(v, ((0, pad), (0, 0)))
        s = jnp.pad(s, ((0, pad), (0, 0)))
    spk, vn = lif_update_pallas(x, v, s, tau=tau, v_th=v_th,
                                soft_reset=soft_reset, block=bb,
                                interpret=interpret)
    return spk[:m].reshape(shape), vn[:m].reshape(shape)
