"""The main-path Pallas kernels compile for a TPU v5e, at real widths.

Each case lowers one kernel through its public wrapper with
``interpret=False`` and compiles it with the TPU compiler for a described
(not attached) ``v5e:2x2`` chip — what Mosaic refuses here it would refuse
on the chip. Nothing runs. Shapes are qwen3-1.7b's: 1024 tokens, d_model
2048, d_ff 6144, 16 heads of 128.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention
from repro.kernels.fused_pe import fused_pe
from repro.kernels.lif_update import lif_update
from repro.kernels.packed import pack_spikes, unpack_spikes
from repro.kernels.qk_attention import qk_attention_fused
from repro.kernels.spike_matmul import spike_matmul
from repro.kernels.spike_matmul.ops import spike_matmul_dw, spike_matmul_dx
from repro.kernels.w2ttfs_pool import w2ttfs_pool_fc

M, D, F, H, DH = 1024, 2048, 6144, 16, 128
SKIPS = ("dense", "gated", "two_level")
ON_CHIP = dict(interpret=False)


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip cannot be read back from the
    # persistent cache without one: keep the cache off for these
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache)


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo


def _packed(x):
    return pack_spikes(x, **ON_CHIP)


def test_pack_unpack(one_chip):
    _compile(lambda x: unpack_spikes(_packed(x), **ON_CHIP), one_chip,
             ((M, D), jnp.int8))


@pytest.mark.parametrize("skip", SKIPS)
@pytest.mark.parametrize("fmt", ["dense", "packed"])
def test_spike_matmul(one_chip, fmt, skip):
    def fn(x, w):
        return spike_matmul(_packed(x) if fmt == "packed" else x, w,
                            skip=skip, **ON_CHIP)
    _compile(fn, one_chip, ((M, F), jnp.int8), ((F, D), jnp.bfloat16))


@pytest.mark.parametrize("skip", SKIPS)
@pytest.mark.parametrize("fmt", ["dense", "packed"])
def test_spike_matmul_dw(one_chip, fmt, skip):
    def fn(x, g):
        return spike_matmul_dw(_packed(x) if fmt == "packed" else x, g,
                               skip=skip, **ON_CHIP)
    _compile(fn, one_chip, ((M, D), jnp.int8), ((M, F), jnp.float32))


def test_spike_matmul_dx(one_chip):
    _compile(lambda g, w, v: spike_matmul_dx(g, w, v, **ON_CHIP), one_chip,
             ((M, F), jnp.float32), ((D, F), jnp.bfloat16),
             ((M, F), jnp.float32))


@pytest.mark.parametrize("skip", SKIPS)
def test_fused_pe_packed_head_blocked(one_chip, skip):
    """Packed x and Q in, packed spikes and the next layer's vld map out,
    with the head-blocked QK write-back mask: the qk_spiking K pass."""
    def fn(x, w, q):
        out = fused_pe(_packed(x), w, q=_packed(q), heads=(H, DH),
                       out_format="packed", skip=skip, **ON_CHIP)
        return out.spikes.words, out.vld_next
    _compile(fn, one_chip, ((M, D), jnp.int8), ((D, H * DH), jnp.bfloat16),
             ((M, H * DH), jnp.int8))


@pytest.mark.parametrize("residual", ["none", "packed"])
def test_fused_pe_dense_emit_vld(one_chip, residual):
    """Dense spikes in and out with the emitted vld map (the default),
    bias, the membrane-current cache and optionally a packed shortcut."""
    def fn(x, w, b, r):
        out = fused_pe(x, w, bias=b, emit_current=True,
                       residual=_packed(r) if residual == "packed" else None,
                       **ON_CHIP)
        return out.spikes, out.vld_next, out.current
    _compile(fn, one_chip, ((M, D), jnp.int8), ((D, D), jnp.bfloat16),
             ((D,), jnp.float32), ((M, D), jnp.int8))


def test_lif_update(one_chip):
    _compile(lambda c, v, s: lif_update(c, v, s, **ON_CHIP), one_chip,
             ((M, F), jnp.float32), ((M, F), jnp.float32),
             ((M, F), jnp.int8))


def test_qk_attention_fused(one_chip):
    _compile(lambda q, k: qk_attention_fused(q, k, **ON_CHIP), one_chip,
             ((M, D), jnp.int8), ((M, D), jnp.int8))


def test_w2ttfs_pool_fc(one_chip):
    """VGG-11's head at CIFAR size: batch 64, 2x2x512 spikes, 10 classes."""
    _compile(lambda s, w, b: w2ttfs_pool_fc(s, w, b, window=2, **ON_CHIP),
             one_chip, ((64, 2, 2, 512), jnp.int8), ((512, 10), jnp.float32),
             ((10,), jnp.float32))


def test_flash_attention(one_chip):
    shape = ((1, H, M, DH), jnp.bfloat16)
    _compile(lambda q, k, v: flash_attention(q, k, v, **ON_CHIP), one_chip,
             shape, shape, shape)
