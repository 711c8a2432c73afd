"""The paper's VGG-11 SNN (T=1, width 1.0) on CIFAR-10-shaped images: the
builders, the plain references and the work counts. Sizes come from
``vgg11-cifar.json`` beside this file.

Deployed artifact (``fuse_model``'s format: BN folded into each conv, weights
on an 8-bit fixed-point grid, W2TTFS head). Layer equations the deploy
reference follows (H(z) = 1 if z >= 0 else 0, float32 throughout):

    s_1 = H(conv(x, W_1) + b_1 - v_th)                 analog images in
    s_l = H(conv(s_{l-1}, W_l) + b_l - v_th)           binary spikes in
    maxpool 2x2 of a spike map = OR of the window
    logits = (sum of the last map over its 2x2 window) @ W_fc / 4 + b_fc

Images are 8-bit pixels scaled to (k - 128) / 256 and weights sit on an
8-bit grid with a power-of-two scale, so every product is exact and every
float32 sum of them is exact: the program and the reference agree bit for
bit wherever they compute the same thing.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


# ------------------------------------------------------------------ sizes
def plan(sizes: dict) -> list:
    """[("conv", cin, cout, H) | ("pool", C, H)] in order, H the input
    spatial size of the layer; then ("head", C, H)."""
    out, c, h = [], sizes["in_channels"], sizes["image_size"]
    for item in sizes["layers"]:
        if item == "M":
            out.append(("pool", c, h))
            h //= 2
        else:
            cout = max(8, int(item * sizes["width_mult"]))
            out.append(("conv", c, cout, h))
            c = cout
    out.append(("head", c, h))
    return out


def conv_taps(h: int, k: int = 3, stride: int = 1) -> int:
    """(output position, kernel tap) pairs of a SAME convolution over an
    h x h map that land inside the map: the multiply-adds per input and
    output channel. Taps on the zero padding are no model work."""
    out = -(-h // stride)
    lo = max((out - 1) * stride + k - h, 0) // 2
    per_dim = sum(1 for o in range(out) for t in range(k)
                  if 0 <= o * stride + t - lo < h)
    return per_dim * per_dim


def deploy_flops_per_image(sizes: dict) -> float:
    """Model FLOPs of one image through the deployed VGG-11: the 3x3
    convolutions (SAME, stride 1) and the classifier."""
    f = 0.0
    for layer in plan(sizes):
        if layer[0] == "conv":
            _, cin, cout, h = layer
            f += 2.0 * conv_taps(h) * cin * cout
        elif layer[0] == "head":
            f += 2.0 * layer[1] * sizes["num_classes"]
    return f


def deploy_event_calls(sizes: dict, batch: int) -> list:
    """(flops, bytes) of each fused-PE call one deployed forward of
    ``batch`` images makes under ``fused_packed``, at the op's entry
    (``ops.fused_pe_layer``): packed im2col patches in, float32 weights and
    bias, packed spikes out. The first conv (analog input) is no event
    call."""
    calls = []
    for layer in plan(sizes)[1:]:
        if layer[0] != "conv":
            continue
        _, cin, cout, h = layer
        m, k = batch * h * h, 9 * cin
        calls.append((2.0 * m * k * cout,
                      m * k / 8 + k * cout * 4 + cout * 4 + m * cout / 8))
    return calls


def snn_config(sizes: dict):
    from repro.core.lif import LIFConfig
    from repro.core.quant import QuantConfig
    from repro.models import snn_cnn

    return snn_cnn.SNNCNNConfig(
        arch=sizes["arch"], num_classes=sizes["num_classes"],
        in_channels=sizes["in_channels"], image_size=sizes["image_size"],
        width_mult=sizes["width_mult"], timesteps=sizes["timesteps"],
        lif=LIFConfig(tau=sizes["lif_tau"], v_th=sizes["lif_v_th"],
                      surrogate=sizes["surrogate"],
                      alpha=sizes["surrogate_alpha"]),
        quant=QuantConfig(enabled=True, bits=sizes["quant_bits"]),
        head=sizes["deploy_head"])


# ----------------------------------------------------------------- images
@functools.partial(jax.jit, static_argnames=("n", "size", "ch"))
def _images(key, n: int, size: int, ch: int):
    k1, k2 = jax.random.split(key)
    coarse = jax.random.normal(k1, (n, size // 4, size // 4, ch))
    img = jax.image.resize(coarse, (n, size, size, ch), "linear")
    img = img + 0.25 * jax.random.normal(k2, img.shape)
    pix = jnp.clip(jnp.round(128 + 48 * img), 0, 255)
    return (pix - 128.0) / 256.0


def images(sizes: dict, key, n: int):
    """n images [n, H, W, C] float32 on 8-bit pixels, from ``key``."""
    return _images(key, n, sizes["image_size"], sizes["in_channels"])


# ---------------------------------------------------------- fixed point
def fixed_point(w, bits: int):
    """Symmetric fixed point with a power-of-two scale per tensor."""
    qmax = 2.0 ** (bits - 1) - 1.0
    scale = 2.0 ** jnp.ceil(jnp.log2(jnp.maximum(jnp.max(jnp.abs(w)),
                                                 1e-30) / qmax))
    return jnp.clip(jnp.round(w / scale), -qmax - 1, qmax) * scale


def _conv(x, w):
    return jax.lax.conv_general_dilated(
        x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=HIGHEST, preferred_element_type=jnp.float32)


def _pool(s):
    b, h, w, c = s.shape
    return s.reshape(b, h // 2, 2, w // 2, 2, c).max(axis=(2, 4))


# --------------------------------------------------------- deploy builder
def make_artifact(sizes: dict, key, calib):
    """The deployed artifact in ``fuse_model``'s format, in one jitted
    call: He-normal convs, BN folded with the per-channel statistics of
    the calibration batch's currents, weights on the 8-bit grid."""
    bits, vth = sizes["quant_bits"], sizes["lif_v_th"]
    layers = plan(sizes)

    def gen(key, x):
        keys = jax.random.split(key, len(layers))
        art = []
        for k, layer in zip(keys, layers):
            if layer[0] == "conv":
                _, cin, cout, _ = layer
                w = jax.random.normal(k, (3, 3, cin, cout)) * \
                    math.sqrt(2.0 / (9 * cin))
                cur = _conv(x, w)
                mu = cur.mean(axis=(0, 1, 2))
                sd = cur.std(axis=(0, 1, 2)) + 1e-5
                wq = fixed_point(w / sd, bits)
                b = -mu / sd
                art.append({"conv": {"w": wq, "b": b}})
                x = (_conv(x, wq) + b >= vth).astype(jnp.float32)
            elif layer[0] == "pool":
                art.append({})
                x = _pool(x)
            else:
                c = layer[1]
                w = jax.random.normal(k, (c, sizes["num_classes"])) / \
                    math.sqrt(c)
                art.append({"fc": {"w": fixed_point(w, bits),
                                   "b": jnp.zeros(sizes["num_classes"])}})
        return art

    return jax.jit(gen)(key, calib)


# ------------------------------------------------------- deploy reference
@functools.partial(jax.jit, static_argnames=("sz", "lower"))
def _deploy_ref(art, x, sz: tuple, lower: bool):
    sizes = _thaw(sz)
    vth, counts = sizes["lif_v_th"], []
    low = (lambda w: fixed_point(w, 4)) if lower else (lambda w: w)
    for p, layer in zip(art, plan(sizes)):
        if layer[0] == "conv":
            x = (_conv(x, low(p["conv"]["w"])) + p["conv"]["b"]
                 >= vth).astype(jnp.float32)
            counts.append(x.sum())
        elif layer[0] == "pool":
            x = _pool(x)
            counts.append(x.sum())
        else:
            b, h, w, c = x.shape
            cnt = x.sum(axis=(1, 2))
            logits = jnp.matmul(cnt, low(p["fc"]["w"]), precision=HIGHEST) \
                / float(h * w) + p["fc"]["b"]
    return logits, jnp.stack(counts)


def _freeze(sizes: dict) -> tuple:
    """Hashable form of the sizes, for a jitted reference's static
    argument (the ``assumed`` notes left out)."""
    def fz(v):
        if isinstance(v, dict):
            return ("__dict__",
                    tuple(sorted((k, fz(x)) for k, x in v.items())))
        return tuple(fz(x) for x in v) if isinstance(v, list) else v
    return tuple(sorted((k, fz(v)) for k, v in sizes.items()
                        if k != "assumed"))


def _thaw(sz: tuple) -> dict:
    def th(v):
        if isinstance(v, tuple) and v and v[0] == "__dict__":
            return {k: th(x) for k, x in v[1]}
        return [th(x) for x in v] if isinstance(v, tuple) else v
    return {k: th(v) for k, v in sz}


def deploy_reference(art, x, sizes: dict, lower: bool = False):
    """(logits [B, classes], spike count of every layer's map) of the
    deployed artifact; ``lower`` puts its weights on a 4-bit grid (the
    control)."""
    return _deploy_ref(art, x, _freeze(sizes), lower)


def deploy_forward(sizes: dict):
    """The timed call's body: ``snn_cnn.forward`` on the deployed artifact
    under the deploy policy; returns (logits, per-layer spike counts)."""
    from repro.models import snn_cnn

    cfg = snn_config(sizes)
    n_maps = len(plan(sizes)) - 1

    def fwd(art, x):
        logits, _, aux = snn_cnn.forward(art, x, cfg,
                                         policy=sizes["deploy_policy"])
        return logits, jnp.stack([jnp.asarray(aux["spikes"][f"layer{i}"],
                                              jnp.float32)
                                  for i in range(n_maps)])

    return fwd


def deploy_outputs(out):
    """(logits, per-layer spike counts) of one timed call's output."""
    return out
