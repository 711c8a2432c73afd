"""The gradient axis of ``repro.ops``: surrogate-gradient implementations.

The paper's algorithm-level contribution (C1) trains single-timestep SNNs
with plain backprop by substituting a smooth pseudo-derivative for the
Heaviside (§III.B).  This module is that substitution expressed as
``(op, mode)`` registry entries, so the SAME policy-driven forward the
deployment stack runs is what the KD pipeline differentiates:

  * ``(op, "reference+grad")`` — the pure-jnp surrogate body, differentiable
    end to end through ``core.surrogate.spike`` (whose own ``custom_vjp``
    carries the registered pseudo-derivative).  This is the autodiff
    baseline every other mode is parity-tested against.
  * ``(op, "fused+grad")`` — a ``jax.custom_vjp`` whose FORWARD runs the
    fused Pallas kernel (dense or packed, per the policy's format) and
    whose BACKWARD consumes RESIDUALS CACHED BY THAT FORWARD: the kernel
    emits its post-bias/-residual membrane current (``emit_current``), so
    the vjp differentiates only the cheap elementwise tail (surrogate
    spike, reset, QK mask) from the cached current and then runs the two
    transposed contractions directly — ``dx = dv @ wᵀ`` and
    ``dw = xᵀ @ dv`` — with NO re-execution of the forward matmul.
    Forward numerics are the deployment kernels'; gradients are the
    training graph's — "train what you serve" in one registry key.

Residual/recompute policy (matmul-bearing ops — matmul, fused_pe,
fused_pe_layer, dense_lif): the forward saves its spike operand, weights,
and the kernel-emitted membrane current; the backward recomputes ONLY the
elementwise nonlinearity from that current.  Elementwise ops (lif,
qk_mask) and the tiny w2ttfs head keep the classic recompute-from-inputs
``jax.vjp`` — re-linearizing them costs about as much as reading a cache.

Backward executor: on TPU (or under ``force_pallas_backward``) the two
contractions run the dedicated event-skipped Pallas backward kernels
(``kernels.spike_matmul.backward``): ``dx`` fuses the surrogate pseudo-
derivative factor into the transpose sweep, and ``dw`` skips the same
silent (m, k) tiles the forward skipped — the spikes ARE the activations,
so the vld/occ metadata prices both directions.  On CPU the identical
contractions run as XLA transposes (the Pallas interpreter would lose the
throughput the residual caching just won); parity between the two
executors is pinned by tests/test_grad_backward.py.

Spike operands arrive as dense f32 arrays (the dispatch layer materializes
SpikeTensors before calling in); spike outputs leave dense f32 so autodiff
connectivity survives the op chain.  Packed-policy forwards round-trip
through the pack/unpack kernels inside the primal only.
"""
from __future__ import annotations

import contextlib
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ..core.lif import LIFConfig
from ..core.surrogate import spike, surrogate_grad
from .registry import register

Array = jax.Array

GRAD_MODES = ("reference+grad", "fused+grad")


# --------------------------------------------------------------- machinery
def _surrogate_vjp(kernel_fwd, ref_fwd):
    """custom_vjp pair: primal = ``kernel_fwd`` (the policy's kernels),
    backward = vjp of ``ref_fwd`` (the pure-jnp surrogate body).  Both take
    ONE pytree of f32 arrays and must return structurally identical f32
    outputs (enforced by the grad-parity tests).  Retained for the
    elementwise ops whose re-linearization is as cheap as a cache read;
    the matmul-bearing ops use residual-cached vjps below."""

    @jax.custom_vjp
    def f(operands):
        return kernel_fwd(operands)

    def fwd(operands):
        return kernel_fwd(operands), operands

    def bwd(operands, g):
        _, vjp = jax.vjp(ref_fwd, operands)
        return vjp(g)

    f.defvjp(fwd, bwd)
    return f


# ------------------------------------------------------- kernel executor
_FORCE_PALLAS_BWD = False


def _pallas_backward() -> bool:
    """Whether the transposed contractions run the event-skipped Pallas
    backward kernels.  Default: only on TPU — on CPU the kernels would run
    under the Pallas interpreter, and the jnp transposes compute the
    IDENTICAL contraction faster (parity pinned by the backward tests)."""
    return _FORCE_PALLAS_BWD or jax.default_backend() == "tpu"


# The TRAINING forward follows the same executor split: on TPU the primal
# inside each custom_vjp runs the real fused kernels; off-TPU it runs the
# identical math as plain jnp (bit-parity with the kernels is pinned by
# the kernel test suites), skipping the Pallas interpreter emulation AND
# its pad/vld bookkeeping.  Inference/serving dispatch is unaffected.
_pallas_training = _pallas_backward


@contextlib.contextmanager
def force_pallas_backward(enabled: bool = True):
    """Force the Pallas kernel executor (interpret mode off-TPU) for BOTH
    directions of the differentiable ops — the primal kernels and the
    event-skipped backward kernels — used by the parity tests to exercise
    the kernel path end to end on CPU.  The flag is read at TRACE time:
    build (or re-trace) the grad function inside this context for it to
    take effect."""
    global _FORCE_PALLAS_BWD
    prev = _FORCE_PALLAS_BWD
    _FORCE_PALLAS_BWD = enabled
    try:
        yield
    finally:
        _FORCE_PALLAS_BWD = prev


def _bwd_dx(g: Array, w: Array, v: Optional[Array] = None, *,
            surrogate: str = "atan", alpha: float = 2.0, v_th: float = 1.0,
            blocks: tuple[int, int, int] = (128, 128, 128)):
    """``dv = g ⊙ surr'(v - v_th)`` (identity when ``v`` is None) and
    ``dx = dv @ wᵀ`` — one Pallas pass with the surrogate factor fused
    in-kernel on the Pallas executor, the identical jnp contraction
    otherwise.  Returns ``(dx, dv)``; 2-D operands only."""
    if _pallas_backward():
        from ..kernels.spike_matmul import spike_matmul_dx

        bm, bn, bk = blocks
        return spike_matmul_dx(g, w, v, surrogate=surrogate, alpha=alpha,
                               v_th=v_th, block_m=bm, block_n=bn, block_k=bk)
    dv = g if v is None else g * surrogate_grad(v - v_th, surrogate,
                                                alpha).astype(g.dtype)
    return dv @ w.T, dv


def _bwd_dw(x: Array, dv: Array, *, skip: str = "dense",
            blocks: tuple[int, int, int] = (128, 128, 128)) -> Array:
    """``dw = xᵀ @ dv`` over the {0,1} spike operand ``x`` — event-skipped
    on the Pallas executor (the tiles silent on the way forward are silent
    here too; ``skip`` applies the same dense/gated/two_level ladder along
    the transposed axis), a jnp transpose otherwise."""
    if _pallas_backward():
        from ..kernels.spike_matmul import spike_matmul_dw

        bm, bn, bk = blocks
        return spike_matmul_dw(x, dv, skip=skip, block_m=bm, block_n=bn,
                               block_k=bk)
    return x.T @ dv


def _f32(x: Optional[Array]) -> Optional[Array]:
    return None if x is None else x.astype(jnp.float32)


def _dense_operand(st) -> Array:
    """SpikeTensor -> dense float operand, preserving autodiff connectivity
    (a dense f32 payload passes through untouched)."""
    from .spike_tensor import SpikeTensor

    if isinstance(st, SpikeTensor):
        return st.to_dense(jnp.float32) if st.is_packed \
            else st.data.astype(jnp.float32)
    return st.astype(jnp.float32)


def _emitted_dense(st) -> Array:
    """A kernel-emitted SpikeTensor (either format) -> dense f32 primal."""
    return _f32(st.to_dense(jnp.float32) if st.is_packed else st.data)


def _lif_step(cur: Array, v_prev: Optional[Array], s_prev: Optional[Array],
              cfg: LIFConfig) -> tuple[Array, Array]:
    """The surrogate LIF body in the KERNEL's state convention (reset by
    ``s_prev`` on entry, reset by the emitted spike on exit — idempotent,
    so chaining with ``s_prev=0`` over already-reset state reproduces
    ``core.lif.lif_single_step`` exactly, gradient included)."""
    v = cur if v_prev is None else \
        cfg.tau * v_prev * (1.0 - (0.0 if s_prev is None else s_prev)) + cur
    s = spike(v - cfg.v_th, cfg.surrogate, cfg.alpha)
    v_next = v - cfg.v_th * s if cfg.soft_reset else v * (1.0 - s)
    return s, v_next


def _qk_rowmask(q: Array, threshold: float, mode: str, surrogate: str,
                alpha: float) -> Array:
    """Per-token write-back mask — ``core.qk_attention.qk_token_mask``
    (ONE definition of the row-sum semantics): the surrogate flows through
    the threshold Heaviside; ``mode="or"`` is the hardware atten_reg,
    forward-identical on integer spike counts with threshold 1 but with
    zero gradient into Q."""
    from ..core.qk_attention import qk_token_mask

    return qk_token_mask(q, mode, threshold, surrogate, alpha)


def _qk_headmask_apply(s: Array, q: Array, heads: tuple[int, int],
                       kv_heads: Optional[int], threshold: float,
                       surrogate: str, alpha: float) -> Array:
    """Head-blocked surrogate write-back mask: one row-sum Heaviside (with
    surrogate pseudo-derivative) per head over ``q``'s head slice, gating
    that head's ``dh`` columns of ``s``. With ``kv_heads < h`` the per-
    QUERY-head mask broadcasts over each KV group, so ``s`` arrives
    grouped ([m, kv_heads*dh]) and leaves expanded ([m, h*dh]) — the
    backward pass then sums each group's cotangents into the shared
    grouped columns, exactly the vjp of the fused path's replicated
    weight columns."""
    h, dh = heads
    m = s.shape[0]
    hkv = h if kv_heads is None else kv_heads
    g = h // hkv
    mask = _qk_rowmask(q.reshape(m, -1)[:, :h * dh].reshape(m, h, dh),
                       threshold, "threshold", surrogate, alpha)
    return (s.reshape(m, hkv, 1, dh)
            * mask.reshape(m, hkv, g, 1)).reshape(m, h * dh)


# ------------------------------------------------------------------- matmul
@functools.lru_cache(maxsize=None)
def _matmul_grad(kernels: str, block_m: int, block_n: int, block_k: int,
                 skip: str = "dense"):
    # unlike the 2-D inference entry point, the differentiable matmul takes
    # leading batch/time dims (the training body feeds [T, B, N, K] token
    # stacks); the reference body contracts batched exactly like the jnp
    # graph it replaces, the kernel form flattens for the Pallas call
    def ref_fwd(ops):
        return ops["x"] @ ops["w"]

    if kernels == "reference":
        return ref_fwd

    def kernel_fwd(ops):
        if not _pallas_training():
            return ref_fwd(ops)
        from ..kernels.spike_matmul import spike_matmul

        x, w = ops["x"], ops["w"]
        out = spike_matmul(x.reshape(-1, x.shape[-1]), w, block_m=block_m,
                           block_n=block_n, block_k=block_k, skip=skip)
        return out.reshape(*x.shape[:-1], w.shape[-1])

    blocks = (block_m, block_n, block_k)

    @jax.custom_vjp
    def f(operands):
        return kernel_fwd(operands)

    def fwd(operands):
        # residuals: the operands themselves — a linear op has no
        # intermediate to cache, but the backward below runs TWO transposed
        # contractions instead of re-linearizing the forward (three)
        return kernel_fwd(operands), (operands["x"], operands["w"])

    def bwd(res, g):
        x, w = res
        x2 = x.reshape(-1, x.shape[-1])
        g2 = g.reshape(-1, g.shape[-1])
        dx, _ = _bwd_dx(g2, w, blocks=blocks)
        dw = _bwd_dw(x2, g2, skip=skip, blocks=blocks)
        return ({"x": dx.reshape(x.shape), "w": dw.astype(w.dtype)},)

    f.defvjp(fwd, bwd)
    return f


def _matmul_impl(kernels):
    # ``skip`` threads through to BOTH directions on the fused path: the
    # forward's event-skipped streaming mode and the backward weight-grad
    # kernel's transposed gating (xᵀ@g skips the same silent tiles).
    def impl(st, w, *, block_m, block_n, block_k, skip="dense"):
        f = _matmul_grad(kernels, block_m, block_n, block_k, skip)
        return f({"x": _dense_operand(st), "w": _f32(w)})
    return impl


# ---------------------------------------------------------------------- lif
@functools.lru_cache(maxsize=None)
def _lif_grad(kernels: str, cfg: LIFConfig):
    def ref_fwd(ops):
        return _lif_step(ops["current"], ops["v_prev"], ops["s_prev"], cfg)

    if kernels == "reference":
        return ref_fwd

    def kernel_fwd(ops):
        from ..kernels.lif_update import lif_update, lif_update_ref

        # Purely elementwise — off-TPU the interpret emulation buys no
        # skip/format behaviour, only wall clock; same math either way.
        fn = lif_update if _pallas_training() else lif_update_ref
        s, v = fn(ops["current"], ops["v_prev"], ops["s_prev"],
                  tau=cfg.tau, v_th=cfg.v_th, soft_reset=cfg.soft_reset)
        return _f32(s), _f32(v)

    return _surrogate_vjp(kernel_fwd, ref_fwd)


def _lif_impl(kernels):
    def impl(current, v_prev, s_prev, cfg: LIFConfig):
        f = _lif_grad(kernels, cfg)
        return f({"current": _f32(current), "v_prev": _f32(v_prev),
                  "s_prev": _f32(s_prev)})
    return impl


# ----------------------------------------------------------------- fused_pe
def _pe_current(ops: dict) -> Array:
    cur = ops["x"] @ ops["w"]
    if ops.get("bias") is not None:
        cur = cur + ops["bias"].reshape(1, -1)
    if ops.get("residual") is not None:
        cur = cur + ops["residual"]
    return cur


@functools.lru_cache(maxsize=None)
def _fused_pe_grad(kernels: str, cfg: LIFConfig, qk_threshold: float,
                   fmt: str, block_m: int, block_n: int, block_k: int,
                   stateful: bool, heads: Optional[tuple[int, int]] = None,
                   skip: str = "dense"):
    def _mask(s, q):
        if q is not None and heads is not None:
            return _qk_headmask_apply(s, q, heads, None, qk_threshold,
                                      cfg.surrogate, cfg.alpha)
        if q is not None:
            return s * _qk_rowmask(q.reshape(s.shape[0], -1),
                                   qk_threshold, "threshold", cfg.surrogate,
                                   cfg.alpha)
        return s

    def ref_fwd(ops):
        s, v_next = _lif_step(_pe_current(ops),
                              ops.get("v_prev"), ops.get("s_prev"), cfg)
        s = _mask(s, ops.get("q"))
        return (s, v_next) if stateful else (s,)

    if kernels == "reference":
        return ref_fwd

    blocks = (block_m, block_n, block_k)

    def run_kernel(ops, emit_current):
        if not _pallas_training():
            # identical math as jnp (kernel bit-parity is test-pinned) —
            # the membrane current doubles as the backward's residual cache
            cur = _pe_current(ops)
            s, v_next = _lif_step(cur, ops.get("v_prev"),
                                  ops.get("s_prev"), cfg)
            s = _mask(s, ops.get("q"))
            primal = (s, v_next) if stateful else (s,)
            return primal, (cur if emit_current else None)
        from ..kernels.fused_pe import fused_pe

        out = fused_pe(ops["x"], ops["w"], bias=ops.get("bias"),
                       residual=ops.get("residual"),
                       v_prev=ops.get("v_prev"), s_prev=ops.get("s_prev"),
                       q=ops.get("q"), tau=cfg.tau, v_th=cfg.v_th,
                       soft_reset=cfg.soft_reset, qk_threshold=qk_threshold,
                       block_m=block_m, block_n=block_n, block_k=block_k,
                       out_format=fmt, skip=skip, heads=heads,
                       emit_current=emit_current)
        spk = out.spikes
        if fmt == "packed":
            from ..kernels.packed import unpack_spikes

            spk = unpack_spikes(spk)
        primal = (_f32(spk), _f32(out.v_next)) if stateful else (_f32(spk),)
        return primal, out.current

    @jax.custom_vjp
    def f(operands):
        return run_kernel(operands, False)[0]

    def fwd(operands):
        # the kernel writes its post-bias/-residual membrane current out
        # once (emit_current) — the backward differentiates the cheap
        # elementwise tail from THAT instead of re-running the event-gated
        # matmul from the inputs
        primal, cur = run_kernel(operands, True)
        return primal, (operands, cur)

    def bwd(res, g):
        ops, cur = res
        w, q = ops["w"], ops.get("q")
        grads = {}
        if not stateful and _pallas_backward():
            # fully-fused stateless backward: dv = g_eff ⊙ surr'(cur - v_th)
            # happens INSIDE the dx kernel's transpose sweep
            (gs,) = g
            if q is not None:
                # primal-spike RECONSTRUCTION, constant wrt cur — the
                # surrogate factor flows through the dx kernel instead
                s_raw = (cur >= cfg.v_th).astype(gs.dtype)  # neurallint: disable=NL-BARE-HEAVISIDE
                masked_cot, vjp_q = jax.vjp(lambda q_: _mask(s_raw, q_), q)
                del masked_cot
                (grads["q"],) = vjp_q(gs)
                mask = _mask(jnp.ones_like(gs), q)
                g_eff = gs * jax.lax.stop_gradient(mask)
            else:
                g_eff = gs
            dx, dcur = _bwd_dx(g_eff, w, cur, surrogate=cfg.surrogate,
                               alpha=cfg.alpha, v_th=cfg.v_th, blocks=blocks)
        else:
            # elementwise tail from the cached current: surrogate spike,
            # reset, QK mask — a VPU pass, no matmul
            diff = {"cur": cur}
            for key in ("v_prev", "s_prev", "q"):
                if ops.get(key) is not None:
                    diff[key] = ops[key]

            def post(d):
                s, v_next = _lif_step(d["cur"], d.get("v_prev"),
                                      d.get("s_prev"), cfg)
                s = _mask(s, d.get("q"))
                return (s, v_next) if stateful else (s,)

            _, vjp = jax.vjp(post, diff)
            (dd,) = vjp(g)
            dcur = dd["cur"]
            for key in ("v_prev", "s_prev", "q"):
                if key in dd:
                    grads[key] = dd[key]
            dx, _ = _bwd_dx(dcur, w, blocks=blocks)
        # the spike operand's silent tiles skip the weight-grad contraction
        grads["x"] = dx
        grads["w"] = _bwd_dw(ops["x"], dcur, skip=skip, blocks=blocks)
        if ops.get("bias") is not None:
            grads["bias"] = dcur.sum(axis=0).reshape(ops["bias"].shape)
        if ops.get("residual") is not None:
            grads["residual"] = dcur
        out = {k: grads.get(k) for k in ops}
        return (out,)

    f.defvjp(fwd, bwd)
    return f


def _fused_pe_impl(kernels):
    def impl(st, w, *, bias, residual, q, v_prev, s_prev, qk_threshold,
             lif_cfg, fmt, block_m, block_n, block_k, skip="dense",
             heads=None):
        from .dispatch import FusedOut
        from .spike_tensor import SpikeTensor

        stateful = v_prev is not None
        f = _fused_pe_grad(kernels, lif_cfg, qk_threshold, fmt,
                           block_m, block_n, block_k, stateful, heads, skip)
        ops = {"x": _dense_operand(st), "w": _f32(w), "bias": _f32(bias)}
        if residual is not None:
            ops["residual"] = _dense_operand(residual)
        if q is not None:
            ops["q"] = _dense_operand(q)
        if stateful:
            ops["v_prev"] = _f32(v_prev)
            ops["s_prev"] = _f32(s_prev) if s_prev is not None \
                else jnp.zeros_like(ops["v_prev"])
        out = f(ops)
        spk = out[0]
        return FusedOut(SpikeTensor.dense(spk, block_m=block_m,
                                          block_k=block_n),
                        out[1] if stateful else None, None)
    return impl


# ----------------------------------------------------------- fused_pe_layer
@functools.lru_cache(maxsize=None)
def _fused_pe_layer_grad(cfg: LIFConfig, qk_threshold: float,
                         t: int, heads: Optional[tuple[int, int]] = None):
    # reference body only: the fused path chains per-timestep residual-
    # cached ``_fused_pe_grad`` vjps instead of one recompute-everything
    # custom_vjp over the whole T loop (see ``_fused_pe_layer_impl``)
    def ref_fwd(ops):
        x, w = ops["x"], ops["w"]
        spikes_ts = []
        v = s = None
        for ti in range(t):
            res_t = None if ops.get("residual") is None \
                else ops["residual"][ti]
            cur = _pe_current({"x": x[ti], "w": w, "bias": ops.get("bias"),
                               "residual": res_t})
            if t == 1:
                spk, _ = _lif_step(cur, None, None, cfg)
            else:
                # stateful form: the LIF carry holds the PRE-mask spikes;
                # the QK mask gates outside (the kernel layer's T>1 path)
                spk, v = _lif_step(cur, v, s, cfg)
                s = spk
            if ops.get("q") is not None and heads is not None:
                spk = _qk_headmask_apply(spk, ops["q"][ti], heads, None,
                                         qk_threshold, cfg.surrogate,
                                         cfg.alpha)
            elif ops.get("q") is not None:
                spk = spk * _qk_rowmask(
                    ops["q"][ti].reshape(spk.shape[0], -1), qk_threshold,
                    "threshold", cfg.surrogate, cfg.alpha)
            spikes_ts.append(spk)
        return jnp.stack(spikes_ts)

    return ref_fwd


def _fused_pe_layer_impl(kernels):
    def impl(st, w, *, bias, residual, q, qk_threshold, lif_cfg, fmt,
             block_m, block_n, block_k, skip="dense", heads=None):
        from .dispatch import FusedOut
        from .spike_tensor import SpikeTensor

        x = _dense_operand(st)
        t = x.shape[0]
        w_, bias_ = _f32(w), _f32(bias)
        res = None if residual is None else _dense_operand(residual)
        q_ = None if q is None else _dense_operand(q)

        if kernels == "reference":
            f = _fused_pe_layer_grad(lif_cfg, qk_threshold, t, heads)
            ops = {"x": x, "w": w_, "bias": bias_}
            if res is not None:
                ops["residual"] = res
            if q_ is not None:
                ops["q"] = q_
            spk = f(ops)
            return FusedOut(SpikeTensor.dense(spk, block_m=block_m,
                                              block_k=block_n), None, None)

        # fused: per-timestep residual-cached custom_vjp chain.  T=1 runs
        # the fully-fused masked stateless kernel; T>1 runs the stateful
        # kernel per step with the QK mask applied OUTSIDE on the pre-mask
        # carry — exactly the kernel layer's own T>1 semantics.
        spikes_ts = []
        if t == 1:
            f = _fused_pe_grad(kernels, lif_cfg, qk_threshold, fmt,
                               block_m, block_n, block_k, False, heads, skip)
            ops = {"x": x[0], "w": w_, "bias": bias_}
            if res is not None:
                ops["residual"] = res[0]
            if q_ is not None:
                ops["q"] = q_[0]
            spikes_ts.append(f(ops)[0])
        else:
            f = _fused_pe_grad(kernels, lif_cfg, qk_threshold, fmt,
                               block_m, block_n, block_k, True, None, skip)
            m, n = x.shape[1], w_.shape[1]
            v = jnp.zeros((m, n), jnp.float32)
            s = jnp.zeros((m, n), jnp.float32)
            for ti in range(t):
                ops = {"x": x[ti], "w": w_, "bias": bias_,
                       "v_prev": v, "s_prev": s}
                if res is not None:
                    ops["residual"] = res[ti]
                spk, v = f(ops)
                s = spk                      # pre-mask carry
                if q_ is not None and heads is not None:
                    spk = _qk_headmask_apply(spk, q_[ti], heads, None,
                                             qk_threshold, lif_cfg.surrogate,
                                             lif_cfg.alpha)
                elif q_ is not None:
                    spk = spk * _qk_rowmask(
                        q_[ti].reshape(spk.shape[0], -1), qk_threshold,
                        "threshold", lif_cfg.surrogate, lif_cfg.alpha)
                spikes_ts.append(spk)
        spk_t = jnp.stack(spikes_ts)
        return FusedOut(SpikeTensor.dense(spk_t, block_m=block_m,
                                          block_k=block_n), None, None)
    return impl


# ------------------------------------------------------------------ qk_mask
@functools.lru_cache(maxsize=None)
def _qk_mask_grad(kernels: str, threshold: float, mode: str, surrogate: str,
                  alpha: float):
    def ref_fwd(ops):
        return _qk_rowmask(ops["q"], threshold, mode, surrogate, alpha) \
            * ops["k"]

    if kernels == "reference":
        return ref_fwd

    def kernel_fwd(ops):
        if not _pallas_training():
            return ref_fwd(ops)
        from ..kernels.qk_attention import qk_attention_fused

        # "or" on non-negative integer spike counts == rowsum >= 1
        thr = 1.0 if mode == "or" else threshold
        return _f32(qk_attention_fused(ops["q"], ops["k"], threshold=thr))

    return _surrogate_vjp(kernel_fwd, ref_fwd)


def _qk_mask_impl(kernels):
    def impl(q, k, threshold, *, mode="threshold", surrogate="atan",
             alpha=2.0):
        f = _qk_mask_grad(kernels, threshold, mode, surrogate, alpha)
        return f({"q": _f32(q), "k": _f32(k)})
    return impl


# ---------------------------------------------------------------- dense_lif
@functools.lru_cache(maxsize=None)
def _dense_lif_grad(kernels: str, cfg: LIFConfig, qk_threshold: float,
                    fmt: str, has_bias: bool,
                    heads: Optional[tuple[int, int]] = None,
                    kv_heads: Optional[int] = None):
    grouped = (heads is not None and kv_heads is not None
               and kv_heads != heads[0])

    def _tail(cur, q):
        # everything after the membrane current: surrogate spike + the
        # head-blocked / grouped-KV mask chain — elementwise and cheap
        s = spike(cur - cfg.v_th, cfg.surrogate, cfg.alpha)
        if q is not None and heads is not None:
            s = _qk_headmask_apply(s, q, heads, kv_heads,
                                   qk_threshold, cfg.surrogate, cfg.alpha)
        elif q is not None:
            s = s * _qk_rowmask(q.reshape(s.shape[0], -1),
                                qk_threshold, "threshold", cfg.surrogate,
                                cfg.alpha)
        elif grouped:
            h, dh = heads
            m, g = s.shape[0], heads[0] // kv_heads
            s = jnp.broadcast_to(s.reshape(m, kv_heads, 1, dh),
                                 (m, kv_heads, g, dh)).reshape(m, h * dh)
        return s

    def _current(ops):
        cur = ops["x"] @ ops["w"]
        return cur + ops["b"] if has_bias else cur

    def ref_fwd(ops):
        # grouped KV (kv_heads < h): the matmul stays on the UNEXPANDED
        # weight — the group expansion happens inside the mask broadcast,
        # so its backward sums group cotangents into the shared columns
        return _tail(_current(ops), ops.get("q"))

    if kernels == "reference":
        return ref_fwd

    def run_kernel(ops, with_current):
        if not _pallas_training():
            # identical math as jnp; the cached current stays in the
            # GROUPED (unexpanded-weight) layout the vjp differentiates
            cur = _current(ops)
            return _tail(cur, ops.get("q")), (cur if with_current else None)
        from .impls import _dense_lif_fused
        from .spike_tensor import SpikeTensor

        p = {"w": ops["w"]}
        if has_bias:
            p["b"] = ops["b"]
        q = ops.get("q")
        out = _dense_lif_fused(p, ops["x"], cfg,
                               q=None if q is None else SpikeTensor.dense(q),
                               qk_threshold=qk_threshold, fmt=fmt,
                               heads=heads, kv_heads=kv_heads,
                               with_current=with_current)
        if not with_current:
            return _emitted_dense(out), None
        st, cur = out
        if grouped:
            # the kernel ran on group-EXPANDED weights, so its cached
            # current replicates each kv group's columns exactly — slice
            # one replica back to the grouped layout the vjp needs
            h, dh = heads
            m = cur.shape[0]
            cur = cur.reshape(m, kv_heads, h // kv_heads, dh)[:, :, 0, :]
            cur = cur.reshape(m, kv_heads * dh)
        return _emitted_dense(st), cur

    @jax.custom_vjp
    def f(operands):
        return run_kernel(operands, False)[0]

    def fwd(operands):
        primal, cur = run_kernel(operands, True)
        return primal, (operands, cur)

    def bwd(res, g):
        ops, cur = res
        diff = {"cur": cur}
        if ops.get("q") is not None:
            diff["q"] = ops["q"]

        _, vjp = jax.vjp(lambda d: _tail(d["cur"], d.get("q")), diff)
        (dd,) = vjp(g)
        # the matmul's own vjp: the same transposed contractions, in the
        # same order, that autodiff of ``ref_fwd`` runs
        _, mm_vjp = jax.vjp(_current, {k: ops[k] for k in ("x", "w", "b")
                                       if k in ops})
        (grads,) = mm_vjp(dd["cur"])
        if "q" in dd:
            grads["q"] = dd["q"]
        return ({k: grads.get(k) for k in ops},)

    f.defvjp(fwd, bwd)
    return f


def _dense_lif_impl(kernels):
    def impl(p, flat, cfg, *, q, qk_threshold, fmt, heads=None,
             kv_heads=None):
        from .spike_tensor import SpikeTensor

        f = _dense_lif_grad(kernels, cfg, qk_threshold, fmt, "b" in p,
                            heads, kv_heads)
        ops = {"x": _f32(flat), "w": _f32(p["w"])}
        if "b" in p:
            ops["b"] = _f32(p["b"])
        if q is not None:
            ops["q"] = _dense_operand(q)
        return SpikeTensor.dense(f(ops))
    return impl


# -------------------------------------------------------------- w2ttfs_head
@functools.lru_cache(maxsize=None)
def _w2ttfs_grad(kernels: str, window: int):
    from ..core.w2ttfs import w2ttfs_classifier

    def ref_fwd(ops):
        return w2ttfs_classifier(ops["spikes"], ops["fc_w"], ops["fc_b"],
                                 window)

    if kernels == "reference":
        return ref_fwd

    def kernel_fwd(ops):
        if not _pallas_training():
            return ref_fwd(ops)
        from ..kernels.w2ttfs_pool import w2ttfs_pool_fc

        return _f32(w2ttfs_pool_fc(ops["spikes"], ops["fc_w"], ops["fc_b"],
                                   window=window))

    return _surrogate_vjp(kernel_fwd, ref_fwd)


def _w2ttfs_impl(kernels):
    def impl(spikes, fc_w, fc_b, *, window):
        f = _w2ttfs_grad(kernels, window)
        return f({"spikes": _f32(spikes), "fc_w": _f32(fc_w),
                  "fc_b": _f32(fc_b)})
    return impl


# ------------------------------------------- differentiable data movement
# im2col / max-pool are pure data movement with native vjps (slicing and
# reduce_window); the grad-mode registrations only differ from the
# inference ones by PRESERVING the float dtype (the int8 casts in the
# inference impls are exact on {0,1} values but sever autodiff).

def _im2col_diff(st, spatial, kh, kw, stride, *, t, fmt):
    from ..models import nn
    from .spike_tensor import SpikeTensor

    b, h, w_, c = spatial
    x = _dense_operand(st)[:, :b * h * w_].reshape(t * b, h, w_, c)
    pat = nn.im2col(x, kh, kw, stride)
    _, ho, wo, kdim = pat.shape
    return (SpikeTensor.dense(pat.reshape(t, b * ho * wo, kdim),
                              block_m=st.block_m, block_k=st.block_k),
            (ho, wo))


def _pool_diff(st, spatial, *, t, window, fmt):
    from ..models import nn
    from .spike_tensor import SpikeTensor

    b, h, w_, c = spatial
    x = _dense_operand(st)[:, :b * h * w_].reshape(t * b, h, w_, c)
    pooled = nn.max_pool(x, window)
    h2, w2 = pooled.shape[1], pooled.shape[2]
    return (SpikeTensor.dense(pooled.reshape(t, b * h2 * w2, c),
                              block_m=st.block_m, block_k=st.block_k),
            (h2, w2))


# ------------------------------------------------------------ registration
def _register_all() -> None:
    for kernels in ("reference", "fused"):
        mode = f"{kernels}+grad"
        register("matmul", mode)(_matmul_impl(kernels))
        register("lif", mode)(_lif_impl(kernels))
        register("fused_pe", mode)(_fused_pe_impl(kernels))
        register("fused_pe_layer", mode)(_fused_pe_layer_impl(kernels))
        register("qk_mask", mode)(_qk_mask_impl(kernels))
        register("dense_lif", mode)(_dense_lif_impl(kernels))
        register("w2ttfs_head", mode)(_w2ttfs_impl(kernels))
        register("im2col", mode)(_im2col_diff)
        register("pool", mode)(_pool_diff)


_register_all()
