"""Fused PE dataflow kernel (paper Fig 3 + Fig 5 in ONE Pallas pass).

NEURAL's central claim is that a PE executes the whole per-layer dataflow —
event-gated MAC accumulation, LIF membrane update, and the QKFormer token
attention — "on the fly ... within the baseline computing flow without
requiring dedicated hardware units". Our previous reproduction ran that
chain as four separate kernels with full HBM round-trips between stages:

    spike_matmul -> [f32 pre-act HBM] -> lif_update -> [int8 spikes HBM]
                 -> qk_attention      -> [spikes HBM] -> block_count_map_2d

This kernel is the TPU realization of the paper's fusion: per output tile,

  1. accumulate the event-skipped spike matmul over the K grid axis using
     the scalar-prefetched ``vld_cnt`` map (PipeSDA metadata, paper C3) —
     ``@pl.when(vld_cnt > 0)`` skips silent blocks exactly as
     ``spike_matmul`` does (Fig 3 (2)/(3): SDU FIFO + MAC gating);
  2. on the LAST K step, add bias / residual current and apply the LIF
     membrane update in-register (Fig 3 (4): tau decay, threshold,
     hard/soft reset) — the f32 pre-activation NEVER touches HBM;
  3. optionally gate the emitted spikes with the QK token mask computed
     from Q's row sums (Fig 5 (2) atten_reg -> (4) write-back fusion);
  4. emit the NEXT layer's ``vld_cnt`` block-count map as a second output,
     so layer L produces layer L+1's PipeSDA routing metadata on the fly
     instead of a separate reduction pass re-reading the spikes from HBM.

Event COMPRESSION (the ``packed_*`` static flags): every spike operand can
arrive bit-packed — 32 spikes per int32 lane, the ``PackedSpikes`` HBM
format — and the emitted spike map can leave bit-packed. Packed K-tiles /
residual tiles are unpacked in VMEM right before use; a packed Q tile's row
sum is a popcount (no unpack at all); the packed output is built from the
in-register spike tile during write-back. With ``packed_in + packed_out``
a chained layer moves ~1/8th the spike bytes over HBM in each direction
while producing bit-identical spikes.

Inputs (optional operands selected by static flags):
  x        [M, K]  int8 spikes (or dense activations; only zero-blocks skip)
           packed_in:  [M, K/32] int32 words
  w        [K, N]  weights
  bias     [1, N]  f32  (with_bias)    — F&Q-folded BN bias
  residual [M, N]  f32  (with_residual)— shortcut membrane current (MS-ResNet)
           packed_residual: [M, N/32] int32 words (binary spike shortcut)
  v_prev   [M, N]  f32  (with_state)   — membrane state for T>1
  s_prev   [M, N]  int8 (with_state)   — previous-step spikes for hard reset
  q        [M, Dq] int8 (apply_qk)     — Q spikes; row-sum -> token mask
           packed_q: [M, Dq/32] int32 words; row-sum == popcount row-sum

Outputs:
  spikes   [M, N]        int8; packed_out: [M, N/32] int32 words
  v_next   [M, N]        f32   (with_state only — T=1 deployed mode skips
                                the write entirely: s = H(I - v_th))
  vld_next [M/bm, N/bn]  int32 (emit_vld) — per-tile nonzero count of the
                                EMITTED (post-mask) spikes

Grid is (M/bm, N/bn, K/bk) with K innermost; an f32 VMEM scratch tile is
the accumulator (it persists across the sequential K sweep). ``m_valid`` /
``n_valid`` mask padded rows/cols out of the spike map and the emitted
count map, so padding stays inert for ANY bias/threshold values.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.events import LANE_BITS, compact_kmap, head_lane_masks
from ..gating import accum_tile
from ..words import (pack_tile_t, row_spec, tile_bits_t, transpose_words,
                     x_operand_spec)

Array = jax.Array


def _make_kernel(*, tau: float, v_th: float, soft_reset: bool,
                 qk_threshold: float, with_bias: bool, with_residual: bool,
                 with_state: bool, apply_qk: bool, emit_vld: bool,
                 emit_current: bool,
                 m_valid: int, n_valid: int, block_m: int, block_n: int,
                 block_k: int, packed_in: bool, packed_q: bool,
                 packed_residual: bool, packed_out: bool, skip: str = "dense",
                 heads: tuple[int, int] | None = None):
    wpb_k = block_k // LANE_BITS
    wpb_n = block_n // LANE_BITS

    def kernel(*allrefs):
        # scalar-prefetch block: vld map (dense) or the compacted routing
        # tables (gated / two_level) from core.events.compact_kmap
        occ_ref = None
        if skip == "dense":
            vld_ref, *refs = allrefs
        elif skip == "gated":
            nact_ref, kmap_ref, *refs = allrefs
        else:
            nact_ref, kmap_ref, occ_ref, *refs = allrefs
        it = iter(refs)
        x_ref = next(it)
        w_ref = next(it)
        b_ref = next(it) if with_bias else None
        r_ref = next(it) if with_residual else None
        v_ref = next(it) if with_state else None
        s_ref = next(it) if with_state else None
        q_ref = next(it) if apply_qk else None
        sel_ref = next(it) if apply_qk and packed_q and heads else None
        spike_ref = next(it)
        vout_ref = next(it) if with_state else None
        cnt_ref = next(it) if emit_vld else None
        cur_ref = next(it) if emit_current else None
        acc_ref = next(it)
        # transposed packed-word scratches (kernels.words)
        xt_ref = next(it) if packed_in else None
        rt_ref = next(it) if packed_residual else None
        ot_ref = next(it) if packed_out else None

        i = pl.program_id(0)
        j = pl.program_id(1)
        k = pl.program_id(2)
        kb = k if skip == "dense" else kmap_ref[i, k]
        src = xt_ref if packed_in else x_ref

        @pl.when(k == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            if packed_in:
                transpose_words(x_ref, src)

        if skip == "dense":
            # event skip: silent block -> no MXU (bytes still stream)
            gate = vld_ref[i, k] > 0
        else:
            # steps past nact[i] revisit the last active block index, so
            # the BlockSpec never changes -> no DMA; this skips the MXU
            gate = k < nact_ref[i]

        @pl.when(gate)
        def _accum():
            accum_tile(acc_ref, src, w_ref, wpb=wpb_k if packed_in else None,
                       kb=kb, occ_bits=(occ_ref[i, kb] if skip == "two_level"
                                        else None))

        @pl.when(k == pl.num_programs(2) - 1)
        def _writeback():
            cur = acc_ref[...]
            if with_bias:
                cur = cur + b_ref[...].astype(jnp.float32)
            if with_residual:
                if packed_residual:  # binary spike shortcut, stored packed
                    transpose_words(r_ref, rt_ref)
                    cur = cur + tile_bits_t(rt_ref, j, wpb_n).T
                else:
                    cur = cur + r_ref[...].astype(jnp.float32)
            if emit_current:
                # residual cache for the backward: the post-bias/-residual
                # membrane current leaves ONCE, instead of the vjp
                # re-running the whole event-gated matmul from its inputs
                cur_ref[...] = cur
            if with_state:
                v_prev = v_ref[...].astype(jnp.float32)
                s_prev = s_ref[...].astype(jnp.float32)
                v = tau * v_prev * (1.0 - s_prev) + cur
            else:                    # deployed T=1: v[0]=0 -> v = I
                v = cur
            spk = (v >= v_th).astype(jnp.float32)
            if with_state:
                if soft_reset:
                    vout_ref[...] = v - v_th * spk
                else:
                    vout_ref[...] = v * (1.0 - spk)
            if apply_qk and heads is None:
                # Fig 5: atten_reg gates the write-back (whole-row mask)
                if packed_q:         # row sum of packed spikes == popcount
                    rowsum = jnp.sum(
                        jax.lax.population_count(q_ref[...]), axis=1,
                        keepdims=True).astype(jnp.float32)
                else:
                    rowsum = q_ref[...].astype(jnp.float32).sum(
                        axis=1, keepdims=True)
                spk = spk * (rowsum >= qk_threshold).astype(jnp.float32)
            elif apply_qk:
                # head-blocked Fig 5: one atten_reg per head — per-head row
                # sums over Q's head slice gate only that head's output
                # columns. Static per-head slices / lane masks keep this on
                # the VPU (no gathers); pad columns map to no head.
                hq, dh = heads
                cols = (jax.lax.broadcasted_iota(
                    jnp.int32, (block_m, block_n), 1) + j * block_n)
                head_of_col = cols // dh
                gate = jnp.zeros((block_m, block_n), jnp.float32)
                for hh in range(hq):
                    if packed_q:     # per-head popcount over the word lanes
                        rs = jnp.sum(jax.lax.population_count(
                            q_ref[...] & sel_ref[hh:hh + 1, :]), axis=1,
                            keepdims=True).astype(jnp.float32)
                    else:
                        rs = q_ref[:, hh * dh:(hh + 1) * dh].astype(
                            jnp.float32).sum(axis=1, keepdims=True)
                    gate = gate + ((rs >= qk_threshold)
                                   & (head_of_col == hh)
                                   ).astype(jnp.float32)
                spk = spk * gate
            if m_valid % block_m or n_valid % block_n:
                rows = (jax.lax.broadcasted_iota(
                    jnp.int32, (block_m, block_n), 0) + i * block_m)
                cols = (jax.lax.broadcasted_iota(
                    jnp.int32, (block_m, block_n), 1) + j * block_n)
                spk = spk * ((rows < m_valid) & (cols < n_valid)
                             ).astype(jnp.float32)
            if packed_out:           # compress in-register before the write
                ot_ref[pl.ds(j * wpb_n, wpb_n), :] = pack_tile_t(spk)

                @pl.when(j == pl.num_programs(1) - 1)  # row block complete
                def _store():
                    spike_ref[...] = ot_ref[...].T
            else:
                spike_ref[...] = spk.astype(spike_ref.dtype)
            if emit_vld:             # on-the-fly next-layer PipeSDA metadata
                cnt_ref[i, j] = jnp.sum(spk).astype(jnp.int32)

    return kernel


@functools.partial(jax.jit,
                   static_argnames=("tau", "v_th", "soft_reset",
                                    "qk_threshold", "block_m", "block_n",
                                    "block_k", "emit_vld", "emit_current",
                                    "m_valid",
                                    "n_valid", "packed_in", "packed_q",
                                    "packed_residual", "packed_out",
                                    "skip", "heads", "interpret"))
def fused_pe_pallas(x: Array, w: Array, vld_cnt: Array,
                    bias: Array | None = None,
                    residual: Array | None = None,
                    v_prev: Array | None = None,
                    s_prev: Array | None = None,
                    q: Array | None = None,
                    occ: Array | None = None, *,
                    tau: float = 0.5, v_th: float = 1.0,
                    soft_reset: bool = False, qk_threshold: float = 1.0,
                    block_m: int = 128, block_n: int = 128,
                    block_k: int = 128, emit_vld: bool = True,
                    emit_current: bool = False,
                    m_valid: int | None = None, n_valid: int | None = None,
                    packed_in: bool = False, packed_q: bool = False,
                    packed_residual: bool = False, packed_out: bool = False,
                    skip: str = "dense",
                    heads: tuple[int, int] | None = None,
                    interpret: bool = False):
    """Block-aligned core. All shapes must already be padded to the blocks;
    use ``repro.kernels.fused_pe.ops.fused_pe`` for the padding wrapper.
    ``m_valid``/``n_valid`` are the pre-padding extents: spikes and counts
    in the padded margin are forced to zero (bias alone could otherwise
    fire pad rows). The ``packed_*`` flags select the bit-packed layout for
    the corresponding spike operand / output (int32 words along the packed
    axis, 32 spikes per lane).

    ``skip`` selects the byte-skip strategy: ``"dense"`` streams every tile
    and gates the MXU on ``vld_cnt``; ``"gated"`` walks the compacted
    non-silent block list (silent x/w tiles never DMA'd); ``"two_level"``
    additionally elides silent 32-column stripes inside active tiles via
    the ``occ`` word-occupancy bitmap (required for that mode).

    ``heads=(h, dh)`` makes the QK write-back HEAD-BLOCKED: Q and the
    output are treated as ``h`` head blocks of width ``dh`` each, the row
    sum / threshold mask is computed per head (packed Q: per-head
    popcounts through static lane masks), and each head's mask gates only
    its own output columns — the multi-head form of the Fig-5 fusion.
    Requires ``n_valid == h * dh`` (the output must be exactly the
    head-concatenated map). ``None`` keeps the whole-row mask.

    ``emit_current`` additionally emits the post-bias/-residual membrane
    current as an f32 [M, N] output — the residual cache the event-skipped
    backward differentiates from instead of recomputing the matmul.

    Returns (spikes, v_next | None, vld_next | None, current | None).
    """
    m = x.shape[0]
    k = x.shape[1] * LANE_BITS if packed_in else x.shape[1]
    k2, n = w.shape
    assert k == k2 and m % block_m == 0 and k % block_k == 0 \
        and n % block_n == 0, (x.shape, w.shape, block_m, block_n, block_k)
    if packed_in or packed_out or packed_residual:
        assert block_k % LANE_BITS == 0 and block_n % LANE_BITS == 0
    with_state = v_prev is not None
    assert (s_prev is not None) == with_state
    assert skip in ("dense", "gated", "two_level"), skip
    if heads is not None:
        assert q is not None, "heads=(h, dh) requires the q operand"
        assert heads[0] * heads[1] == (n_valid or n), \
            (heads, n_valid or n)   # output == head-concatenated map
    grid = (m // block_m, n // block_n, k // block_k)

    kern = _make_kernel(
        tau=tau, v_th=v_th, soft_reset=soft_reset, qk_threshold=qk_threshold,
        with_bias=bias is not None, with_residual=residual is not None,
        with_state=with_state, apply_qk=q is not None, emit_vld=emit_vld,
        emit_current=emit_current,
        m_valid=m_valid or m, n_valid=n_valid or n,
        block_m=block_m, block_n=block_n, block_k=block_k,
        packed_in=packed_in, packed_q=packed_q,
        packed_residual=packed_residual,
        packed_out=packed_out, skip=skip, heads=heads)

    # scalar-prefetch set: vld map (dense) or the compacted routing tables
    # (gated / two_level); index maps receive the refs as trailing args
    if skip == "dense":
        scalars = (vld_cnt,)

        def x_idx(i, j, kk, *refs):
            return (i, kk)

        def w_idx(i, j, kk, *refs):
            return (kk, j)
    else:
        nact, kmap = compact_kmap(vld_cnt)
        if skip == "two_level":
            assert occ is not None, "two_level gating needs the occ bitmap"
            scalars = (nact, kmap, occ)
        else:
            scalars = (nact, kmap)

        def x_idx(i, j, s, nact_ref, kmap_ref, *rest):
            return (i, kmap_ref[i, s])

        def w_idx(i, j, s, nact_ref, kmap_ref, *rest):
            return (kmap_ref[i, s], j)

    x_spec, scratch, _ = x_operand_spec(x, packed_in, block_m, block_k,
                                        x_idx)
    in_specs = [x_spec, pl.BlockSpec((block_k, block_n), w_idx)]
    operands = [x, w]
    if bias is not None:
        in_specs.append(pl.BlockSpec((1, block_n),
                                     lambda i, j, kk, *refs: (0, j)))
        operands.append(bias.reshape(1, n))
    if packed_residual:
        in_specs.append(row_spec(block_m, n // LANE_BITS,
                                 lambda i, j, kk, *refs: i))
        operands.append(residual)
        scratch.append(pltpu.VMEM((n // LANE_BITS, block_m), jnp.int32))
    elif residual is not None:
        in_specs.append(pl.BlockSpec((block_m, block_n),
                                     lambda i, j, kk, *refs: (i, j)))
        operands.append(residual)
    if with_state:
        in_specs += [pl.BlockSpec((block_m, block_n),
                                  lambda i, j, kk, *refs: (i, j))] * 2
        operands += [v_prev, s_prev]
    if q is not None:
        dq = q.shape[1]
        in_specs.append(pl.BlockSpec((block_m, dq),
                                     lambda i, j, kk, *refs: (i, 0)))
        operands.append(q)
        if packed_q and heads is not None:
            # per-head word masks: a constant operand, since a kernel body
            # cannot capture one
            sel = head_lane_masks(*heads, dq * LANE_BITS)
            in_specs.append(pl.BlockSpec(sel.shape,
                                         lambda i, j, kk, *refs: (0, 0)))
            operands.append(sel)

    if packed_out:
        out_shape = [jax.ShapeDtypeStruct((m, n // LANE_BITS), jnp.int32)]
        out_specs = [row_spec(block_m, n // LANE_BITS,
                              lambda i, j, kk, *refs: i)]
        scratch.append(pltpu.VMEM((n // LANE_BITS, block_m), jnp.int32))
    else:
        out_shape = [jax.ShapeDtypeStruct((m, n), jnp.int8)]
        out_specs = [pl.BlockSpec((block_m, block_n),
                                  lambda i, j, kk, *refs: (i, j))]
    if with_state:
        out_shape.append(jax.ShapeDtypeStruct((m, n), jnp.float32))
        out_specs.append(pl.BlockSpec((block_m, block_n),
                                      lambda i, j, kk, *refs: (i, j)))
    if emit_vld:
        out_shape.append(jax.ShapeDtypeStruct(
            (m // block_m, n // block_n), jnp.int32))
        out_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
    if emit_current:
        out_shape.append(jax.ShapeDtypeStruct((m, n), jnp.float32))
        out_specs.append(pl.BlockSpec((block_m, block_n),
                                      lambda i, j, kk, *refs: (i, j)))

    outs = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=grid,
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)]
            + scratch,
        ),
        out_shape=out_shape,
        interpret=interpret,
    )(*scalars, *operands)

    outs = list(outs)
    spikes = outs.pop(0)
    v_next = outs.pop(0) if with_state else None
    vld_next = outs.pop(0) if emit_vld else None
    current = outs.pop(0) if emit_current else None
    return spikes, v_next, vld_next, current
