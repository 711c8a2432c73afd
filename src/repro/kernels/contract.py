"""Kernel-contract declarations: what each family PROMISES the registry.

The sparsity/event machinery lives or dies on metadata contracts —
``vld_cnt`` block maps, ``occ`` word-occupancy bitmaps, packed pad lanes,
head lane masks — being honored at every ``(op, mode)`` boundary. Runtime
asserts (``check_block_contract``, the packed-pad-lane integrity guard)
catch violations *on the shapes that happen to run*; the static pass in
``repro.analysis.contracts`` proves them over the whole registry before
anything runs on hardware. This module is the declaration side of that
pass: each kernel family publishes ONE ``KernelContract`` stating which
registry ops it backs, which policy axes those ops support, and a static
VMEM-residency model derived from its BlockSpecs.

Declarations are plain data — this module imports nothing from the ops or
analysis layers, so a family's ``ops.py`` can declare at import time
without cycles. ``kernel_contracts()`` is the aggregation point the
verifier (and ``tools/neurallint.py``) walks.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

#: spikes per packed int32 word (mirrors core.events.LANE_BITS without the
#: import — contract declarations must stay dependency-free)
LANE_BITS = 32

#: the seven kernel families; ``kernel_contracts`` imports each family's
#: ``ops`` module so a missing declaration is a hard error, not a silent
#: coverage gap
FAMILIES = ("spike_matmul", "lif_update", "fused_pe", "packed",
            "qk_attention", "flash_attention", "w2ttfs_pool")


@dataclasses.dataclass(frozen=True)
class KernelContract:
    """One family's registry contract.

    family       : kernel-family package name under ``repro.kernels``.
    ops          : registry op names the family backs (the keys its
                   ``repro.ops.impls`` registrations use).
    modes        : base kernel modes registered per op ("reference"/"fused";
                   the "+grad" variants are derived from ``grad``).
    formats      : spike-map formats the ops accept/emit.
    skips        : byte-skip strategies the matmul-sweep ops accept.
    grad         : True when the family participates in the "+grad" axis
                   (every op must then also resolve "<mode>+grad").
    grad_ops     : when only a subset of ``ops`` participates in "+grad",
                   name them here (overrides ``grad`` per op; e.g. the
                   packed family's im2col/pool differentiate but
                   pack/unpack are inference-only format conversions).
    emits_spikes : True when outputs are SpikeTensors — the metadata-
                   propagation contract (vld_cnt present + shape-consistent
                   on every packed output) applies.
    head_blocked : True when the op takes ``heads=(h, dh)`` (the verifier
                   sweeps multi-head configs through it).
    vmem_bytes   : static VMEM-residency model derived from the kernel's
                   BlockSpecs: worst-case bytes resident per grid step for
                   a given tiling. Signature ``(block_m, block_n, block_k,
                   packed) -> int``; None for families whose working set is
                   not block-tiled (checked against the corpus shapes
                   instead).
    """
    family: str
    ops: tuple
    modes: tuple = ("reference", "fused")
    formats: tuple = ("dense", "packed")
    skips: tuple = ("dense",)
    grad: bool = False
    grad_ops: Optional[tuple] = None
    emits_spikes: bool = False
    head_blocked: bool = False
    vmem_bytes: Optional[Callable[[int, int, int, bool], int]] = None

    def gradient_ops(self) -> tuple:
        """The ops that must resolve both ``+grad`` registry modes."""
        if self.grad_ops is not None:
            return self.grad_ops
        return self.ops if self.grad else ()


_CONTRACTS: dict[str, KernelContract] = {}


def declare(contract: KernelContract) -> KernelContract:
    """Register a family's contract (called at family-ops import time)."""
    _CONTRACTS[contract.family] = contract
    return contract


def kernel_contracts() -> dict[str, KernelContract]:
    """All declared contracts, forcing every family's declaration in.

    Importing each family's ``ops`` module here (not at module import) keeps
    ``repro.kernels.contract`` importable without dragging Pallas in, while
    guaranteeing the verifier sees a contract for every family — an
    undeclared family raises instead of shrinking the sweep.
    """
    import importlib

    for fam in FAMILIES:
        importlib.import_module(f"repro.kernels.{fam}.ops")
        if fam not in _CONTRACTS:
            raise RuntimeError(
                f"kernel family {fam!r} declares no KernelContract — every "
                f"family must declare() one in its ops module so the static "
                f"verifier covers it")
    return dict(_CONTRACTS)


# ---------------------------------------------------------- VMEM tile models
#: widest packed operand row the budget is priced at. Kernels take packed
#: words a whole row block at a time (``kernels.words``), so their
#: residency grows with the row width, not with ``block_k``; the widest
#: packed contraction of the served models is d_ff 6144.
PACKED_ROW_BITS = 8192


def packed_rows_vmem(block_m: int) -> int:
    """One packed operand's row block: the double-buffered ``(block_m,
    W)`` words plus their transposed ``(W, block_m)`` scratch."""
    return 3 * block_m * (PACKED_ROW_BITS // LANE_BITS) * 4


def matmul_vmem(block_m: int, block_n: int, block_k: int,
                packed: bool) -> int:
    """Spike-matmul sweep residency: the x operand (packed: the row
    block's words + their transposed scratch + the f32 bits of one K-tile;
    dense: the int8 tile), one f32 w tile, one f32 accumulator tile, plus
    the scalar-prefetched metadata row.  The family budget is the max over
    its forward and BACKWARD sweeps — the dx backward holds all-f32 tiles
    (cotangent + w + dx accumulator) plus the cached-current tile its
    fused surrogate factor re-reads."""
    if packed:
        x = packed_rows_vmem(block_m) + block_m * block_k * 4
    else:
        x = block_m * block_k
    meta = 4 * (block_k // 8 + 2)            # vld row + nact/kmap scalars
    fwd = x + block_k * block_n * 4 + block_m * block_n * 4 + meta
    bwd = (block_m * block_n * 4              # incoming cotangent tile
           + block_k * block_n * 4            # w tile (transposed read)
           + block_m * block_k * 4            # dx accumulator
           + block_m * block_n * 4            # cached membrane current
           + meta)
    return max(fwd, bwd)


def fused_pe_vmem(block_m: int, block_n: int, block_k: int,
                  packed: bool) -> int:
    """Fused PE adds to the matmul sweep: bias row, residual tile, LIF
    state tiles (v f32 + s int8), the Q tile for the write-back mask, the
    emitted spike tile (packed: the output row block and a packed
    residual row block, each with its transposed scratch), and the f32
    membrane-current tile the training forward writes back
    (``emit_current`` — the residual cache the event-skipped backward
    differentiates from)."""
    extra = (block_n * 4                      # bias
             + block_m * block_n * 4          # residual
             + block_m * block_n * 5          # v_prev f32 + s_prev int8
             + block_m * 128                  # q row block (lane-padded)
             + block_m * block_n              # emitted int8 spike tile
             + block_m * block_n * 4)         # emit_current f32 tile
    if packed:
        extra += 2 * packed_rows_vmem(block_m)
    return matmul_vmem(block_m, block_n, block_k, packed) + extra


def pack_vmem(block_m: int, block_n: int, block_k: int, packed: bool) -> int:
    """Pack/unpack pair: one int8 tile in, the row block's words out (the
    vld/occ maps live in SMEM)."""
    return block_m * block_k + packed_rows_vmem(block_m)
