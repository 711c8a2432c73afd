"""Batched serving engine: continuous batching over a fixed slot pool, with
an elastic-FIFO chunked-prefill pipeline (the paper's FIFO-decoupled hybrid
data-event execution applied at the request-scheduling layer).

Design (vLLM-style, TPU-static-shapes edition):
  * ``max_slots`` concurrent sequences share one preallocated KV cache of
    shape [L, max_slots, max_len, Hkv, Dh] — slots are rows of the batch dim.
  * prefill runs per-request (padded to ``prefill_pad`` buckets so a handful
    of compiled shapes serve all prompt lengths) and WRITES the produced
    cache into the slot row.
  * decode is ONE jitted step over the whole pool every tick regardless of
    how many slots are live (static shape — idle slots compute garbage that
    is masked out; this is the standard TPU trade).
  * completion (EOS or max_new) frees the slot; queued requests are admitted
    on the next tick — continuous batching.
  * spiking/QKFormer models (attention_kind='qk_spiking') have an EMPTY
    attention cache (token-local masks), so the same engine serves them with
    per-slot state of size 0 — the paper's O(1)-decode claim in practice.

Elastic-FIFO pipeline (``prefill_chunk > 0``), mirroring the paper's FIFO
depth elasticity in software:
  * chunked prefill — each prompt is split into ``prefill_chunk``-token
    chunks that run through ``LM.prefill_chunk`` against a per-request
    bucket cache; at most ``prefill_chunks_per_tick`` chunks run per engine
    tick, so one long prompt can no longer freeze every live decode slot
    (head-of-line stall → bounded p99 decode-tick latency). Bit-identical
    to the blocking prefill under greedy decode: chunks cover the same
    padded bucket, so every reduction runs over the same axis lengths.
    (Caveat: above ``cfg.flash_threshold`` the blocking prefill switches
    to flash accumulation, whose different f32 reduction order chunked
    prefill does not reproduce — raise the threshold for strict parity on
    very long prompts.)
  * elastic admission FIFO — ``max_queue`` bounds the submit queue;
    ``submit`` applies backpressure by donating engine ticks (draining the
    pipeline) until a queue slot frees, like a producer stalling on a full
    hardware FIFO. Occupancy high-water marks are exported via ``stats()``.
  * per-slot output FIFOs — sampled tokens stream into a per-request FIFO
    (``pop_output``); with ``out_fifo_depth`` set, a slot whose consumer
    stops draining is STALLED (its cache row is restored after the pool
    decode, its token re-fed next tick — exact and order-preserving under
    greedy decode; temperature sampling draws from the engine's shared RNG
    stream, whose consumption order stalls reshuffle) while the other
    slots keep decoding: downstream backpressure without head-of-line
    blocking.

Sampling: greedy or temperature (per request). The greedy slots of a pool
decode share one jitted row-wise argmax and one fetch of its [max_slots]
tokens per tick; temperature slots sample one slot at a time.

Observability: every ``step()`` appends one record to a bounded tick log
(``last_tick``; aggregated in ``stats()``) with the nanoseconds of each
phase that ran and the number of device->host syncs the tick made, and runs
each phase inside a ``serve.<phase>`` span of the JAX profiler, on the same
clock as the device's events when a trace is active (see ``step``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import itertools
import time
from collections import deque
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import ops
from ..core.events import pad_lane_mask
from .faults import FaultPlan, ReplicaFailure

Array = jax.Array

# Profiler span prefix of the engine's phases (see ``Engine.step``).
SPAN_PREFIX = "serve."

# Jitted engine step functions shared across Engine instances of the same
# (model class, config): a process serving N replicas — or a test suite
# constructing many engines — compiles each (shape, config) combination
# exactly once instead of once per engine.
_JIT_CACHE: dict = {}

# Greedy tokens of a whole pool decode: one row-wise argmax, so a tick
# fetches one [max_slots] vector however many slots are live.
_greedy_rows = jax.jit(lambda logits: jnp.argmax(logits, axis=-1))


def _jitted_steps(model):
    key = (type(model), model.cfg)
    if key not in _JIT_CACHE:
        def prefill_fn(params, tokens):
            return model.prefill(params, {"tokens": tokens},
                                 return_all_logits=True)

        chunk_fn = getattr(model, "prefill_chunk", None)
        _JIT_CACHE[key] = (jax.jit(prefill_fn),
                           jax.jit(model.decode_step),
                           jax.jit(chunk_fn) if chunk_fn else None)
    return _JIT_CACHE[key]


class QueueFull(RuntimeError):
    """Raised by ``submit`` when the admission FIFO stays full (non-blocking
    submit, or a blocking submit that exhausted its tick budget)."""


class StalledEngine(RuntimeError):
    """``run_until_drained`` detected a livelock: work is still pending but
    no pipeline stage has made progress for the grace window (or the tick
    budget ran out). The message names the stuck slots and FIFO depths;
    ``report`` carries the same data machine-readably."""

    def __init__(self, msg: str, report: Optional[dict] = None):
        super().__init__(msg)
        self.report = report or {}


def clear_jit_cache() -> None:
    """Drop the shared jitted-step cache. Needed when a process-global ops
    demotion (``repro.ops.fallback``) is reset and the engine must re-trace
    through the restored fused kernels — compiled executables baked the
    demoted graph in."""
    _JIT_CACHE.clear()


# Request.status lifecycle. "done" is the only SUCCESS terminal; the
# ``done`` bool means "terminal" (any of the last four).
STATUS_QUEUED = "queued"
STATUS_PREFILL = "prefill"
STATUS_DECODE = "decode"
STATUS_DONE = "done"
STATUS_CANCELLED = "cancelled"
STATUS_DEADLINE = "deadline_miss"
STATUS_FAILED = "failed"
TERMINAL = (STATUS_DONE, STATUS_CANCELLED, STATUS_DEADLINE, STATUS_FAILED)


@dataclasses.dataclass(eq=False)
class Request:
    uid: int
    prompt: np.ndarray                  # [S] int32
    max_new: int = 32
    temperature: float = 0.0            # 0 = greedy
    eos_id: Optional[int] = None
    # deadlines (absolute, resolved at submit; None = none)
    deadline_tick: Optional[int] = None
    deadline_t: Optional[float] = None
    # -- filled by the engine --
    out: list = dataclasses.field(default_factory=list)
    fifo: deque = dataclasses.field(default_factory=deque)  # undrained tokens
    slot: int = -1
    done: bool = False
    status: str = STATUS_QUEUED
    retries: int = 0                    # quarantine evict->requeue count
    pushed: int = 0                     # tokens ever pushed to the FIFO:
    # a quarantine replay regenerates the greedy stream from scratch but
    # only pushes tokens PAST this mark — at-most-once delivery
    enqueued_t: float = 0.0
    admitted_t: float = 0.0             # first taken from the admission FIFO
    first_token_t: float = 0.0
    finished_t: float = 0.0
    enqueued_tick: int = 0
    first_token_tick: int = -1


@dataclasses.dataclass
class _PrefillJob:
    """One request's in-flight chunked prefill (an elastic-FIFO entry)."""
    req: Request
    slot: int
    cache: dict                         # per-request bucket cache
    bucket: int                         # positions this job must process
    done: int = 0                       # positions processed so far
    last_logits: Optional[Array] = None  # logits at the prompt's last token


@dataclasses.dataclass
class EngineConfig:
    max_slots: int = 8
    max_len: int = 512
    prefill_pad: int = 64               # prompt length bucket size
    # --- elastic-FIFO pipeline ---
    # prefill_chunk > 0: split prefill into chunks of this many tokens that
    # interleave with decode ticks (0 = blocking, monolithic prefill). The
    # engine rounds the chunk up to the model family's exactness granularity
    # (``cfg.prefill_chunk_align``: ssm/hybrid chunk on ssm_chunk bounds).
    prefill_chunk: int = 0
    prefill_chunks_per_tick: int = 1    # prefill work budget per decode tick
    max_queue: int = 0                  # admission FIFO bound (0 = unbounded)
    submit_block_ticks: int = 10_000    # backpressure budget before QueueFull
    out_fifo_depth: int = 0             # per-slot output FIFO bound (0 = inf)
    # policy: how THIS engine executes qk_spiking models, overriding the
    # model config's own policy (repro.ops.ExecutionPolicy or a preset
    # name). "fused_dense"/"fused_packed" route the LIF projections and
    # binary-activation matmuls through the fused-PE / spike_matmul Pallas
    # kernels (forward-exact; serving is inference, so the missing
    # surrogate gradient is irrelevant); a packed policy additionally ships
    # the masked attention spike maps bit-packed (32 spikes per int32
    # lane), caches each slot's spike state packed, and measures spike
    # sparsity + packed bytes in flight every decode tick (see ``stats``).
    # None = inherit the model's policy unchanged.
    policy: Optional[Any] = None
    # deprecated flag pair -> policy (repro.ops.compat translates + warns);
    # each flag ESCALATES only its own policy axis — exactly the pre-policy
    # engine's semantics, which could switch features on but never off
    use_event_kernels: Optional[bool] = None
    spike_format: Optional[str] = None
    # measure spike telemetry every Nth decode tick (0 disables): each
    # measurement syncs the packed state pool to host, so latency-sensitive
    # deployments should sample sparsely
    spike_stats_every: int = 1
    # --- self-healing ---
    # run the per-tick integrity guard every Nth decode tick (0 disables):
    # one jitted scan over the slot-pool cache + logits (finite-check on
    # float state, pad-lane invariant on packed spike words) whose verdict
    # is a [max_slots] bool pair — a flagged LIVE slot is quarantined
    # (evicted, scrubbed, requeued) instead of crashing the engine
    integrity_every: int = 0
    # quarantine retry budget: a request evicted more than this many times
    # is failed (status "failed") instead of requeued again
    quarantine_retries: int = 2
    # default per-request deadline in engine ticks (0 = none); individual
    # submits may override
    deadline_ticks: int = 0

    def __post_init__(self):
        resolved = ops.legacy_flags_policy(
            "EngineConfig", self.policy, self.use_event_kernels,
            self.spike_format)
        if self.policy is not None:
            self.policy = resolved


class Engine:
    def __init__(self, model, params, cfg: EngineConfig, rng_seed: int = 0,
                 faults: Optional[FaultPlan] = None):
        self.model = model
        self.params = params
        self.cfg = cfg
        # fault-injection script (None in production); kernel faults are
        # process-global and armed immediately
        self.faults = faults
        if faults is not None:
            faults.arm_kernel_faults()
        spiking = getattr(model.cfg, "attention_kind", "") == "qk_spiking"
        self.policy = getattr(model.cfg, "exec_policy", ops.REFERENCE)
        if spiking:
            eff = ops.merge_engine_policy(
                model.cfg.exec_policy, cfg.policy, cfg.use_event_kernels,
                cfg.spike_format)
            if eff != model.cfg.exec_policy:
                # run THIS engine's prefills/decodes under the engine's
                # policy without mutating the caller's model (fused
                # policies are inference-only; a shared model may still be
                # used for training under its own "reference" policy)
                self.model = type(model)(ops.with_policy(model.cfg, eff))
            self.policy = eff
        self.queue: deque[Request] = deque()
        self.prefill_fifo: deque[_PrefillJob] = deque()
        self.active: dict[int, Request] = {}
        self.finished: list[Request] = []
        self.requests: dict[int, Request] = {}
        self._rng = jax.random.PRNGKey(rng_seed)
        self._uid = itertools.count()
        # per-decode-tick spike telemetry (packed qk_spiking mode only)
        self._track_spikes = (spiking and self.policy.packed
                              and cfg.spike_stats_every > 0)
        self._spike_log: list[dict] = []
        self._tick = 0
        # elastic-FIFO telemetry: occupancy high-water marks
        self._queue_hwm = 0
        self._prefill_fifo_hwm = 0
        self._out_fifo_hwm = 0
        self._stall_ticks = 0
        self._prefill_chunks = 0
        # tick log, one record per step() call (see ``step``); a rolling
        # window, so stats() percentiles stay O(window) and memory bounded
        self._ticks: deque = deque(maxlen=4096)
        self._rec: dict = {}                # the record of the running step
        self._greedy = None                 # (pool logits, their greedy tokens)
        self._syncs = 0                     # device->host syncs ever made
        # self-healing state + counters
        self._tokens_emitted = 0
        self._cancelled = 0
        self._deadline_miss = 0
        self._quarantined = 0
        self._requeues = 0
        self._failed = 0
        self._guard_scans = 0
        self._guard_fn = None               # lazily-jitted integrity scan
        self._forced_stalls: dict[int, int] = {}   # slot -> stall-until tick

        # slot-pool cache; per-slot valid lengths tracked host-side
        self.cache = self.model.init_cache(cfg.max_slots, cfg.max_len)
        self.cache["len"] = jnp.zeros((), jnp.int32)  # engine manages length
        self.slot_len = np.zeros(cfg.max_slots, np.int64)
        self.free_slots = list(range(cfg.max_slots))

        if cfg.prefill_chunk > 0 and not hasattr(self.model, "prefill_chunk"):
            raise ValueError(
                f"{type(self.model).__name__} has no prefill_chunk: chunked "
                f"prefill serves the decoder-only LM zoo (set "
                f"EngineConfig.prefill_chunk=0 for blocking prefill)")
        # shared jitted steps: prefill returns all-position logits (prompts
        # are right-padded; the engine reads each prompt's true last
        # position) and decode is one pool-wide tick whose cache['len'] is
        # the per-slot [B] length vector, so every slot attends exactly its
        # own prefix
        self._prefill, self._decode, self._prefill_chunk = \
            _jitted_steps(self.model)

    # ------------------------------------------------------------ lifecycle
    def submit(self, prompt: np.ndarray, max_new: int = 32,
               temperature: float = 0.0, eos_id: Optional[int] = None,
               block: bool = True, deadline_ticks: Optional[int] = None,
               deadline_s: Optional[float] = None) -> int:
        """Enqueue a request. With ``max_queue`` set and the admission FIFO
        full, a blocking submit applies backpressure: it donates engine
        ticks (draining prefill chunks and decode work) until a queue slot
        frees; ``block=False`` raises ``QueueFull`` immediately instead.

        ``deadline_ticks`` (engine ticks from enqueue, deterministic) and
        ``deadline_s`` (wall seconds, for latency SLOs) bound the request's
        lifetime: a request still unfinished past either deadline is
        cancelled with status "deadline_miss" at the next tick, its slot
        reclaimed. ``deadline_ticks=None`` inherits
        ``EngineConfig.deadline_ticks`` (0 = no deadline)."""
        prompt = np.asarray(prompt, np.int32)
        if len(prompt) == 0:
            raise ValueError("empty prompt: there is no position to read "
                             "first-token logits from")
        if len(prompt) >= self.cfg.max_len:
            raise ValueError(f"prompt length {len(prompt)} >= max_len "
                             f"{self.cfg.max_len}: the slot pool cannot "
                             f"hold it (raise EngineConfig.max_len)")
        if self.cfg.max_queue and len(self.queue) >= self.cfg.max_queue:
            if not block:
                raise QueueFull(f"admission FIFO at bound "
                                f"{self.cfg.max_queue}")
            for _ in range(self.cfg.submit_block_ticks):
                self.step()
                if len(self.queue) < self.cfg.max_queue:
                    break
            else:
                raise QueueFull("backpressure tick budget exhausted")
        req = Request(uid=next(self._uid), prompt=prompt,
                      max_new=max_new, temperature=temperature, eos_id=eos_id)
        req.enqueued_t = time.time()
        req.enqueued_tick = self._tick
        if deadline_ticks is None:
            deadline_ticks = self.cfg.deadline_ticks or None
        if deadline_ticks is not None:
            req.deadline_tick = self._tick + int(deadline_ticks)
        if deadline_s is not None:
            req.deadline_t = req.enqueued_t + float(deadline_s)
        self.queue.append(req)
        self.requests[req.uid] = req
        self._queue_hwm = max(self._queue_hwm, len(self.queue))
        return req.uid

    def cancel(self, uid: int, status: str = STATUS_CANCELLED) -> bool:
        """Cancel a request wherever it is in the pipeline: drop it from
        the admission queue, abandon its in-flight prefill, or evict its
        decode slot (the slot frees this tick — the pool decode simply
        stops computing it; no rollback needed since the row is dead).
        Already-emitted tokens stay drainable via ``pop_output``. Returns
        False for unknown/terminal uids."""
        req = self.requests.get(uid)
        if req is None or req.done:
            return False
        if req in self.queue:
            self.queue.remove(req)
        for job in list(self.prefill_fifo):
            if job.req is req:
                self.prefill_fifo.remove(job)
                self._release_slot(job.slot)
        if req.slot >= 0 and self.active.get(req.slot) is req:
            del self.active[req.slot]
            self._release_slot(req.slot, scrub=self.cfg.integrity_every > 0)
        self._finish(req, status)
        if status == STATUS_CANCELLED:
            self._cancelled += 1
        return True

    def _finish(self, req: Request, status: str) -> None:
        req.done = True
        req.status = status
        req.slot = -1
        req.finished_t = time.time()
        self.finished.append(req)

    def _release_slot(self, slot: int, scrub: bool = False) -> None:
        self.slot_len[slot] = 0
        self.free_slots.append(slot)
        if scrub:
            self._scrub_slot(slot)

    def _deadline_sweep(self) -> None:
        """Cancel every in-flight request whose tick or wall deadline has
        passed (status "deadline_miss")."""
        live = list(self.queue) + [j.req for j in self.prefill_fifo] \
            + list(self.active.values())
        now = None
        for req in live:
            over = (req.deadline_tick is not None
                    and self._tick >= req.deadline_tick)
            if not over and req.deadline_t is not None:
                now = time.time() if now is None else now
                over = now >= req.deadline_t
            if over:
                self.cancel(req.uid, status=STATUS_DEADLINE)
                self._deadline_miss += 1

    def pop_output(self, uid: int) -> list[int]:
        """Drain a request's output FIFO (the consumer side of the per-slot
        elastic FIFO). Draining un-stalls a slot paused by a full FIFO.
        A finished, fully-drained request is retired from the uid map (so a
        long-running server does not accumulate request state); draining an
        unknown/retired uid returns []."""
        req = self.requests.get(uid)
        if req is None:
            return []
        out, req.fifo = list(req.fifo), deque()
        if req.done:
            del self.requests[uid]
        return out

    def load(self) -> int:
        """Requests in flight (queued + prefilling + decoding) — the
        dispatch metric for the multi-replica router."""
        return len(self.queue) + len(self.prefill_fifo) + len(self.active)

    # ------------------------------------------------------------- admission
    def _bucket_len(self, s: int) -> int:
        if self.model.cfg.family in ("ssm", "hybrid"):
            # SSM recurrences integrate pad positions into the state —
            # prefill at TRUE length (attention pads are causal-inert,
            # SSM pads are not)
            return s
        return min(self.cfg.max_len,
                   -(-s // self.cfg.prefill_pad) * self.cfg.prefill_pad)

    def _admit(self) -> None:
        chunked = self.cfg.prefill_chunk > 0
        while self.queue and self.free_slots:
            req = self.queue.popleft()
            if not req.admitted_t:          # a replay keeps its first
                req.admitted_t = time.time()
            slot = self.free_slots.pop()
            req.slot = slot
            req.status = STATUS_PREFILL
            if chunked:
                self._admit_chunked(req, slot)
            else:
                self._admit_blocking(req, slot)

    def _admit_blocking(self, req: Request, slot: int) -> None:
        s = len(req.prompt)
        pad_len = self._bucket_len(s)
        toks = np.zeros((1, pad_len), np.int32)
        toks[0, :s] = req.prompt        # right-pad (causal: pads inert)
        with self._phase("prefill", uid=req.uid, chunk=0):
            logits, cache = self._prefill(self.params, jnp.asarray(toks))
            self._write_slot(slot, cache)
            self._activate(req, slot, logits[0, s - 1])

    def _admit_chunked(self, req: Request, slot: int) -> None:
        s = len(req.prompt)
        bucket = self._bucket_len(s)
        cache = self.model.init_cache(1, bucket)
        cache["len"] = jnp.zeros((), jnp.int32)
        if self.model.cfg.kv_dtype:
            # chunk attention must read back the prefix it wrote: keep the
            # per-request cache at COMPUTE precision and quantize (f8 etc.)
            # once at _write_slot — exactly where the blocking path does —
            # or chunked would attend quantized keys blocking never saw
            dt = self.model.cfg.dtype
            cache["layers"] = jax.tree_util.tree_map(
                lambda a: a.astype(dt) if a.dtype == jnp.float8_e4m3fn
                else a, cache["layers"])
        self.prefill_fifo.append(_PrefillJob(req, slot, cache, bucket))
        self._prefill_fifo_hwm = max(self._prefill_fifo_hwm,
                                     len(self.prefill_fifo))

    def _chunk_size(self) -> int:
        align = self.model.cfg.prefill_chunk_align
        return -(-self.cfg.prefill_chunk // align) * align

    def _prefill_step(self, job: _PrefillJob) -> bool:
        """Run ONE chunk of one request's prefill. Returns True when the
        job completed (its slot cache is written and the request is live)."""
        req, s = job.req, len(job.req.prompt)
        chunk = min(self._chunk_size(), job.bucket - job.done)
        toks = np.zeros((1, chunk), np.int32)
        valid = max(0, min(chunk, s - job.done))
        toks[0, :valid] = req.prompt[job.done:job.done + valid]
        logits, job.cache = self._prefill_chunk(self.params,
                                                jnp.asarray(toks), job.cache)
        self._prefill_chunks += 1
        if job.done <= s - 1 < job.done + chunk:
            job.last_logits = logits[0, s - 1 - job.done]
        job.done += chunk
        if job.done < job.bucket:
            return False
        self._write_slot(job.slot, job.cache)
        self._activate(req, job.slot, job.last_logits)
        return True

    def _emit(self, req: Request, tok: int) -> None:
        """Record one sampled token. The FIFO only receives tokens PAST
        ``req.pushed`` — a quarantine replay regenerates the stream from
        scratch (greedy decode is deterministic) without re-delivering."""
        req.out.append(tok)
        self._tokens_emitted += 1
        if len(req.out) > req.pushed:
            req.fifo.append(tok)
            req.pushed = len(req.out)
            self._out_fifo_hwm = max(self._out_fifo_hwm, len(req.fifo))

    def _activate(self, req: Request, slot: int, last_logits: Array) -> None:
        """Prefill finished: slot goes live with the first sampled token."""
        self.slot_len[slot] = len(req.prompt)  # only the REAL prompt is valid
        tok = self._sample(last_logits, req)
        self._emit(req, int(tok))
        if req.first_token_tick < 0:    # a replay keeps the original TTFT
            req.first_token_t = time.time()
            req.first_token_tick = self._tick
        req.status = STATUS_DECODE
        self.active[slot] = req

    # ---------------------------------------------------------- cache moves
    def _write_slot(self, slot: int, prefill_cache: dict) -> None:
        """Copy one request's prefill cache into its slot row."""

        def write(path, pool, new):
            ps = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                          for k in path)
            nd = pool.ndim
            idx = [slice(None)] * nd
            if "ssm" in ps:                 # [.., slots, H, P, N]
                idx[nd - 4] = slice(slot, slot + 1)
            elif "conv" in ps:              # [.., slots, K-1, C]
                idx[nd - 3] = slice(slot, slot + 1)
            else:                           # KV [.., slots, max_len, H, D]
                if new.shape[nd - 3] == 0:  # qk_spiking: stateless
                    return pool
                idx[nd - 4] = slice(slot, slot + 1)
                idx[nd - 3] = slice(0, new.shape[nd - 3])
            return pool.at[tuple(idx)].set(new.astype(pool.dtype))

        self.cache["layers"] = jax.tree_util.tree_map_with_path(
            write, self.cache["layers"], prefill_cache["layers"])

    def _restore_slot(self, slot: int, prev_layers: Any) -> None:
        """Copy one slot's rows back from a pre-decode cache snapshot —
        makes a stalled slot's tick side-effect-free (its SSM/spike state
        must not advance while the consumer is not draining)."""

        def restore(path, pool, prev):
            ps = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                          for k in path)
            nd = pool.ndim
            idx = [slice(None)] * nd
            idx[nd - 3 if "conv" in ps else nd - 4] = slice(slot, slot + 1)
            idx = tuple(idx)
            return pool.at[idx].set(prev[idx])

        self.cache["layers"] = jax.tree_util.tree_map_with_path(
            restore, self.cache["layers"], prev_layers)

    def _scrub_slot(self, slot: int) -> None:
        """Zero one slot's rows in every cache pool — quarantine hygiene:
        a poisoned row must not survive into the slot's next occupant
        (prefill only overwrites the prompt's own positions)."""

        def scrub(path, pool):
            ps = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                          for k in path)
            nd = pool.ndim
            idx = [slice(None)] * nd
            idx[nd - 3 if "conv" in ps else nd - 4] = slice(slot, slot + 1)
            idx = tuple(idx)
            return pool.at[idx].set(jnp.zeros_like(pool[idx]))

        self.cache["layers"] = jax.tree_util.tree_map_with_path(
            scrub, self.cache["layers"])

    # ------------------------------------------------------ fault injection
    def _resolve_fault_slot(self, slot: int) -> Optional[int]:
        if slot >= 0:
            return slot if slot in self.active else None
        return min(self.active) if self.active else None

    def _inject_faults(self, logits: Array) -> Array:
        """Apply this tick's due state/logit faults (post-decode, pre-guard
        — the guard must see the corruption the same tick it lands)."""
        for ev in self.faults.due(
                ("nan_logits", "nan_state", "corrupt_word"), self._tick):
            slot = self._resolve_fault_slot(ev.slot)
            if slot is None:            # no live slot yet: fire next tick
                self.faults.defer(ev)
                continue
            if ev.kind == "corrupt_word" and self._corrupt_words(slot):
                continue
            if ev.kind == "nan_state" and self._corrupt_state(slot, ev.value):
                continue
            # nan_logits — and the fallback when a family has no float or
            # packed per-slot state to corrupt (e.g. qk_spiking is
            # stateless under a dense policy)
            logits = logits.at[slot].set(
                jnp.asarray(ev.value, logits.dtype))
        return logits

    def _corrupt_words(self, slot: int) -> bool:
        """Flip one packed spike-state word of a slot to all-ones (pad
        lanes included — guaranteed to violate the pad-lane invariant).
        False if the cache holds no packed word pool."""
        leaves, treedef = jax.tree_util.tree_flatten(self.cache["layers"])
        for i, leaf in enumerate(leaves):
            if leaf.dtype == jnp.int32 and leaf.ndim == 5 and leaf.size:
                idx = [0] * leaf.ndim
                idx[leaf.ndim - 4] = slot
                idx[-1] = leaf.shape[-1] - 1
                leaves[i] = leaf.at[tuple(idx)].set(jnp.int32(-1))
                self.cache["layers"] = jax.tree_util.tree_unflatten(
                    treedef, leaves)
                return True
        return False

    def _corrupt_state(self, slot: int, value: float) -> bool:
        """Poison one element of a slot's float state row (membrane / KV /
        SSM). False if the model keeps no float per-slot state."""
        flat, treedef = jax.tree_util.tree_flatten_with_path(
            self.cache["layers"])
        leaves = [leaf for _, leaf in flat]
        for i, (path, leaf) in enumerate(flat):
            if not (jnp.issubdtype(leaf.dtype, jnp.floating) and leaf.size):
                continue
            ps = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                          for k in path)
            ax = leaf.ndim - (3 if "conv" in ps else 4)
            if ax < 0 or leaf.shape[ax] != self.cfg.max_slots:
                continue
            idx = [0] * leaf.ndim
            idx[ax] = slot
            leaves[i] = leaf.at[tuple(idx)].set(
                jnp.asarray(value, leaf.dtype))
            self.cache["layers"] = jax.tree_util.tree_unflatten(
                treedef, leaves)
            return True
        return False

    # ------------------------------------------------------ integrity guard
    def _integrity_verdict(self, logits: Array) -> tuple:
        """One jitted scan over (slot-pool cache, decode logits): per-slot
        ``(numeric_bad, packed_bad)`` bool vectors. Numeric = any non-finite
        in the slot's logits or float state rows; packed = any set bit in a
        packed word pool's PAD lanes (columns >= n_heads*head_dim — always
        zero for well-formed packed spike state)."""
        if self._guard_fn is None:
            nslots = self.cfg.max_slots
            try:
                d_logical = (self.model.cfg.n_heads *
                             self.model.cfg.resolved_head_dim)
            except AttributeError:
                d_logical = 0

            def scan(layers, lg):
                bad_num = ~jnp.isfinite(lg.astype(jnp.float32)) \
                    .reshape(nslots, -1).all(axis=1)
                bad_pack = jnp.zeros((nslots,), bool)
                flat = jax.tree_util.tree_flatten_with_path(layers)[0]
                for path, leaf in flat:
                    if not leaf.size:
                        continue
                    ps = "/".join(str(getattr(k, "key",
                                              getattr(k, "idx", k)))
                                  for k in path)
                    ax = leaf.ndim - (3 if "conv" in ps else 4)
                    if ax < 0 or leaf.shape[ax] != nslots:
                        continue
                    if jnp.issubdtype(leaf.dtype, jnp.floating):
                        fin = jnp.isfinite(leaf.astype(jnp.float32))
                        bad_num |= ~jnp.moveaxis(fin, ax, 0) \
                            .reshape(nslots, -1).all(axis=1)
                    elif leaf.dtype == jnp.int32 and leaf.ndim == 5 \
                            and d_logical > 0:
                        mask = jnp.asarray(pad_lane_mask(
                            d_logical, leaf.shape[-1]))
                        viol = (leaf & mask) != 0
                        bad_pack |= jnp.moveaxis(viol, ax, 0) \
                            .reshape(nslots, -1).any(axis=1)
                return bad_num, bad_pack

            self._guard_fn = jax.jit(scan)
        return self._guard_fn(self.cache["layers"], logits)

    def _quarantine(self, slot: int, reason: str) -> None:
        """Evict a slot whose state failed the integrity guard: scrub the
        poisoned row, free the slot, and requeue the request from scratch
        (front of the queue; greedy replay regenerates the identical
        stream, ``pushed`` suppresses re-delivery). Past the retry budget
        the request fails loudly instead."""
        req = self.active.pop(slot)
        self._release_slot(slot, scrub=True)
        self._quarantined += 1
        req.retries += 1
        if req.retries > self.cfg.quarantine_retries:
            self._finish(req, STATUS_FAILED)
            self._failed += 1
            return
        req.out = []
        req.slot = -1
        req.status = STATUS_QUEUED
        self.queue.appendleft(req)
        self._requeues += 1

    def _sample(self, logits: Array, req: Request) -> int:
        """Every served token comes from here. ``logits`` is one row
        ``[vocab]`` (a prefill's last position) or a pool decode's
        ``[max_slots, vocab]``, whose row ``req.slot`` is the request's. A
        greedy request reads a pool's row from one batched argmax, fetched
        once per logits array (logits replaced by fault injection are
        reduced afresh); a temperature request draws from the engine's RNG
        stream one slot at a time."""
        pooled = logits.ndim > 1
        if req.temperature <= 0.0:
            if not pooled:
                return int(self._sync(jnp.argmax(logits)))
            if self._greedy is None or self._greedy[0] is not logits:
                self._greedy = (logits, self._sync(_greedy_rows(logits)))
            self._rec["batched"] += 1
            return int(self._greedy[1][req.slot])
        row = logits[req.slot] if pooled else logits
        self._rng, k = jax.random.split(self._rng)
        return int(self._sync(
            jax.random.categorical(k, row / req.temperature)))

    # ------------------------------------------------------ tick telemetry
    def _sync(self, x: Array, fetch: bool = True) -> Any:
        """Every point where the engine waits for the device goes through
        here, so the tick log counts each one: ``fetch`` copies ``x`` to
        the host as a NumPy array, else it only waits until ``x`` is
        computed and returns it."""
        self._syncs += 1
        return np.asarray(x) if fetch else jax.block_until_ready(x)

    @contextlib.contextmanager
    def _phase(self, name: str, annotation=jax.profiler.TraceAnnotation,
               **meta):
        """Run one phase of the current tick inside a ``serve.<name>``
        profiler span (``meta`` rides on the span) and add its host
        nanoseconds to the tick's record. While no trace is active a
        phase costs a few microseconds of host time."""
        t0 = time.perf_counter_ns()
        try:
            with annotation(SPAN_PREFIX + name, **meta):
                yield
        finally:
            ph = self._rec["phases"]
            ph[name] = ph.get(name, 0) + time.perf_counter_ns() - t0

    @property
    def last_tick(self) -> Optional[dict]:
        """The newest tick record (see ``step``); None before the first."""
        return self._ticks[-1] if self._ticks else None

    # ------------------------------------------------------------------ tick
    def _stalled_slots(self) -> set:
        stalled = set()
        if self.faults is not None:
            for ev in self.faults.due("stall_consumer", self._tick):
                slot = self._resolve_fault_slot(ev.slot)
                if slot is None:
                    self.faults.defer(ev)
                    continue
                self._forced_stalls[slot] = self._tick + max(ev.ticks, 1)
        if self._forced_stalls:
            self._forced_stalls = {
                s: until for s, until in self._forced_stalls.items()
                if self._tick < until and s in self.active}
            stalled |= set(self._forced_stalls)
        if self.cfg.out_fifo_depth:
            stalled |= {slot for slot, req in self.active.items()
                        if len(req.fifo) >= self.cfg.out_fifo_depth}
        return stalled

    def step(self) -> int:
        """One engine tick: admit, drain up to ``prefill_chunks_per_tick``
        chunks from the prefill FIFO, then one pool decode for all live,
        un-stalled slots. Returns number of live sequences.

        Every call appends one record to the tick log (``last_tick``), also
        a call that returns early or raises: ``tick`` (the engine tick it
        ran at), ``live`` (slots that sampled a token from the decode),
        ``batched`` (those of them whose token came from the decode's one
        batched greedy fetch; ``live - batched`` sampled one slot at a
        time), ``decoded``, ``chunks`` (prefill chunks run), ``syncs``
        (device->host syncs: the decode's wait, the batched greedy fetch,
        one per token sampled alone (a temperature slot, a finished
        prefill's first token), one per packed pool that spike telemetry
        fetches, one per guard verdict) and
        ``phases`` (host nanoseconds of each phase that ran). Each phase
        runs in a profiler span named ``serve.<phase>``, nested in
        ``serve.step`` (a step annotation whose ``step_num`` is ``tick``):

        * ``admit``: the deadline sweep and admission (a blocking prefill
          nests its ``prefill`` span here);
        * ``prefill``: one chunk (upload, dispatch, last-logit slice; on
          the final chunk the slot write and the first token's sample),
          with the request's ``uid`` and ``chunk`` index as metadata;
        * ``decode``: token and length uploads, the pool decode's dispatch
          and the wait for its logits;
        * ``guard``: fault injection and the integrity scan;
        * ``spike_stats``: packed spike telemetry;
        * ``sample``: stall restore, quarantine, the batched greedy fetch,
          then per slot the sample, emit, finish and slot release."""
        rec = self._rec = {"tick": self._tick, "live": 0, "batched": 0,
                           "decoded": False, "chunks": 0, "syncs": 0,
                           "phases": {}}
        syncs0, chunks0 = self._syncs, self._prefill_chunks
        try:
            with self._phase("step", jax.profiler.StepTraceAnnotation,
                             step_num=rec["tick"]):
                return self._step(rec)
        finally:
            rec["syncs"] = self._syncs - syncs0
            rec["chunks"] = self._prefill_chunks - chunks0
            self._ticks.append(rec)

    def _step(self, rec: dict) -> int:
        if self.faults is not None and self.faults.die_due(self._tick):
            raise ReplicaFailure(
                f"injected replica death at tick {self._tick}")
        with self._phase("admit"):
            self._deadline_sweep()
            self._admit()
        if self.cfg.prefill_chunk > 0:
            budget = max(1, self.cfg.prefill_chunks_per_tick)
            while budget > 0 and self.prefill_fifo:
                job = self.prefill_fifo[0]
                with self._phase("prefill", uid=job.req.uid,
                                 chunk=job.done // self._chunk_size()):
                    finished = self._prefill_step(job)
                if finished:
                    self.prefill_fifo.popleft()
                budget -= 1
        if not self.active:
            return 0
        stalled = self._stalled_slots()
        self._tick += 1
        if stalled and len(stalled) == len(self.active):
            self._stall_ticks += 1
            return len(self.active)     # every consumer is backed up
        with self._phase("decode"):
            toks = np.zeros((self.cfg.max_slots, 1), np.int32)
            for slot, req in self.active.items():
                toks[slot, 0] = req.out[-1]
            # per-slot length vector: every slot attends exactly its own
            # prefix
            self.cache["len"] = jnp.asarray(self.slot_len, jnp.int32)
            prev_layers = self.cache["layers"] if stalled else None
            logits, self.cache = self._decode(self.params, jnp.asarray(toks),
                                              self.cache)
            logits = self._sync(logits, fetch=False)
        rec["decoded"] = True
        scan = (self.cfg.integrity_every > 0
                and self._tick % self.cfg.integrity_every == 0)
        bad, reasons = set(), {}
        if self.faults is not None or scan:
            with self._phase("guard"):
                if self.faults is not None:
                    # injected corruption lands AFTER the decode, BEFORE
                    # the guard — the guard must catch it before a token
                    # is sampled from it
                    logits = self._inject_faults(logits)
                if scan:
                    self._guard_scans += 1
                    bad_num, bad_pack = map(
                        self._sync, self._integrity_verdict(logits))
                    bad = {s for s in self.active
                           if bad_num[s] or bad_pack[s]}
                    reasons = {s: ("packed_invariant" if bad_pack[s]
                                   else "non_finite") for s in bad}
        if self._track_spikes and self._tick % self.cfg.spike_stats_every == 0:
            with self._phase("spike_stats"):
                self._record_spike_step(sorted(self.active.keys()))
        with self._phase("sample"):
            if stalled:
                self._stall_ticks += 1
                for slot in stalled:
                    # exact stall (greedy): state row rolls back, same
                    # token re-fed next tick recomputes the identical step
                    # once the FIFO drains; temperature sampling is only
                    # reproducible up to the shared RNG stream's
                    # consumption order
                    self._restore_slot(slot, prev_layers)
            for slot in sorted(bad):
                # quarantine BEFORE sampling: no token leaves a poisoned
                # slot
                self._quarantine(slot, reasons[slot])
            done_slots = []
            for slot, req in list(self.active.items()):
                if slot in stalled:
                    continue
                tok = self._sample(logits, req)
                rec["live"] += 1
                self._emit(req, tok)
                self.slot_len[slot] += 1
                hit_eos = req.eos_id is not None and tok == req.eos_id
                if hit_eos or len(req.out) >= req.max_new \
                        or self.slot_len[slot] >= self.cfg.max_len - 1:
                    self._finish(req, STATUS_DONE)
                    done_slots.append(slot)
            self._greedy = None
            for slot in done_slots:
                del self.active[slot]
                self.slot_len[slot] = 0
                self.free_slots.append(slot)
        return len(self.active)

    def pending(self) -> bool:
        """True while any pipeline stage still holds work (queued,
        prefilling, or decoding) — THE drain predicate; drive loops should
        use this instead of peeking at individual FIFOs."""
        return bool(self.active or self.queue or self.prefill_fifo)

    def _progress_signature(self) -> tuple:
        """Changes iff the pipeline made observable progress this tick."""
        return (self._tokens_emitted, self._prefill_chunks,
                len(self.finished), len(self.queue),
                len(self.prefill_fifo))

    def _stall_report(self) -> dict:
        return {
            "tick": self._tick,
            "queued": len(self.queue),
            "prefilling": [j.req.uid for j in self.prefill_fifo],
            "stuck_slots": {
                slot: {"uid": req.uid, "out_fifo": len(req.fifo),
                       "tokens": len(req.out), "status": req.status}
                for slot, req in sorted(self.active.items())},
            "free_slots": len(self.free_slots),
        }

    def run_until_drained(self, max_ticks: int = 10_000,
                          stall_grace: int = 200) -> list[Request]:
        """Tick until every request reaches a terminal state. Raises
        ``StalledEngine`` when work is pending but NO stage has progressed
        for ``stall_grace`` consecutive ticks (livelock — e.g. every live
        slot stalled on an output FIFO nobody drains), or when
        ``max_ticks`` runs out with work still pending; the silent-return
        of either case would hand the caller a partial result."""
        last, idle = None, 0
        for _ in range(max_ticks):
            self.step()
            if not self.pending():
                return self.finished
            sig = self._progress_signature()
            if sig == last:
                idle += 1
                if idle >= stall_grace:
                    rep = self._stall_report()
                    raise StalledEngine(
                        f"no progress for {idle} ticks with work pending: "
                        f"stuck slots {sorted(rep['stuck_slots'])}, "
                        f"{rep['queued']} queued, "
                        f"{len(rep['prefilling'])} prefilling "
                        f"(are the output FIFOs being drained?)", rep)
            else:
                last, idle = sig, 0
        rep = self._stall_report()
        raise StalledEngine(
            f"max_ticks={max_ticks} exhausted with work still pending: "
            f"stuck slots {sorted(rep['stuck_slots'])}, "
            f"{rep['queued']} queued", rep)

    def _record_spike_step(self, live_slots: list) -> None:
        """Measure one decode tick's spike activity straight off the PACKED
        per-slot spike state in the cache pool: popcount of the int32 words
        = spike count (the pad lanes are zero), words bytes = what actually
        crossed HBM for spike state this tick."""
        if not live_slots:
            return
        n_units = (self.model.cfg.n_heads *
                   self.model.cfg.resolved_head_dim)
        # one fetch per packed word pool, through the counted sync
        pools = [self._sync(leaf)[:, live_slots]
                 for leaf in jax.tree_util.tree_leaves(self.cache["layers"])
                 if leaf.dtype == jnp.int32 and leaf.ndim == 5]
        spikes = packed_b = units = 0
        nz_words = blk_groups = blk_active = occ_words = 0
        for sel in pools:
            spikes += int(np.unpackbits(
                np.ascontiguousarray(sel).view(np.uint8)).sum())
            packed_b += sel.size * 4
            units += sel.shape[0] * len(live_slots) * n_units
            nz = (sel != 0).reshape(-1, sel.shape[-1])
            # group word columns into 128-column (4-word) metadata blocks:
            # the k-axis granularity of the gated kernels' vld/occ maps
            wpb = min(4, nz.shape[-1])
            g = nz.shape[-1] // wpb
            grp = nz[:, :g * wpb].reshape(-1, g, wpb)
            any_blk = grp.any(axis=-1)
            blk_groups += any_blk.size
            blk_active += int(any_blk.sum())
            occ_words += int(grp.sum())       # nonzero words (all in active)
            nz_words += wpb * int(any_blk.sum())  # words inside active blocks
        if units:
            entry = {
                "live": len(live_slots),
                "spike_rate": spikes / units,
                "packed_bytes": packed_b,
                "dense_bytes": units}         # the int8 maps it replaces
            if blk_groups:
                # feed the measured (block-active, word-occupancy) fractions
                # to the roofline autotuner: the "auto" policy's sparsity
                # hint for traced operands (one EWMA profile per engine)
                from ..ops.autotune import get_tuner

                active = blk_active / blk_groups
                occ = occ_words / max(nz_words, 1)
                entry["block_active_frac"] = active
                entry["word_occ_frac"] = occ
                get_tuner().observe(active, occ)
            self._spike_log.append(entry)

    def stats(self) -> dict:
        if not self.finished:
            return {}
        # timing/token aggregates cover the SUCCESSFUL completions only —
        # a cancelled request may never have produced a first token
        done = [r for r in self.finished if r.status == STATUS_DONE]
        ttft = [r.first_token_t - r.enqueued_t for r in done]
        lat = [r.finished_t - r.enqueued_t for r in done]
        toks = sum(len(r.out) for r in done)
        span = (max(r.finished_t for r in done)
                - min(r.enqueued_t for r in done)) if done else 0.0
        out = {"n": len(done),
               "n_terminal": len(self.finished),
               "ttft_mean_s": float(np.mean(ttft)) if ttft else 0.0,
               "latency_mean_s": float(np.mean(lat)) if lat else 0.0,
               "tokens": toks,
               "tok_per_s": toks / max(span, 1e-9),
               "queue_depth": len(self.queue),
               "active": len(self.active),
               "policy": self.policy.name,
               "spike_format": self.policy.format,
               # self-healing counters (tentpole: these are the fault
               # ledger callers alarm on)
               "ticks": self._tick,
               "cancelled": self._cancelled,
               "deadline_miss": self._deadline_miss,
               "quarantined": self._quarantined,
               "requeues": self._requeues,
               "failed": self._failed,
               "guard_scans": self._guard_scans,
               # elastic-FIFO telemetry: the software analogue of the
               # paper's FIFO-depth elasticity measurements
               "prefill_mode": ("chunked" if self.cfg.prefill_chunk > 0
                                else "blocking"),
               "prefill_chunks": self._prefill_chunks,
               "queue_hwm": self._queue_hwm,
               "prefill_fifo_hwm": self._prefill_fifo_hwm,
               "out_fifo_hwm": self._out_fifo_hwm,
               "stall_ticks": self._stall_ticks}
        # tick log: the decode phase keeps the decode_tick_* keys; every
        # phase's p50/p99, and the syncs of the ticks that decoded
        decode_s = [r["phases"]["decode"] / 1e9 for r in self._ticks
                    if "decode" in r["phases"]]
        if decode_s:
            decoded = [r for r in self._ticks if r["decoded"]]
            live = sum(r["live"] for r in decoded)
            out.update({
                "decode_ticks": len(decode_s),
                "decode_tick_p50_s": float(np.percentile(decode_s, 50)),
                "decode_tick_p99_s": float(np.percentile(decode_s, 99)),
                "decode_tick_max_s": float(max(decode_s)),
                "host_syncs_per_tick_mean": float(np.mean(
                    [r["syncs"] for r in decoded])),
                # share of decoded tokens taken from the batched greedy fetch
                "batched_sample_share": (sum(r["batched"] for r in decoded)
                                         / live if live else 0.0)})
        by_phase: dict = {}
        for r in self._ticks:
            for name, ns in r["phases"].items():
                by_phase.setdefault(name, []).append(ns / 1e6)
        if by_phase:
            out["phase_ms"] = {
                name: {"p50": float(np.percentile(ms, 50)),
                       "p99": float(np.percentile(ms, 99))}
                for name, ms in by_phase.items()}
        if self._spike_log:
            rate = float(np.mean([e["spike_rate"] for e in self._spike_log]))
            pb = float(np.mean([e["packed_bytes"] for e in self._spike_log]))
            db = float(np.mean([e["dense_bytes"] for e in self._spike_log]))
            out.update({
                "decode_ticks_measured": len(self._spike_log),
                "spike_rate_mean": rate,
                "spike_sparsity_mean": 1.0 - rate,
                "packed_spike_bytes_per_tick_mean": pb,
                "dense_spike_bytes_per_tick_mean": db,
                "spike_state_hbm_reduction": db / max(pb, 1e-9)})
            af = [e["block_active_frac"] for e in self._spike_log
                  if "block_active_frac" in e]
            if af:
                out["block_active_frac_mean"] = float(np.mean(af))
        # the autotuner's live state: the observed-sparsity EWMA feeding
        # "auto" plans for traced operands, and every plan resolved so far
        from ..ops.autotune import get_tuner
        from ..ops import fallback

        out["autotune"] = get_tuner().snapshot()
        # fused->reference demotions (process-global; see ops.fallback)
        out["kernel_demotions"] = fallback.demotions()
        if self.faults is not None:
            out["fault_plan"] = self.faults.summary()
        return out
