"""Data-parallel multi-replica serving: a least-loaded router over N engine
replicas whose slot pools shard across the local devices.

Each replica is a full ``Engine`` (own slot-pool cache, own elastic FIFOs)
placed on one device via the ``models.sharding`` replica-mesh helpers —
weights replicate, slot pools shard: the serving-side data-parallel axis.
Dispatch is least-loaded (queued + prefilling + active), lowest replica
index on ties, so a given arrival trace routes deterministically and
per-request outputs stay bit-identical to a single engine under greedy
decode (each replica's pool math is slot-count-independent).

Replica health + failover: a replica whose ``step()`` raises — an injected
``ReplicaFailure``, a real kernel crash — or whose tick wall latency trips
``health_latency_s`` is marked DEAD: ``submit`` stops routing to it, and
every one of its non-terminal requests (queued, prefilling, AND mid-decode)
is requeued onto the healthy replicas from the original prompt. Greedy
decode makes the replay bit-identical, and the per-uid delivered-token
ledger drops the replayed prefix the consumer already saw — at-most-once
delivery end to end (tokens sitting undelivered in the dead replica's
FIFOs are discarded and regenerated). Requests that FINISHED on a dead
replica stay readable. Only when the LAST replica dies does the failure
propagate to the caller.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional

import numpy as np

from ..models.sharding import replica_meshes, replicate_params
from .engine import (Engine, EngineConfig, QueueFull, Request, StalledEngine,
                     TERMINAL)
from .faults import FaultPlan, ReplicaFailure


class AllReplicasDead(RuntimeError):
    """Every replica has failed: nothing can serve the pending work."""


class ReplicaRouter:
    def __init__(self, model, params, cfg: EngineConfig, n_replicas: int = 2,
                 devices: Optional[list] = None, rng_seed: int = 0,
                 faults: Optional[FaultPlan] = None,
                 health_latency_s: Optional[float] = None):
        assert n_replicas >= 1
        meshes = replica_meshes(n_replicas, devices)
        if faults is not None:
            faults.arm_kernel_faults()
        # per-replica rng offset: temperature sampling must not replay the
        # same stream on every replica (greedy decode is seed-independent)
        self.engines = [
            Engine(model, replicate_params(params, mesh), cfg,
                   rng_seed=rng_seed + i,
                   faults=faults.view(i) if faults is not None else None)
            for i, mesh in enumerate(meshes)]
        for e, mesh in zip(self.engines, meshes):
            # the slot pool lives on its replica's device, beside the weights
            e.cache = replicate_params(e.cache, mesh)
        self.meshes = meshes
        self.faults = faults
        self.health_latency_s = health_latency_s
        self.alive = [True] * n_replicas
        self._dispatch = np.zeros(n_replicas, np.int64)
        self._by_uid: dict[int, tuple[int, int]] = {}   # uid -> (replica, local uid)
        self._uid = 0
        # failover bookkeeping
        self._meta: dict[int, dict] = {}       # uid -> original submit args
        self._delivered: dict[int, int] = {}   # uid -> tokens popped by caller
        self._skip: dict[int, int] = {}        # uid -> replayed prefix to drop
        self._orphans: list[int] = []          # uids awaiting re-dispatch
        self._failures: list[dict] = []
        self._requeued = 0

    # ------------------------------------------------------------- dispatch
    def _order(self) -> list[int]:
        """Alive replicas, least-loaded first (stable on ties)."""
        alive = [r for r in range(len(self.engines)) if self.alive[r]]
        return sorted(alive, key=lambda r: (self.engines[r].load(), r))

    def submit(self, prompt, max_new: int = 32, temperature: float = 0.0,
               eos_id=None, block: bool = True,
               deadline_ticks: Optional[int] = None,
               deadline_s: Optional[float] = None) -> int:
        """Least-loaded dispatch over the ALIVE replicas with router-level
        backpressure: if the chosen replica's admission FIFO is full, try
        the others before falling back to a blocking submit on the
        least-loaded one."""
        meta = dict(max_new=max_new, temperature=temperature, eos_id=eos_id,
                    deadline_ticks=deadline_ticks, deadline_s=deadline_s)
        order = self._order()
        if not order:
            raise AllReplicasDead("submit with no healthy replica")
        attempts = [(r, False) for r in order]
        if block:
            # every FIFO full: block on the LEAST-loaded replica — it is
            # the one whose backpressure ticks free a queue slot soonest
            attempts.append((order[0], True))
        for r, blocking in attempts:
            if not self.alive[r]:       # may have died mid-attempt list
                continue
            try:
                local = self.engines[r].submit(
                    prompt, block=blocking, **meta)
            except QueueFull:
                continue
            except ReplicaFailure as exc:
                # a blocking submit donates engine ticks, which can trip
                # an injected death — fail over and keep trying
                self._fail_replica(r, f"submit backpressure: {exc}")
                continue
            uid = self._uid
            self._uid += 1
            self._by_uid[uid] = (r, local)
            self._meta[uid] = dict(meta, prompt=np.asarray(prompt, np.int32))
            self._dispatch[r] += 1
            return uid
        raise QueueFull("every replica's admission FIFO is full")

    # ------------------------------------------------------------- failover
    def _fail_replica(self, r: int, reason: str) -> None:
        """Mark replica ``r`` dead and orphan its non-terminal requests for
        re-dispatch. The dead engine is never stepped again, so requests
        that already FINISHED there stay readable from its request map."""
        self.alive[r] = False
        dead = self.engines[r]
        self._failures.append({"replica": r, "tick": dead._tick,
                               "reason": reason, "t": time.time()})
        for uid, (rr, local) in sorted(self._by_uid.items()):
            if rr != r:
                continue
            req = dead.requests.get(local)
            if req is None or req.status in TERMINAL:
                continue                # fully served (or retired): keep
            # undelivered tokens in the dead FIFO are DISCARDED — the
            # replay regenerates them; the skip ledger only drops what the
            # consumer actually saw (at-most-once, no loss of the rest)
            req.fifo.clear()
            self._skip[uid] = self._delivered.get(uid, 0)
            self._orphans.append(uid)
        self._dispatch_orphans()

    def _dispatch_orphans(self) -> None:
        """Resubmit orphaned requests (prompt from the original submit) on
        healthy replicas, non-blocking — what does not fit now retries at
        the next step()."""
        still: list[int] = []
        for uid in self._orphans:
            placed = False
            for r in self._order():
                try:
                    local = self.engines[r].submit(
                        block=False, **self._meta[uid])
                except QueueFull:
                    continue
                self._by_uid[uid] = (r, local)
                self._dispatch[r] += 1
                self._requeued += 1
                placed = True
                break
            if not placed:
                still.append(uid)
        self._orphans = still

    # ------------------------------------------------------------ lifecycle
    def step(self) -> int:
        if self._orphans:
            self._dispatch_orphans()
        total = 0
        for r, e in enumerate(self.engines):
            if not self.alive[r]:
                continue
            others_alive = any(self.alive[i] for i in range(len(self.engines))
                               if i != r)
            t0 = time.perf_counter()
            try:
                total += e.step()
            except ReplicaFailure as exc:
                self._fail_replica(r, f"step raised: {exc}")
                continue
            except Exception as exc:
                if not others_alive:
                    raise       # nowhere to fail over to: surface the bug
                self._fail_replica(
                    r, f"step raised: {type(exc).__name__}: {exc}")
                continue
            dt = time.perf_counter() - t0
            if self.health_latency_s is not None \
                    and dt > self.health_latency_s:
                self._fail_replica(
                    r, f"tick latency {dt:.3f}s > health threshold "
                       f"{self.health_latency_s:.3f}s")
        return total

    def pending(self) -> bool:
        return bool(self._orphans) or any(
            e.pending() for r, e in enumerate(self.engines) if self.alive[r])

    def run_until_drained(self, max_ticks: int = 10_000,
                          stall_grace: int = 200) -> list[Request]:
        """Tick until drained. Raises ``StalledEngine`` on router-wide
        livelock (no replica progressed for ``stall_grace`` ticks with work
        pending) or tick-budget exhaustion, and ``AllReplicasDead`` when a
        failover leaves orphans with no healthy replica to take them."""
        last, idle = None, 0
        for _ in range(max_ticks):
            self.step()
            if not self.pending():
                return self.finished
            if self._orphans and not any(self.alive):
                raise AllReplicasDead(
                    f"{len(self._orphans)} requests orphaned and no "
                    f"healthy replica remains")
            sig = tuple(e._progress_signature() for e in self.engines) \
                + (len(self._orphans),)
            if sig == last:
                idle += 1
                if idle >= stall_grace:
                    reps = {r: e._stall_report()
                            for r, e in enumerate(self.engines)
                            if self.alive[r]}
                    raise StalledEngine(
                        f"router made no progress for {idle} ticks with "
                        f"work pending (alive={self.alive}, "
                        f"orphans={len(self._orphans)})",
                        {"replicas": reps, "orphans": list(self._orphans)})
            else:
                last, idle = sig, 0
        raise StalledEngine(
            f"max_ticks={max_ticks} exhausted with work still pending "
            f"(alive={self.alive})",
            {"replicas": {r: e._stall_report()
                          for r, e in enumerate(self.engines)},
             "orphans": list(self._orphans)})

    @property
    def finished(self) -> list[Request]:
        """Finished requests re-keyed to ROUTER uids (each engine numbers
        its own requests from 0, so replica-local uids collide across the
        pool — callers must never see them). Includes requests that
        finished on a now-dead replica; each uid appears exactly once."""
        by_local = [{req.uid: req for req in e.finished}
                    for e in self.engines]
        out = []
        for uid, (r, local) in sorted(self._by_uid.items()):
            req = by_local[r].get(local)
            if req is not None:
                out.append(dataclasses.replace(req, uid=uid))
        return out

    def result(self, uid: int) -> Optional[Request]:
        entry = self._by_uid.get(uid)
        if entry is None:
            return None
        r, local = entry
        return self.engines[r].requests.get(local)

    def pop_output(self, uid: int) -> list[int]:
        r, local = self._by_uid[uid]
        toks = self.engines[r].pop_output(local)
        skip = self._skip.get(uid, 0)
        if skip:
            # failover replay: drop the regenerated prefix the consumer
            # already received from the dead replica
            drop = min(skip, len(toks))
            toks = toks[drop:]
            self._skip[uid] = skip - drop
        if toks:
            self._delivered[uid] = self._delivered.get(uid, 0) + len(toks)
        return toks

    def cancel(self, uid: int) -> bool:
        entry = self._by_uid.get(uid)
        if entry is None:
            return False
        if uid in self._orphans:
            self._orphans.remove(uid)
            return True
        r, local = entry
        return self.engines[r].cancel(local)

    def stats(self) -> dict:
        per = [e.stats() for e in self.engines]
        toks = sum(p.get("tokens", 0) for p in per)
        return {
            "replicas": len(self.engines),
            "alive": list(self.alive),
            "failovers": len(self._failures),
            "failures": [dict(f) for f in self._failures],
            "requeued": self._requeued,
            "orphans": len(self._orphans),
            "dispatch": self._dispatch.tolist(),
            "devices": [str(m.devices.ravel()[0]) for m in self.meshes],
            "tokens": toks,
            "n": sum(p.get("n", 0) for p in per),
            "queue_hwm": max((p.get("queue_hwm", 0) for p in per), default=0),
            "prefill_fifo_hwm": max((p.get("prefill_fifo_hwm", 0)
                                     for p in per), default=0),
            "per_replica": per,
        }
