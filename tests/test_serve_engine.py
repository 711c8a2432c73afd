"""Serving engine: continuous batching correctness + elastic-FIFO
invariants.

The decisive tests:
  * the engine's greedy output for each request EQUALS a naive
    single-request reference loop — slot pooling, padding buckets, and
    per-slot length vectors must not change a single token;
  * the chunked-prefill pipeline is BIT-IDENTICAL to the blocking engine
    (same tokens per request, any family);
  * per-request outputs are invariant to arrival order and slot
    contention, and to downstream out-FIFO stalls;
  * no request starves under sustained admission backpressure (bounded
    ticks-to-first-token at a full queue).
"""
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.serve import Engine, EngineConfig, QueueFull, ReplicaRouter

ARCHS = ["qwen3-1.7b", "mamba2-130m", "zamba2-7b"]
REF_MAXLEN = 32          # fixed reference cache size: one decode compile/arch
_REF_JIT: dict = {}


def _prompts(cfg, n=3, lens=(3, 12), seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, int(rng.integers(*lens)))
            for _ in range(n)]


def _ref_steps(model):
    key = (type(model), model.cfg)
    if key not in _REF_JIT:
        _REF_JIT[key] = (
            jax.jit(functools.partial(model.prefill,
                                      return_all_logits=False,
                                      max_len=REF_MAXLEN)),
            jax.jit(model.decode_step))
    return _REF_JIT[key]


def _reference_greedy(model, params, prompt, max_new):
    prefill, decode = _ref_steps(model)
    toks = jnp.asarray(prompt, jnp.int32)[None, :]
    logits, cache = prefill(params, {"tokens": toks})
    out = [int(jnp.argmax(logits[0]))]
    for _ in range(max_new - 1):
        l, cache = decode(params, jnp.asarray([[out[-1]]], jnp.int32), cache)
        out.append(int(jnp.argmax(l[0])))
    return out


def _run(model, params, prompts, max_new=6, **cfg_kw):
    kw = dict(max_slots=3, max_len=64, prefill_pad=8)
    kw.update(cfg_kw)
    eng = Engine(model, params, EngineConfig(**kw))
    uids = [eng.submit(p, max_new=max_new) for p in prompts]
    fin = {r.uid: r for r in eng.run_until_drained()}
    assert len(fin) == len(prompts)
    return [fin[u].out for u in uids], eng


@pytest.mark.parametrize("arch", ARCHS)
def test_engine_matches_reference(arch, lm_zoo):
    cfg, model, params = lm_zoo(arch)
    prompts = _prompts(cfg)
    outs, _ = _run(model, params, prompts)
    for out, prompt in zip(outs, prompts):
        ref = _reference_greedy(model, params, prompt, 6)
        assert out == ref, f"engine={out} ref={ref}"


@pytest.mark.parametrize("arch", ARCHS)
def test_chunked_prefill_matches_blocking(arch, lm_zoo):
    """The tentpole invariant: the elastic-FIFO chunked-prefill pipeline is
    BIT-IDENTICAL to the blocking engine under greedy decode — chunks run
    over the same padded bucket, so every reduction keeps its axis length
    and no token may change."""
    cfg, model, params = lm_zoo(arch)
    prompts = _prompts(cfg, n=4, lens=(3, 20))
    blocking, _ = _run(model, params, prompts)
    chunked, eng = _run(model, params, prompts, prefill_chunk=8)
    assert chunked == blocking
    st = eng.stats()
    assert st["prefill_mode"] == "chunked" and st["prefill_chunks"] > 0


def test_chunked_prefill_matches_blocking_f8_kv(lm_zoo):
    """Quantized serving cache (kv_dtype='f8_e4m3'): the engine must keep
    per-request chunk caches at compute precision and quantize once at the
    slot write — where the blocking path does — so chunked stays
    bit-identical even though the POOL stores f8 keys."""
    cfg, model, params = lm_zoo("qwen3-1.7b", kv_dtype="f8_e4m3")
    prompts = _prompts(cfg, n=3, lens=(3, 14), seed=5)
    blocking, _ = _run(model, params, prompts)
    chunked, _ = _run(model, params, prompts, prefill_chunk=8)
    assert chunked == blocking


def test_submit_rejects_oversized_prompt(lm_zoo):
    cfg, model, params = lm_zoo("qwen3-1.7b")
    eng = Engine(model, params,
                 EngineConfig(max_slots=1, max_len=32, prefill_pad=8,
                              prefill_chunk=8))
    with pytest.raises(ValueError, match="max_len"):
        eng.submit(np.arange(40), max_new=4)


def test_arrival_order_and_slot_contention_invariance(lm_zoo):
    """Per-request outputs depend only on the request, never on arrival
    order or which slots its neighbors occupy: reversing the arrival order
    (different slot assignment, different contention) must reproduce every
    sequence token-for-token."""
    cfg, model, params = lm_zoo("qwen3-1.7b")
    prompts = _prompts(cfg, n=5, lens=(3, 16), seed=1)
    fwd, _ = _run(model, params, prompts, prefill_chunk=8, max_slots=2)
    rev, _ = _run(model, params, prompts[::-1], prefill_chunk=8, max_slots=2)
    assert fwd == rev[::-1]


def test_continuous_batching_overlaps(lm_zoo):
    """More requests than slots: all served; slots reused."""
    cfg, model, params = lm_zoo("qwen3-1.7b")
    outs, eng = _run(model, params,
                     [np.arange(4) + i for i in range(7)],
                     max_new=4, max_slots=2, max_len=32)
    assert len(outs) == 7
    st = eng.stats()
    assert st["tokens"] == 7 * 4


def test_backpressure_no_starvation(lm_zoo):
    """Sustained submits against a FULL bounded admission FIFO: every
    request is served FIFO (no starvation), and ticks-to-first-token stays
    bounded by the work queued ahead of it — the elastic-FIFO guarantee
    that backpressure delays admission, never progress."""
    cfg, model, params = lm_zoo("qwen3-1.7b")
    rng = np.random.default_rng(2)
    eng = Engine(model, params,
                 EngineConfig(max_slots=1, max_len=64, prefill_pad=8,
                              prefill_chunk=8, max_queue=2))
    n_req, max_new = 6, 4
    uids = [eng.submit(rng.integers(0, cfg.vocab_size, 10), max_new=max_new)
            for _ in range(n_req)]
    fin = {r.uid: r for r in eng.run_until_drained()}
    assert len(fin) == n_req                      # nobody starved
    assert eng.stats()["queue_hwm"] == 2          # the FIFO really filled
    # FIFO order: first tokens issue in submit order
    first_ticks = [fin[u].first_token_tick for u in uids]
    assert first_ticks == sorted(first_ticks)
    # bounded ttft: work ahead of any request is at most (queue bound +
    # one live slot) requests x (prefill chunks + decode ticks) each
    per_req = 2 + max_new                         # 2 chunks of 8 for len 10
    bound = (2 + 1) * per_req + per_req
    waits = [fin[u].first_token_tick - fin[u].enqueued_tick for u in uids]
    assert max(waits) <= bound, (waits, bound)


def test_out_fifo_stall_invariance(lm_zoo):
    """A consumer that stops draining stalls ONLY its own slot (exact
    stall: state rolls back, token re-fed) — outputs match the unbounded
    engine token-for-token and the engine reports the stall pressure."""
    cfg, model, params = lm_zoo("qwen3-1.7b")
    prompts = _prompts(cfg, n=4, lens=(3, 12), seed=3)
    ref, _ = _run(model, params, prompts, prefill_chunk=8, max_slots=2)
    eng = Engine(model, params,
                 EngineConfig(max_slots=2, max_len=64, prefill_pad=8,
                              prefill_chunk=8, out_fifo_depth=2))
    uids = [eng.submit(p, max_new=6) for p in prompts]
    drained = {u: [] for u in uids}
    for t in range(500):
        eng.step()
        if t % 3 == 2:                            # lazy consumer
            for u in uids:
                drained[u].extend(eng.pop_output(u))
        if not eng.pending():
            break
    for u in uids:
        drained[u].extend(eng.pop_output(u))
    st = eng.stats()
    assert st["stall_ticks"] > 0                  # backpressure really hit
    assert st["out_fifo_hwm"] <= 2                # bound held
    assert [drained[u] for u in uids] == ref


def test_submit_backpressure_raises_nonblocking(lm_zoo):
    cfg, model, params = lm_zoo("qwen3-1.7b")
    eng = Engine(model, params,
                 EngineConfig(max_slots=1, max_len=32, prefill_pad=8,
                              prefill_chunk=8, max_queue=1))
    eng.submit(np.arange(6), max_new=4)
    with pytest.raises(QueueFull):
        eng.submit(np.arange(6), max_new=4, block=False)
    eng.run_until_drained()


def test_stats_expose_fifo_telemetry(lm_zoo):
    """The software analogue of the paper's FIFO-depth elasticity: queue /
    prefill-FIFO / out-FIFO occupancy high-water marks and decode-tick
    latency percentiles are first-class stats."""
    cfg, model, params = lm_zoo("qwen3-1.7b")
    _, eng = _run(model, params, _prompts(cfg, n=5), prefill_chunk=8)
    st = eng.stats()
    for key in ("queue_hwm", "prefill_fifo_hwm", "out_fifo_hwm",
                "stall_ticks", "prefill_chunks", "decode_tick_p99_s",
                "decode_tick_p50_s", "decode_ticks"):
        assert key in st, key
    assert st["prefill_fifo_hwm"] >= 1
    assert st["decode_tick_p99_s"] >= st["decode_tick_p50_s"] >= 0.0


def test_replica_router_matches_single_engine(lm_zoo):
    """Data-parallel serving: sharding the slot pools across replicas with
    least-loaded dispatch must not change any request's tokens, and the
    dispatch must actually balance."""
    cfg, model, params = lm_zoo("qwen3-1.7b")
    prompts = _prompts(cfg, n=4, lens=(3, 14), seed=4)
    single, _ = _run(model, params, prompts, prefill_chunk=8, max_slots=2)
    router = ReplicaRouter(
        model, params,
        EngineConfig(max_slots=2, max_len=64, prefill_pad=8,
                     prefill_chunk=8), n_replicas=2)
    uids = [router.submit(p, max_new=6) for p in prompts]
    router.run_until_drained()
    outs = [router.result(u).out for u in uids]
    assert outs == single
    st = router.stats()
    assert st["replicas"] == 2 and sum(st["dispatch"]) == len(prompts)
    assert min(st["dispatch"]) >= 1               # least-loaded balanced


def test_qk_spiking_engine_stateless_cache(lm_zoo):
    """Paper C4 serving: QKFormer attention decodes with a 0-length cache,
    identically under blocking and chunked prefill."""
    cfg, model, params = lm_zoo("qwen3-1.7b", spiking=True,
                                attention_kind="qk_spiking")
    cache = model.init_cache(2, 64)
    k, v = cache["layers"]
    assert k.shape[-3] == 0                     # no KV storage at all
    blocking, _ = _run(model, params, [np.arange(5)], max_new=4,
                       max_slots=2, max_len=32)
    chunked, _ = _run(model, params, [np.arange(5)], max_new=4,
                      max_slots=2, max_len=32, prefill_chunk=4)
    assert blocking == chunked
    assert len(blocking[0]) == 4


# ------------------------------------------------ tick log and phase spans
PHASES = {"serve.step", "serve.admit", "serve.prefill", "serve.decode",
          "serve.spike_stats", "serve.sample"}


def _packed_engine(lm_zoo, **cfg_kw):
    """A tiny spiking engine under ``fused_packed``: one packed word pool,
    spike telemetry every tick unless ``spike_stats_every`` says else."""
    cfg, model, params = lm_zoo("qwen3-1.7b", spiking=True,
                                attention_kind="qk_spiking")
    kw = dict(max_slots=2, max_len=32, prefill_pad=8, prefill_chunk=4,
              policy="fused_packed")
    kw.update(cfg_kw)
    return cfg, Engine(model, params, EngineConfig(**kw))


def _drive(eng, prompts, max_new=4):
    """Submit, then step until drained; returns (outputs by submit order,
    number of step() calls)."""
    uids = [eng.submit(p, max_new=max_new) for p in prompts]
    calls = 0
    while eng.pending():
        eng.step()
        calls += 1
    fin = {r.uid: r.out for r in eng.finished}
    return [fin[u] for u in uids], calls


def test_tick_log_keeps_one_record_per_step(lm_zoo):
    cfg, eng = _packed_engine(lm_zoo)
    assert eng.last_tick is None
    eng.step()                                  # nothing to do: early return
    empty = eng.last_tick
    assert not empty["decoded"] and empty["live"] == 0
    assert empty["syncs"] == 0 and empty["chunks"] == 0
    assert set(empty["phases"]) == {"step", "admit"}
    _, calls = _drive(eng, _prompts(cfg, n=3, lens=(3, 9), seed=5))
    recs = list(eng._ticks)
    assert len(recs) == calls + 1
    assert [r["tick"] for r in recs] == sorted(r["tick"] for r in recs)
    assert sum(r["chunks"] for r in recs) == eng._prefill_chunks
    assert sum(r["live"] for r in recs) + 3 == eng._tokens_emitted
    for r in recs:
        assert r["decoded"] == ("decode" in r["phases"])
        assert r["phases"]["step"] >= sum(
            ns for k, ns in r["phases"].items() if k != "step")


@pytest.mark.parametrize("spike_every,telemetry_syncs", [(1, 1), (0, 0)])
def test_host_syncs_per_tick_follow_the_live_slots(lm_zoo, spike_every,
                                                   telemetry_syncs):
    """A tick that finishes no prefill syncs once for the decode's logits,
    once for the batched greedy tokens of all its live slots and once per
    packed pool spike telemetry fetches (the tiny model holds one): the
    count no longer follows the live slots."""
    cfg, eng = _packed_engine(lm_zoo, spike_stats_every=spike_every,
                              max_slots=4)
    # staggered lengths: pure decode ticks at 4, 3, 2 and 1 live slots
    for i, p in enumerate(_prompts(cfg, n=4, lens=(3, 9), seed=6)):
        eng.submit(p, max_new=4 + 3 * i)
    while eng.pending():
        eng.step()
    pure = [r for r in eng._ticks if r["decoded"] and r["chunks"] == 0]
    assert {1, 2} <= {r["live"] for r in pure}
    assert max(r["live"] for r in pure) >= 3
    for r in pure:
        assert r["syncs"] == 2 + telemetry_syncs, r
        assert r["batched"] == r["live"], r
    assert ("spike_stats" in eng.last_tick["phases"]) == bool(spike_every)


@pytest.fixture(scope="module")
def traced_run(lm_zoo, tmp_path_factory):
    """The same requests served twice: once with a profiler trace running,
    once without. Returns (traced outputs, untraced outputs, the host
    events of the trace as (name, start, end), records made traced)."""
    from jax.profiler import ProfileData

    cfg, _ = _packed_engine(lm_zoo)
    prompts = _prompts(cfg, n=3, lens=(3, 9), seed=7)
    plain, _ = _drive(_packed_engine(lm_zoo)[1], prompts)
    eng = _packed_engine(lm_zoo)[1]
    log_dir = str(tmp_path_factory.mktemp("engine-trace"))
    jax.profiler.start_trace(log_dir)
    try:
        traced, _ = _drive(eng, prompts)
    finally:
        jax.profiler.stop_trace()
    (path,) = [os.path.join(d, f) for d, _, fs in os.walk(log_dir)
               for f in fs if f.endswith(".xplane.pb")]
    events = [(ev.name.split("#", 1)[0], ev.start_ns,
               ev.start_ns + ev.duration_ns)
              for plane in ProfileData.from_file(path).planes
              for line in plane.lines for ev in line.events
              if ev.name.startswith("serve.")]
    return traced, plain, events, list(eng._ticks)


def test_phase_spans_nest_in_the_step_span_of_a_trace(traced_run):
    _, _, events, recs = traced_run
    assert PHASES <= {name for name, _, _ in events}
    steps = [(s, e) for name, s, e in events if name == "serve.step"]
    assert len(steps) == len(recs)
    for name, s, e in events:
        if name != "serve.step":
            assert any(a <= s and e <= b for a, b in steps), name
    # one prefill span per chunk the engine ran
    assert sum(name == "serve.prefill" for name, _, _ in events) == \
        sum(r["chunks"] for r in recs)


def test_greedy_outputs_are_identical_with_a_trace_running(traced_run):
    traced, plain, _, _ = traced_run
    assert traced == plain
    assert all(len(out) == 4 for out in traced)


def test_stats_derive_tick_percentiles_and_syncs_from_the_tick_log(lm_zoo):
    cfg, eng = _packed_engine(lm_zoo)
    _drive(eng, _prompts(cfg, n=3, lens=(3, 9), seed=8))
    st = eng.stats()
    decoded = [r for r in eng._ticks if r["decoded"]]
    assert st["decode_ticks"] == len(decoded)
    assert st["decode_tick_max_s"] >= st["decode_tick_p99_s"] \
        >= st["decode_tick_p50_s"] > 0.0
    assert st["decode_tick_max_s"] == \
        max(r["phases"]["decode"] for r in decoded) / 1e9
    assert st["host_syncs_per_tick_mean"] == pytest.approx(
        np.mean([r["syncs"] for r in decoded]))
    assert PHASES <= {"serve." + k for k in st["phase_ms"]}
    for ms in st["phase_ms"].values():
        assert ms["p99"] >= ms["p50"] >= 0.0
    for r in eng.finished:
        assert r.enqueued_t <= r.admitted_t <= r.first_token_t
