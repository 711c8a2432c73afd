"""Greedy sampling of a pool decode: one batched argmax per tick.

Every served token passes through ``Engine._sample``. The greedy slots of
a pool decode read their tokens from one row-wise argmax fetched once per
tick; a temperature slot still draws from the engine's RNG stream one slot
at a time. The invariants, for every LM family the engine serves:

  * each request's greedy tokens equal the same request served alone;
  * a stalled slot and a quarantined slot emit nothing a fault-free,
    unstalled run would not;
  * a temperature request gives the same seeded tokens beside greedy
    neighbours as alone, and only it is sampled one slot at a time;
  * a patched ``Engine._sample`` still decides a served decode token.
"""
import numpy as np
import pytest

from repro.serve import Engine, EngineConfig, FaultPlan

SPIKING = dict(spiking=True, attention_kind="qk_spiking")
# (arch, model overrides, engine policy): the ANN softmax form, SSM,
# hybrid, and the spiking form under the dense and the packed policy
FAMILIES = {
    "softmax": ("qwen3-1.7b", {}, None),
    "ssm": ("mamba2-130m", {}, None),
    "hybrid": ("zamba2-7b", {}, None),
    "spiking_dense": ("qwen3-1.7b", SPIKING, "fused_dense"),
    "spiking_packed": ("qwen3-1.7b", SPIKING, "fused_packed"),
}
MAX_NEW = 6


def _engine(lm_zoo, family, rng_seed=0, faults=None, **cfg_kw):
    arch, overrides, policy = FAMILIES[family]
    cfg, model, params = lm_zoo(arch, **overrides)
    kw = dict(max_slots=3, max_len=64, prefill_pad=8, prefill_chunk=8,
              policy=policy)
    kw.update(cfg_kw)
    return cfg, Engine(model, params, EngineConfig(**kw), rng_seed=rng_seed,
                       faults=faults)


def _prompts(cfg, n=3, seed=11):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size, int(rng.integers(3, 12)))
            for _ in range(n)]


def _serve(eng, prompts, temperatures=None):
    temperatures = temperatures or [0.0] * len(prompts)
    uids = [eng.submit(p, max_new=MAX_NEW, temperature=t)
            for p, t in zip(prompts, temperatures)]
    fin = {r.uid: r for r in eng.run_until_drained()}
    return [fin[u].out for u in uids]


def _alone(lm_zoo, family, prompts, temperatures=None, **kw):
    temperatures = temperatures or [0.0] * len(prompts)
    return [_serve(_engine(lm_zoo, family, **kw)[1], [p], [t])[0]
            for p, t in zip(prompts, temperatures)]


@pytest.mark.parametrize("family", FAMILIES)
def test_greedy_pool_tokens_equal_each_request_served_alone(lm_zoo, family):
    cfg, eng = _engine(lm_zoo, family)
    prompts = _prompts(cfg)
    together = _serve(eng, prompts)
    assert together == _alone(lm_zoo, family, prompts)
    decoded = [r for r in eng._ticks if r["decoded"]]
    assert max(r["live"] for r in decoded) == 3
    for r in decoded:                   # an all-greedy pool: every token
        assert r["batched"] == r["live"], r
    assert eng.stats()["batched_sample_share"] == 1.0


@pytest.mark.parametrize("family", FAMILIES)
def test_stalled_and_quarantined_slots_emit_nothing_wrong(lm_zoo, family):
    """A lazy consumer stalls slots on a full output FIFO, and a NaN lands
    in one slot's logits with the integrity guard on: that slot is
    quarantined before the sample and replayed. What every consumer drains
    equals the fault-free, unstalled run, each token delivered once."""
    cfg, ref_eng = _engine(lm_zoo, family)
    prompts = _prompts(cfg, seed=12)
    ref = _serve(ref_eng, prompts)
    plan = FaultPlan(3).nan_logits(4)
    _, eng = _engine(lm_zoo, family, faults=plan, out_fifo_depth=2,
                     integrity_every=1)
    uids = [eng.submit(p, max_new=MAX_NEW) for p in prompts]
    drained = {u: [] for u in uids}
    for t in range(500):
        eng.step()
        if t % 3 == 2:
            for u in uids:
                drained[u].extend(eng.pop_output(u))
        if not eng.pending():
            break
    for u in uids:
        drained[u].extend(eng.pop_output(u))
    st = eng.stats()
    assert st["stall_ticks"] > 0 and st["quarantined"] == 1
    assert [drained[u] for u in uids] == ref
    for r in eng._ticks:
        assert r["batched"] == r["live"], r


@pytest.mark.parametrize("family", ["softmax", "spiking_packed"])
def test_logits_replaced_by_a_fault_are_reduced_afresh(lm_zoo, family):
    """With the guard off a NaN row reaches the sample: its token is the
    per-row argmax of the replaced logits (index 0), not of the decode's."""
    cfg, eng = _engine(lm_zoo, family, faults=FaultPlan(3).nan_logits(4, 2))
    prompts = _prompts(cfg, seed=13)
    outs = _serve(eng, prompts)
    ref = _serve(_engine(lm_zoo, family)[1], prompts)
    (hit,) = [i for i, (o, r) in enumerate(zip(outs, ref)) if o != r]
    k = next(j for j, (a, b) in enumerate(zip(outs[hit], ref[hit]))
             if a != b)
    assert outs[hit][k] == 0


@pytest.mark.parametrize("family", FAMILIES)
def test_a_temperature_request_samples_alike_beside_greedy_neighbours(
        lm_zoo, family):
    cfg, eng = _engine(lm_zoo, family, rng_seed=5)
    prompts = _prompts(cfg, seed=14)
    temps = [0.0, 0.9, 0.0]
    uids = [eng.submit(p, max_new=MAX_NEW, temperature=t)
            for p, t in zip(prompts, temps)]
    hot = eng.requests[uids[1]]
    fresh = []                          # decode tokens of `hot` per step
    while eng.pending():
        n0 = len(hot.out)
        eng.step()
        fresh.append(len(hot.out) - n0 - (n0 == 0 and len(hot.out) > 0))
    outs = [r.out for r in sorted(eng.finished, key=lambda r: r.uid)]
    assert outs[1] == _alone(lm_zoo, family, [prompts[1]], [0.9],
                             rng_seed=5)[0]
    assert [outs[0], outs[2]] == _alone(lm_zoo, family,
                                        [prompts[0], prompts[2]])
    recs = list(eng._ticks)
    assert [r["live"] - r["batched"] for r in recs] == fresh
    assert any(r["live"] == 3 and r["batched"] == 2 for r in recs)
    assert eng.stats()["batched_sample_share"] == pytest.approx(
        sum(r["batched"] for r in recs) / sum(r["live"] for r in recs))


@pytest.mark.parametrize("family", FAMILIES)
def test_a_patched_sample_alters_a_served_decode_token(lm_zoo, family,
                                                       monkeypatch):
    cfg, eng = _engine(lm_zoo, family)
    prompts = _prompts(cfg, seed=15)
    ref = _serve(eng, prompts)
    orig = Engine._sample

    def altered(self, logits, req):
        tok = orig(self, logits, req)
        return (tok + 1) % logits.shape[-1] if len(req.out) == 3 else tok

    monkeypatch.setattr(Engine, "_sample", altered)
    outs = _serve(_engine(lm_zoo, family)[1], prompts)
    for out, r in zip(outs, ref):
        assert out[:3] == r[:3]
        assert out[3] == (r[3] + 1) % cfg.vocab_size
