"""Open-loop serving traffic through ``repro.serve.Engine``.

Requests arrive on a Poisson schedule at ``rate_per_s`` whatever the
engine does (independent users). Prompt and output lengths are lognormal
(``median``, ``sigma``, clipped to ``min``..``max``); every request decodes
greedily to its output length. The lengths and the inter-arrival gaps are
stratified quantiles of the two laws, put in one fixed order (drawn once,
from a stream no seed changes); the seed draws the prompts' tokens and the
weights. So every seed brings the same work at the same times: with ~50
requests in a window, the order alone moved the p95 TTFT by half between
seeds.

Timing: a request is *due* at its scheduled time; it is submitted at the
first loop turn after that (the lateness is recorded). The inter-token
gaps, the cell's metric, are those between consecutive tokens of a
request, both emitted inside the window. After the window the engine keeps
stepping, with no new arrivals, until every request due in the window has
its first token (at most ``drain_s``); a request without one then has
failed. The time to first token, from when a request was due to the end
of the engine step that emitted its first token, goes on an earlier line
of the run's record: a host stall of a second or two in one run of five
moves its tail over ~50-100 requests by more than any bound holds.
"""
from __future__ import annotations

import dataclasses
import statistics
import time
from collections import deque

import numpy as np

from bench.common import (STREAM_SAMPLE, STREAM_TRAFFIC, STREAM_WEIGHTS,
                          jax_key, np_rng, percentile)


OFF_TOP = 1e-3                        # logits


# ---------------------------------------------------------------- schedule
def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_lengths(n: int, law: dict) -> np.ndarray:
    """n stratified draws of a clipped lognormal, in ascending order."""
    nd = statistics.NormalDist()
    z = np.array([nd.inv_cdf(u) for u in _quantiles(n)])
    x = np.round(law["median"] * np.exp(law["sigma"] * z))
    return np.clip(x, law["min"], law["max"]).astype(np.int64)


@dataclasses.dataclass
class Req:
    due: float                        # seconds from the window's start
    prompt: np.ndarray
    max_new: int
    uid: int = -1
    submitted: float = float("nan")
    first: float = float("nan")
    times: list = dataclasses.field(default_factory=list)
    n_seen: int = 0


def schedule(params: dict, seed: int, seconds: float, vocab: int) -> list:
    """The requests due in a window of ``seconds``, in order of arrival."""
    n = max(1, int(round(params["rate_per_s"] * seconds)))
    order = np_rng(0, STREAM_TRAFFIC)
    plen = order.permutation(lognormal_lengths(n, params["prompt_len"]))
    olen = order.permutation(lognormal_lengths(n, params["output_len"]))
    gaps = order.permutation(-np.log1p(-_quantiles(n)) / params["rate_per_s"])
    due = np.cumsum(gaps) - gaps[0]
    rng = np_rng(seed, STREAM_TRAFFIC)
    return [Req(float(t), rng.integers(0, vocab, int(p)).astype(np.int32),
                int(o))
            for t, p, o in zip(due, plen, olen) if t < seconds]


# ------------------------------------------------------------------- setup
@dataclasses.dataclass
class State:
    model: object
    params: dict
    engine: object
    reqs: list = dataclasses.field(default_factory=list)
    steps: list = dataclasses.field(default_factory=list)
    window: float = 0.0
    drain_end: float = 0.0
    pairs: list = dataclasses.field(default_factory=list)


def _chunks(prompt_len: int, chunk: int, pad: int) -> list:
    """(rows run, valid prompt tokens) of each prefill chunk the engine
    runs for a prompt: its bucket is the prompt rounded up to ``pad``, cut
    into chunks of ``chunk`` rows."""
    bucket = -(-prompt_len // pad) * pad
    return [(min(chunk, bucket - s), max(0, min(chunk, prompt_len - s)))
            for s in range(0, bucket, chunk)]


def setup(run) -> State:
    from repro.serve import Engine

    cfgm, sizes = run.config, run.sizes
    model, params = cfgm.build(sizes, jax_key(run.seed, STREAM_WEIGHTS),
                               run.devices[0])
    ecfg = cfgm.engine_config(sizes)
    # warm-up: prompts whose buckets take every chunk call the traffic
    # makes (a first, a following and a 64-token tail chunk), two of each,
    # two greedy tokens each. The decode runs over the whole pool whatever
    # its occupancy, and the engine's per-slot eager ops take the slot as
    # an operand, so six requests compile what sixty-four would.
    warm = Engine(model, params, ecfg)
    rng = np_rng(run.seed, STREAM_SAMPLE)
    c, p = ecfg.prefill_chunk, ecfg.prefill_pad
    for n in 2 * [c - 28, p - 14, 2 * c + p - 20]:
        warm.submit(rng.integers(0, sizes["vocab_size"], n), max_new=2)
    warm.run_until_drained()
    del warm
    return State(model, params, Engine(model, params, ecfg))


# ----------------------------------------------------------------- measure
def measure(run, st: State) -> None:
    eng = st.engine
    ecfg = eng.cfg
    chunk, pad = eng._chunk_size(), ecfg.prefill_pad
    st.reqs = schedule(run.params, run.seed, run.seconds,
                       run.sizes["vocab_size"])
    todo = deque(st.reqs)
    live: dict = {}
    chunk_list: list = []             # (rows, valid tokens) of each chunk
    chunk_done = 0
    t0 = time.perf_counter()
    st.window = run.seconds
    in_window = True
    waiting_first = 0
    while True:
        now = time.perf_counter() - t0
        if in_window and now >= run.seconds:
            in_window = False
            run.trace_stop()
        if not in_window and (waiting_first == 0
                              or now >= run.seconds + run.params["drain_s"]):
            break
        if in_window:
            run.trace_tick(now)
            while todo and todo[0].due <= now:
                r = todo.popleft()
                r.uid = eng.submit(r.prompt, max_new=r.max_new)
                r.submitted = now
                live[r.uid] = r
                waiting_first += 1
                chunk_list += _chunks(len(r.prompt), chunk, pad)
        if not eng.pending():
            if in_window:
                nxt = todo[0].due if todo else run.seconds
                time.sleep(max(0.0, min(nxt, run.seconds) - now))
            continue
        c0, k0, e0 = eng._prefill_chunks, eng._tick, eng._tokens_emitted
        with run.span("step"):
            eng.step()
        t = time.perf_counter() - t0
        firsts = 0
        for uid, r in list(live.items()):
            req = eng.requests.get(uid)
            out = req.out if req is not None else []
            new = len(out) - r.n_seen
            if new > 0:
                if r.n_seen == 0:
                    r.first = t
                    firsts += 1
                    waiting_first -= 1
                r.times.extend([t] * new)
                r.n_seen = len(out)
            if req is None or req.done:
                del live[uid]
                if r.n_seen == 0:
                    waiting_first -= 1
        dc = eng._prefill_chunks - c0
        ran = chunk_list[chunk_done:chunk_done + dc]
        chunk_done += dc
        st.steps.append(dict(dt=run.spans[-1][2] - run.spans[-1][1],
                             traced=run.spans[-1][3],
                             decoded=eng._tick > k0,
                             chunk_rows=[rows for rows, _ in ran],
                             prompt_tokens=sum(v for _, v in ran),
                             decode_tokens=eng._tokens_emitted - e0 - firsts))
    st.drain_end = time.perf_counter() - t0
    late = [r.submitted - r.due for r in st.reqs if r.uid >= 0]
    done = [r for r in st.reqs if r.uid >= 0 and _status(st, r) == "done"]
    ttft = ttft_s(st)
    run.note(generator_late_p50_ms=1e3 * percentile(late, 50),
             generator_late_max_ms=1e3 * max(late, default=0.0),
             sent=sum(r.uid >= 0 for r in st.reqs), completed=len(done),
             failed=attempts(run, st)[1],
             drain_s=st.drain_end - run.seconds,
             steps=len(st.steps),
             ttft_p50_ms=1e3 * percentile(ttft, 50),
             ttft_p95_ms=1e3 * percentile(ttft, 95))
    stats = eng.stats()
    if "spike_rate_mean" in stats:
        run.note(spike_rate_mean=stats["spike_rate_mean"],
                 spike_note="random weights from the seed; a trained "
                            "model's spike rates differ")


def _status(st: State, r: Req) -> str:
    req = st.engine.requests.get(r.uid)
    if req is not None:
        return req.status
    fin = {q.uid: q.status for q in st.engine.finished}
    return fin.get(r.uid, "lost")


def attempts(run, st: State) -> tuple[int, int]:
    """(requests due in the window, those that failed or never produced a
    first token before the drain ended)."""
    sent = [r for r in st.reqs if r.uid >= 0]
    bad = sum(1 for r in sent
              if np.isnan(r.first)
              or _status(st, r) in ("failed", "cancelled", "deadline_miss",
                                    "lost"))
    return len(sent), bad


def ttft_s(st: State) -> np.ndarray:
    """Time to first token of every request due in the window, from when
    it was due; one still without a first token counts to the drain's
    end."""
    return np.array([(r.first if not np.isnan(r.first) else st.drain_end)
                     - r.due for r in st.reqs if r.uid >= 0])


def end_to_end(run, st: State) -> dict:
    sent = [r for r in st.reqs if r.uid >= 0]
    itl = [b - a for r in sent for a, b in zip(r.times, r.times[1:])
           if b <= st.window]
    return {"itl_p95_ms": 1e3 * percentile(itl, 95)}


# ------------------------------------------------------------------- check
def readings(run, st: State) -> dict:
    """Sample finished requests (the longest among them) until some
    hundreds of served tokens, and hold every served token against the
    reference: the widest gap between the reference's best logit and its
    logit of the served token."""
    eng = st.engine
    done = {q.uid: q for q in eng.finished if q.status == "done"}
    fin = [r for r in st.reqs if r.uid in done]
    st.pairs = sample_pairs(fin, done, run.seed, run.params["check_tokens"])
    st.engine = None                 # free the slot pool before the check
    del eng
    gaps, _ = _gaps(run, st, control=False)
    run.note(check_tokens=len(st.pairs),
             check_requests=len({p[2] for p in st.pairs}))
    return _summary(gaps)


def controls(run, st: State) -> dict:
    """The control's readings on the same pairs: the gaps of the tokens
    the lower-precision reference puts first."""
    _, gaps_c = _gaps(run, st, control=True)
    return {"control": _summary(gaps_c)}


def _summary(gaps) -> dict:
    """The widest gap, and the share of tokens the reference puts more
    than ``OFF_TOP`` below its best (rounding of its float32 sums moves a
    logit by ~1e-5)."""
    if not len(gaps):
        return {"served_gap_max": float("inf"),
                "served_off_top_share": float("inf")}
    return {"served_gap_max": float(gaps.max()),
            "served_off_top_share": float((gaps > OFF_TOP).mean())}


def _gaps(run, st: State, control: bool):
    inputs = np.array([p[0] for p in st.pairs], np.int32)
    served = np.array([p[1] for p in st.pairs], np.int32)
    return run.config.served_gaps(st.params, run.sizes, inputs, served,
                                  control=control)


def sample_pairs(fin: list, done: dict, seed: int, want: int) -> list:
    """(input token, served token, uid) of the requests sampled from the
    seed: the longest first, then in random order until ``want`` tokens."""
    if not fin:
        return []
    rng = np_rng(seed, STREAM_SAMPLE)
    order = sorted(fin, key=lambda r: -done[r.uid].max_new)[:1] + \
        [fin[i] for i in rng.permutation(len(fin))]
    pairs, seen = [], set()
    for r in order:
        if r.uid in seen:
            continue
        seen.add(r.uid)
        out = done[r.uid].out
        inp = [int(r.prompt[-1])] + [int(t) for t in out[:-1]]
        pairs += [(a, int(b), r.uid) for a, b in zip(inp, out)]
        if len(pairs) >= want:
            break
    return pairs
