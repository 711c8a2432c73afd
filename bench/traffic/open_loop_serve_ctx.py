"""Open-loop serving traffic, checked over whole sequences: for models whose
logits at a position depend on the tokens before it (KV and SSM state).

The schedule, set-up, window, failures, time to first token and end-to-end
numbers are ``open_loop_serve``'s (same parameters, same laws). Only the
check differs: ``open_loop_serve`` hands the configuration (input token,
served token) pairs, which is enough for a token-local model; here each
sampled request's prompt and served tokens go to the configuration's
``sequence_gaps``, which teacher-forces the whole sequence through the
reference and returns, at every served position, the reference's best
logit minus its logit of the served token, in units of the standard
deviation of the reference's logits at that position.
"""
from __future__ import annotations

import numpy as np

from bench.common import STREAM_SAMPLE, np_rng
from bench.run import traffic

_base = traffic("open_loop_serve")
OFF_TOP = 1e-3                        # logit standard deviations
State = _base.State
schedule, setup, measure = _base.schedule, _base.setup, _base.measure
attempts, ttft_s, end_to_end = _base.attempts, _base.ttft_s, _base.end_to_end


def readings(run, st) -> dict:
    """Sample finished requests (the longest among them) until some
    hundreds of served tokens, free the slot pool, and hold every served
    token against the reference run over its whole sequence."""
    eng = st.engine
    done = {q.uid: q for q in eng.finished if q.status == "done"}
    fin = [r for r in st.reqs if r.uid in done]
    st.pairs = sample_sequences(fin, done, run.seed,
                                run.params["check_tokens"])
    st.engine = None                 # free the slot pool before the check
    del eng
    gaps, _ = run.config.sequence_gaps(st.params, run.sizes, _seqs(st))
    run.note(check_tokens=int(sum(len(s) for _, s, _ in st.pairs)),
             check_requests=len(st.pairs))
    return _summary(gaps)


def controls(run, st) -> dict:
    """The control's readings on the same sequences: the gaps of the
    tokens the lower-precision reference puts first."""
    _, gaps_c = run.config.sequence_gaps(st.params, run.sizes, _seqs(st),
                                         control=True)
    return {"control": _summary(gaps_c)}


def _summary(gaps) -> dict:
    """The widest gap, and the share of served tokens the reference puts
    more than ``OFF_TOP`` below its best (the float32 reference's own
    rounding moves a gap by ~1e-6)."""
    if not len(gaps):
        return {"served_gap_max": float("inf"),
                "served_off_top_share": float("inf")}
    return {"served_gap_max": float(gaps.max()),
            "served_off_top_share": float((gaps > OFF_TOP).mean())}


def _seqs(st) -> list:
    return [(prompt, served) for prompt, served, _ in st.pairs]


def sample_sequences(fin: list, done: dict, seed: int, want: int) -> list:
    """(prompt, served tokens, uid) of the requests sampled from the seed:
    the longest first, then in random order until ``want`` served
    tokens."""
    if not fin:
        return []
    rng = np_rng(seed, STREAM_SAMPLE)
    order = sorted(fin, key=lambda r: -done[r.uid].max_new)[:1] + \
        [fin[i] for i in rng.permutation(len(fin))]
    out, seen, n = [], set(), 0
    for r in order:
        if r.uid in seen:
            continue
        seen.add(r.uid)
        served = np.asarray(done[r.uid].out, np.int32)
        out.append((r.prompt, served, r.uid))
        n += len(served)
        if n >= want:
            break
    return out
