"""Offline batch inference through the deployed artifact: a held data set
classified in calls of ``batch`` images, back to back, for the whole
window.

The data set (``images`` of them) is made on the device from the seed in
one call and stays there, as an offline job holds its input. Call i
classifies batch i mod (images // batch) of it, sliced inside the jitted
call. At most two calls are in flight: a call is dispatched, then the one
before it is waited for. The window ends at the completion of the first
call that ends after ``--seconds``; the rate is the images of all
completed calls over that time.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from bench.common import (STREAM_DATA, STREAM_SAMPLE, STREAM_WEIGHTS,
                          jax_key, np_rng)


@dataclasses.dataclass
class State:
    classify: object                  # jitted (artifact, data, i) -> outputs
    artifact: list
    data: object                      # [images, H, W, C] on the device
    batch: int
    outputs: list = dataclasses.field(default_factory=list)
    calls: int = 0
    window: float = 0.0

    @property
    def batches(self) -> int:
        return self.data.shape[0] // self.batch


def setup(run) -> State:
    import jax

    cfgm, sizes, p = run.config, run.sizes, run.params
    k_data, k_calib = jax.random.split(jax_key(run.seed, STREAM_DATA))
    data = cfgm.images(sizes, k_data, p["images"])
    calib = cfgm.images(sizes, k_calib, p["calib_images"])
    art = cfgm.make_artifact(sizes, jax_key(run.seed, STREAM_WEIGHTS), calib)
    forward, batch = cfgm.deploy_forward(sizes), p["batch"]

    def classify_batch(art, data, i):
        x = jax.lax.dynamic_slice_in_dim(data, i * batch, batch)
        return forward(art, x)

    classify = jax.jit(classify_batch)
    st = State(classify, art, data, batch)
    for i in range(2):
        jax.block_until_ready(classify(art, data, i))
    return st


def images_of(st: State, i: int):
    """The images call i classified."""
    import jax

    return jax.lax.dynamic_slice_in_dim(st.data, (i % st.batches) * st.batch,
                                        st.batch)


def measure(run, st: State) -> None:
    import jax

    outs, prev = [], None
    t0 = time.perf_counter()
    i = 0
    while True:
        now = time.perf_counter() - t0
        run.trace_tick(now)
        with run.span("call"):
            cur = st.classify(st.artifact, st.data, i % st.batches)
            if prev is not None:
                jax.block_until_ready(prev)
        outs.append(cur)
        prev = cur
        i += 1
        if time.perf_counter() - t0 >= run.seconds:
            break
    run.trace_stop()
    jax.block_until_ready(prev)
    st.window = time.perf_counter() - t0
    st.calls = i
    st.outputs = outs
    run.note(calls=i, images=i * run.params["batch"], window_s=st.window)


def attempts(run, st: State) -> tuple[int, int]:
    return st.calls, 0


def end_to_end(run, st: State) -> dict:
    return {"deploy_images_per_s": st.calls * run.params["batch"]
            / st.window}


def _errors(out, ref) -> tuple:
    """(worst logit error over the reference's logit RMS, worst per-layer
    spike-count error over the reference's count)."""
    (lg, cnt), (rl, rc) = ([np.asarray(a) for a in o] for o in (out, ref))
    scale = max(float(np.sqrt(np.mean(rl ** 2))), 1e-30)
    return (float(np.abs(lg - rl).max()) / scale,
            float((np.abs(cnt - rc) / np.maximum(rc, 1)).max()))


def _sample(run, st: State) -> list:
    rng = np_rng(run.seed, STREAM_SAMPLE)
    k = min(run.params["check_calls"], st.calls)
    return sorted({st.calls - 1} | set(
        rng.choice(st.calls, k, replace=False).tolist()))


def readings(run, st: State) -> dict:
    """A sample of the window's calls drawn from the seed (the last one
    among them): every image's logits and every layer's spike count
    against the reference on the same images."""
    err = mis = 0.0
    idx = _sample(run, st)
    for i in idx:
        ref = run.config.deploy_reference(st.artifact, images_of(st, i),
                                          run.sizes)
        e, m = _errors(run.config.deploy_outputs(st.outputs[i]), ref)
        err, mis = max(err, e), max(mis, m)
    run.note(check_calls=len(idx),
             check_images=len(idx) * run.params["batch"])
    return {"logit_err_max": err, "spike_count_err_max": mis}


def controls(run, st: State) -> dict:
    """The control (weights on a 4-bit grid) in the program's place, on
    the same sampled calls' images."""
    err = mis = 0.0
    for i in _sample(run, st):
        x = images_of(st, i)
        e, m = _errors(
            run.config.deploy_reference(st.artifact, x, run.sizes, True),
            run.config.deploy_reference(st.artifact, x, run.sizes))
        err, mis = max(err, e), max(mis, m)
    return {"control": {"logit_err_max": err, "spike_count_err_max": mis}}
