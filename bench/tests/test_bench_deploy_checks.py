"""The deployment cell's comparison at a size a test can hold: a sound run
is correct, the control (4-bit weights) fails a limit, and an answer or a
spike count altered where produced makes ``correct`` false."""
import pytest

from bench import run as R

CELL = "vgg11-cifar.deploy"
TINY = dict(width_mult=0.125)
LOAD = {"batch": 8, "images": 36, "calib_images": 16}


def test_sound_run_is_correct_and_the_control_is_not():
    kind, run = R.prepare(CELL, 2**31 + 3, 2.0, False, require_tpu=False,
                          sizes_override=TINY, params_override=LOAD)
    st = run.state = kind.setup(run)
    kind.measure(run, st)
    lim = run.params["limits"]
    got = kind.readings(run, st)
    assert all(got[k] <= lim[k] for k in lim)
    ctl = kind.controls(run, st)["control"]
    assert any(ctl[k] > lim[k] for k in lim)


@pytest.mark.parametrize("what", ["logit", "spike_count"])
def test_an_answer_altered_where_produced_is_not_correct(monkeypatch, what):
    mod, _ = R.config("vgg11-cifar")

    def altered(out):
        logits, counts = out
        if what == "logit":
            return logits.at[3, 1].add(0.5), counts
        return logits, counts.at[2].add(1.0)

    monkeypatch.setattr(mod, "deploy_outputs", altered)
    out = R.run_cell(CELL, 9, 2.0, False, require_tpu=False,
                     sizes_override=TINY, params_override=LOAD)
    assert out["correct"] is False


def test_calls_step_through_the_held_data_set():
    import numpy as np

    kind, run = R.prepare(CELL, 2**31 + 5, 1.0, False, require_tpu=False,
                          sizes_override=TINY, params_override=LOAD)
    st = kind.setup(run)
    assert st.batches == 4                       # 36 images, 8 a call
    fwd = run.config.deploy_forward(run.sizes)
    seen = []
    for i in (1, 5):                             # call 5 wraps to batch 1
        got = st.classify(st.artifact, st.data, i % st.batches)
        x = np.asarray(kind.images_of(st, i))
        seen.append(x)
        want = fwd(st.artifact, kind.images_of(st, i))
        for a, b in zip(got, want):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(seen[0], np.asarray(st.data[8:16]))
    np.testing.assert_array_equal(seen[0], seen[1])
    assert not np.array_equal(np.asarray(kind.images_of(st, 0)), seen[0])
