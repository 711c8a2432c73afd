"""The hybrid RAG cell's comparison at a size a test can hold: a sound run
is correct, the float8 control in the program's place fails a limit, and a
token altered where the engine produces it makes ``correct`` false. The
tiny configuration keeps granite-4.0-h-micro's layer kinds and multipliers
(two periods of mamba, attention, mamba) and computes in float32, where
the program matches the reference to rounding; its embedding is scaled as
the configuration's is, further at this width, so that the logits depend
on the context and not on the input token alone."""
import numpy as np

from bench import run as R

CELL = "granite-h-micro.rag"
TINY = dict(num_hidden_layers=6, hidden_size=64, num_attention_heads=4,
            num_key_value_heads=1, shared_intermediate_size=128,
            intermediate_size=128, vocab_size=512, mamba_n_heads=8,
            mamba_d_head=16, mamba_d_state=16, mamba_chunk_size=16,
            layer_types=["mamba", "attention", "mamba"] * 2,
            param_dtype="float32", compute_dtype="float32",
            embedding_init_scale=1 / 64,
            engine={"max_slots": 4, "max_len": 512, "prefill_chunk": 32,
                    "prefill_pad": 32})
LOAD = {"rate_per_s": 2.0, "check_tokens": 40,
        "prompt_len": {"median": 60, "sigma": 0.7, "min": 8, "max": 200},
        "output_len": {"median": 8, "sigma": 0.5, "min": 3, "max": 20}}


def tiny_run(seed=2**31 + 21):
    kind, run = R.prepare(CELL, seed, 3.0, False, require_tpu=False,
                          sizes_override=TINY, params_override=LOAD)
    st = run.state = kind.setup(run)
    kind.measure(run, st)
    return kind, run, st


def test_sound_run_is_correct_and_the_control_is_not():
    kind, run, st = tiny_run()
    lim = run.params["limits"]
    got = kind.readings(run, st)
    assert sum(len(s) for _, s, _ in st.pairs) >= 40
    assert all(got[k] <= lim[k] for k in lim), got
    assert got["served_gap_max"] < 1e-4      # float32 program vs reference
    ctl = kind.controls(run, st)["control"]
    assert any(ctl[k] > lim[k] for k in lim), ctl


def test_a_token_altered_where_produced_is_not_correct(monkeypatch):
    from repro.serve import engine

    orig = engine.Engine._sample

    def altered(self, logits, req):
        tok = orig(self, logits, req)
        return (tok + 1) % logits.shape[-1] if len(req.out) == 2 else tok

    monkeypatch.setattr(engine.Engine, "_sample", altered)
    out = R.run_cell(CELL, 5, 3.0, False, require_tpu=False,
                     sizes_override=TINY, params_override=LOAD)
    assert out["correct"] is False


def test_the_reference_is_teacher_forced_over_the_whole_sequence():
    """Changing a prompt token early moves the reference's logits at a
    served position late in the sequence: the check reads context, which a
    pairwise (token-local) check could not."""
    cfgm, sizes = R.config("granite-4.0-h-micro")
    sizes = dict(sizes, **TINY)
    import jax

    from bench.common import STREAM_WEIGHTS, jax_key
    _, params = cfgm.build(sizes, jax_key(3, STREAM_WEIGHTS))
    toks = np.arange(40, dtype=np.int32) * 7 % 512
    rows = np.array([39])
    a = cfgm.reference_logits(params, sizes, toks, rows)
    toks[2] += 1
    b = cfgm.reference_logits(params, sizes, toks, rows)
    assert np.abs(a - b).max() > 1e-4
    assert jax.numpy.isfinite(a).all()


def test_decode_work_counts_the_engine_s_state_and_the_model_s_weights():
    """``decode_work``'s state bytes are the engine's ``state_bytes`` times
    the slots (SSM and conv read and written, KV read), and its weight
    bytes are the bytes of the parameters the model holds."""
    import jax

    from repro.serve import Engine
    cfgm, sizes = R.config("granite-4.0-h-micro")
    sizes = dict(sizes, **TINY)
    model, params = cfgm.build(sizes, jax.random.PRNGKey(0))
    eng = Engine(model, params, cfgm.engine_config(sizes))
    slots = sizes["engine"]["max_slots"]
    work = cfgm.decode_work(sizes, slots)
    sb = eng.state_bytes
    assert work["ssm"][1] == 2 * slots * sb["ssm"]      # read and written
    assert work["conv"][1] == 2 * slots * sb["conv"]
    assert work["kv"][1] == slots * sb["kv"]            # read over max_len
    assert work["weights"][1] == sum(
        a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(params))


def _fake_run(modules, steps=()):
    import types

    from bench import trace as T
    cfgm, sizes = R.config("granite-4.0-h-micro")
    summary = T.Summary(window_ns=8e9, busy_ns=[6e9], ops={},
                        modules=modules, spans={}, idle_gaps=[])
    return types.SimpleNamespace(
        config=cfgm, sizes=sizes, summary=summary,
        peaks=R.peaks("TPU v5 lite"),
        state=types.SimpleNamespace(steps=list(steps)))


def test_the_decode_readers_find_the_decode_program_and_its_roofline():
    """At the published sizes a 20 ms decode call is ~75% of its roofline
    (12.35 GB a call at 819 GB/s is ~15.1 ms); the prefill program is not
    read as the decode; no decode in the trace reads nothing."""
    run = _fake_run({"jit_decode_step(7)": [40e6, 2],
                     "jit_prefill_chunk(3)": [30e6, 2]})
    assert R.metric("decode_step_ms.rag").read(run) == 20.0
    roof = R.metric("decode_roofline.rag").read(run)
    work = run.config.decode_work(run.sizes, 32)
    assert sum(b for _, b in work.values()) / 1e9 == \
        __import__("pytest").approx(12.35, abs=0.02)
    assert 70 < roof < 80
    assert R.metric("prefill_chunk_ms.itl").read(run) == 15.0
    assert R.metric("device_idle_share.itl").read(run) == 25.0
    assert R.metric("decode_step_ms.rag").read(_fake_run({})) is None
    assert R.metric("decode_roofline.rag").read(_fake_run({})) is None


def test_the_engine_tick_reads_the_cells_traced_steps_that_decoded():
    run = _fake_run({}, [dict(dt=0.1, traced=True, decoded=True),
                         dict(dt=0.3, traced=True, decoded=True),
                         dict(dt=5.0, traced=True, decoded=False),
                         dict(dt=9.0, traced=False, decoded=True)])
    assert abs(R.metric("engine_tick_ms.itl").read(run) - 200.0) < 1e-9
    assert R.metric("engine_tick_ms.itl").read(_fake_run({})) is None
