"""The serving cell's comparison at a size a test can hold: a sound run is
correct, the control in the program's place fails the limit, and a token
altered where the engine produces it makes ``correct`` false."""
from bench import run as R

CELL = "qwen3-qks.chat"
TINY = dict(num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, intermediate_size=128,
            vocab_size=512, engine={"policy": "fused_packed", "max_slots": 4,
                                    "max_len": 512, "prefill_chunk": 128})
LOAD = {"rate_per_s": 2.0, "check_tokens": 60,
        "prompt_len": {"median": 60, "sigma": 0.8, "min": 8, "max": 300},
        "output_len": {"median": 8, "sigma": 0.5, "min": 2, "max": 20}}


def tiny_run(seed=2**31 + 11):
    kind, run = R.prepare(CELL, seed, 3.0, False, require_tpu=False,
                          sizes_override=TINY, params_override=LOAD)
    st = run.state = kind.setup(run)
    kind.measure(run, st)
    return kind, run, st


def test_sound_run_is_correct_and_the_control_is_not():
    kind, run, st = tiny_run()
    lim = run.params["limits"]
    got = kind.readings(run, st)
    assert len(st.pairs) >= 30
    assert all(got[k] <= lim[k] for k in lim), got
    ctl = kind.controls(run, st)["control"]
    assert any(ctl[k] > lim[k] for k in lim), ctl


def test_a_token_altered_where_produced_is_not_correct(monkeypatch):
    from repro.serve import engine

    orig = engine.Engine._sample

    def altered(self, logits, req):
        tok = orig(self, logits, req)
        return (tok + 1) % logits.shape[-1] if len(req.out) == 3 else tok

    monkeypatch.setattr(engine.Engine, "_sample", altered)
    out = R.run_cell(CELL, 5, 3.0, False, require_tpu=False,
                     sizes_override=TINY, params_override=LOAD)
    assert out["correct"] is False


def test_sweep_runs_each_rate_on_a_fresh_engine():
    import importlib.util

    spec = importlib.util.spec_from_file_location("bench_sweep",
                                                  R.BENCH / "sweep.py")
    sweep = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(sweep)
    rows = sweep.sweep(CELL, 2**31 + 17, 2.0, [1.0, 4.0], 20.0,
                       require_tpu=False, sizes_override=TINY,
                       params_override=LOAD)
    assert [r["rate_per_s"] for r in rows] == [1.0, 4.0]
    assert [r["due"] for r in rows] == [2, 8]
    for r in rows:
        assert r["ttft_p95_ms"] >= r["ttft_p50_ms"] > 0
