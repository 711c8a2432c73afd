"""The idle-by-span reduction of ``bench/phases.py`` on a small synthetic
trace whose numbers are known by hand, and its runner at a size a test can
hold."""
import importlib.util

import pytest

from bench import run as R
from bench import trace as T

# window: bench.window [1000, 11000). Host spans, nested as the harness
# and the engine nest them: bench.step [1500, 10800) > serve.step
# [1600, 10700) > serve.decode [2000, 5000), serve.spike_stats (with a
# metadata suffix) [5000, 6000), serve.sample [6000, 10500).
# chip 0 ops: [1000, 3000), [4000, 4500), [7000, 8000): busy 3500, idle
#   [3000, 4000) decode 1000;
#   [4500, 7000) decode 500, spike_stats 1000, sample 1000;
#   [8000, 11000) sample 2500, serve.step 200, step 100, none 200.
# chip 1 ops: [1000, 11000), never idle. Over two chips every idle share
# halves. Decode program runs on chip 0: [2500, 4600) inside serve.decode,
# [9000, 9100) outside it; a prefill program is not counted.
SYNTH = """
planes {
  name: "/device:TPU:0"
  lines { name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 1 offset_ps: 4000000 duration_ps: 500000 }
    events { metadata_id: 2 offset_ps: 7000000 duration_ps: 1000000 }
  }
  lines { name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 2500000 duration_ps: 2100000 }
    events { metadata_id: 3 offset_ps: 9000000 duration_ps: 100000 }
    events { metadata_id: 4 offset_ps: 1000000 duration_ps: 2000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fusion.3 = f32[2] fusion(f32[2] %p)" } }
  event_metadata { key: 2 value { id: 2 name: "%my_kernel.1 = f32[2] custom-call(%fusion.3)" } }
  event_metadata { key: 3 value { id: 3 name: "jit_decode_step(123)" } }
  event_metadata { key: 4 value { id: 4 name: "jit_prefill_chunk(7)" } }
}
planes {
  name: "/device:TPU:1"
  lines { name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 10000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fusion.3 = f32[2] fusion(f32[2] %p)" } }
}
planes {
  name: "/host:CPU"
  lines { name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 1500000 duration_ps: 9300000 }
    ENGINE
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.step" } }
  event_metadata { key: 3 value { id: 3 name: "serve.step" } }
  event_metadata { key: 4 value { id: 4 name: "serve.decode" } }
  event_metadata { key: 5 value { id: 5 name: "serve.spike_stats#pools=1#" } }
  event_metadata { key: 6 value { id: 6 name: "serve.sample" } }
}
"""
ENGINE = """
    events { metadata_id: 3 offset_ps: 1600000 duration_ps: 9100000 }
    events { metadata_id: 4 offset_ps: 2000000 duration_ps: 3000000 }
    events { metadata_id: 5 offset_ps: 5000000 duration_ps: 1000000 }
    events { metadata_id: 6 offset_ps: 6000000 duration_ps: 4500000 }
"""


def profile(engine_spans=True):
    from jax.profiler import ProfileData

    text = SYNTH.replace("ENGINE", ENGINE if engine_spans else "")
    return ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(text))


def phases():
    spec = importlib.util.spec_from_file_location("bench_phases",
                                                  R.BENCH / "phases.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def red():
    return phases().reduce(profile())


def test_idle_time_is_split_by_the_innermost_span(red):
    assert red["idle_by_span"] == pytest.approx(
        {"serve.decode": 750, "serve.spike_stats": 500, "serve.sample": 1750,
         "serve.step": 100, "step": 50, "none": 100})


def test_idle_by_span_sums_to_window_less_busy(red):
    busy = sum(red["busy_ns"]) / len(red["busy_ns"])
    assert sum(red["idle_by_span"].values()) == pytest.approx(
        red["window_ns"] - busy)
    s = phases().shares(red)
    assert sum(s["idle_share_by_span"].values()) == pytest.approx(
        s["idle_share"])
    assert s["idle_share"] == pytest.approx(32.5)
    assert s["idle_share_by_span"]["serve.sample"] == pytest.approx(17.5)
    assert s["idle_share_by_span"]["serve.spike_stats"] == pytest.approx(5.0)
    # the harness's step and no span at all: 50 + 100 ns of 10,000
    assert s["idle_outside_engine_share"] == pytest.approx(1.5)


def test_gaps_carry_the_engine_phase_they_fall_in(red):
    assert red["gaps"] == [("serve.sample", 3000),
                           ("serve.spike_stats", 2500),
                           ("serve.decode", 1000)]


def test_decode_runs_are_counted_inside_the_decode_span(red):
    assert (red["decode_runs_in_span"], red["decode_runs"]) == (1, 2)
    assert phases().shares(red)["decode_in_span_share"] == \
        pytest.approx(50.0)


@pytest.mark.parametrize("engine_spans", [True, False])
def test_busy_time_agrees_with_the_benchmark_reduction(engine_spans):
    """The program's spans change no number the benchmark's own
    reduction gives, and this reduction sees the same busy time."""
    mine = phases().reduce(profile(engine_spans))
    with_spans, without = T.reduce(profile(True)), T.reduce(profile(False))
    assert with_spans.busy_ns == without.busy_ns == mine["busy_ns"] \
        == [3500, 10000]
    assert with_spans.ops == without.ops
    assert with_spans.modules == without.modules
    assert with_spans.window_ns == mine["window_ns"] == 10000
    if not engine_spans:              # a trace of an engine without spans
        assert mine["idle_by_span"] == pytest.approx(
            {"step": 3150, "none": 100})
        assert mine["decode_runs_in_span"] == 0


def test_metadata_suffix_is_stripped_and_harness_names_kept():
    P = phases()
    assert P.span_label("serve.prefill#uid=3,chunk=0#") == "serve.prefill"
    assert P.span_label("bench.step") == "step"
    assert P.split_idle([(0, 10)], []) == {"none": 10}


def test_runner_reports_both_windows_on_a_tiny_engine():
    from bench.tests.test_bench_serve_checks import CELL, LOAD, TINY

    untraced, traced = phases().phases(
        CELL, 2**31 + 23, 2.0, require_tpu=False, sizes_override=TINY,
        params_override=dict(LOAD, drain_s=20))
    assert untraced["traced"] is False and traced["traced"] is True
    assert untraced["step_ms_mean"] > 0
    slow = untraced["slowest_step"]
    assert slow["syncs"] >= slow["live"] + 1
    assert "decode" in slow["phases_ms"]
    assert untraced["queue_wait_ms"][1] >= untraced["queue_wait_ms"][0] >= 0
    # the CPU trace has no device plane: the host readings only
    assert traced["steps_decoded"] > 0
    assert traced["host_syncs_per_tick"] >= traced["live_per_tick"] + 1
    assert set(traced["syncs_minus_live"]) <= {1, 2, 3}
