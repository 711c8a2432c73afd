"""The trace reduction on a small synthetic trace whose numbers are known
by hand."""
import pytest

from bench import trace as T

# window: host span bench.window from 1000 ns for 10000 ns.
# chip 0 ops: [1000, 3000) fusion, [2000, 4000) my_kernel (overlaps),
#             [1000, 7000) a while loop holding ops (busy, not an op),
#             [6000, 7000) my_kernel, [10500, 12000) fusion (clipped to
#             [10500, 11000)); busy = 6000 + 500 = 6500 ns.
# chip 1 ops: [1000, 2000) fusion; busy 1000 ns.
# idle gap on chip 0: [7000, 10500) under span bench.sample.
SYNTH = """
planes {
  name: "/device:TPU:0"
  lines { name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 2000000 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 6000000 duration_ps: 1000000 }
    events { metadata_id: 1 offset_ps: 10500000 duration_ps: 1500000 }
    events { metadata_id: 1 offset_ps: 20000000 duration_ps: 1000000 }
    events { metadata_id: 4 offset_ps: 1000000 duration_ps: 6000000 }
  }
  lines { name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 1000000 duration_ps: 6000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fusion.3 = f32[2] fusion(f32[2] %p)" } }
  event_metadata { key: 2 value { id: 2 name: "%my_kernel.1 = f32[2] custom-call(%fusion.3)" } }
  event_metadata { key: 3 value { id: 3 name: "jit_decode_step(123)" } }
  event_metadata { key: 4 value { id: 4 name: "%while.2 = (s32[]) while(%t)" } }
}
planes {
  name: "/device:TPU:1"
  lines { name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fusion.3 = f32[2] fusion(f32[2] %p)" } }
}
planes {
  name: "/host:CPU"
  lines { name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 3500000 duration_ps: 3000000 }
    events { metadata_id: 3 offset_ps: 7000000 duration_ps: 4000000 }
    events { metadata_id: 4 offset_ps: 1000000 duration_ps: 500000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.step" } }
  event_metadata { key: 3 value { id: 3 name: "bench.sample" } }
  event_metadata { key: 4 value { id: 4 name: "unrelated" } }
}
"""


@pytest.fixture(scope="module")
def summary():
    from jax.profiler import ProfileData

    raw = ProfileData.text_proto_to_serialized_xspace(SYNTH)
    return T.reduce(ProfileData.from_serialized_xspace(raw))


def test_busy_is_the_union_of_op_intervals_per_chip(summary):
    assert summary.window_ns == 10000
    assert summary.busy_ns == [6500, 1000]
    assert summary.busy_s == pytest.approx((6500 + 1000) / 2 / 1e9)
    assert summary.window_s == pytest.approx(1e-5)


def test_kernel_time_by_name_is_clipped_to_the_window(summary):
    assert summary.ops["%my_kernel.1"] == [3000, 2]
    # chip 0: 2000 + 500 (clipped), chip 1: 1000; the op past the window
    # is dropped, and so is the while loop that holds the others
    assert summary.ops["%fusion.3"] == [3500, 3]
    assert set(summary.ops) == {"%my_kernel.1", "%fusion.3"}
    assert summary.op_ns("%my_kernel") == (3000, 2)
    assert summary.op_ns("kernel") == (0, 0)
    assert summary.modules["jit_decode_step(123)"] == [6000, 1]


def test_idle_gaps_are_labelled_by_the_harness_span(summary):
    assert summary.idle_gaps == [("sample", 3500)]
    assert set(summary.spans) == {"bench.window", "bench.step",
                                  "bench.sample"}


def test_breakdown_lists_ops_and_gaps_in_seconds(summary):
    b = T.breakdown(summary)
    assert b["device_ops"][0] == ["%fusion.3", pytest.approx(3500 / 2 / 1e9)]
    assert b["idle_gaps"][0] == ["sample", pytest.approx(3.5e-6)]


def test_a_trace_without_the_window_span_is_refused():
    from jax.profiler import ProfileData

    raw = ProfileData.text_proto_to_serialized_xspace(
        SYNTH.replace("bench.window", "other"))
    with pytest.raises(ValueError):
        T.reduce(ProfileData.from_serialized_xspace(raw))
