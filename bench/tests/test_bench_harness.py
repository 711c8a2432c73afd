"""The harness's data path: traffic from the seed, cells, configurations
and metrics found by file name, no TPU, no device at import."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from bench import run as R

ENV = dict(os.environ, JAX_PLATFORMS="cpu")


def serve():
    return R.traffic("open_loop_serve")


def test_traffic_is_deterministic_in_the_seed():
    params = R.workload("qwen3-qks.chat")["params"]
    seed = 2**31 + 12345
    a = serve().schedule(params, seed, 30.0, 151936)
    b = serve().schedule(params, seed, 30.0, 151936)
    c = serve().schedule(params, seed + 1, 30.0, 151936)
    def key(reqs):
        return [(r.due, r.max_new, r.prompt.tobytes()) for r in reqs]

    def work(reqs):
        return [(r.due, r.max_new, len(r.prompt)) for r in reqs]

    assert key(a) == key(b)
    assert key(a) != key(c)
    # every seed brings the same work at the same times; only what the
    # prompts say differs
    assert work(a) == work(c)


def test_lognormal_median_and_clipping_hold():
    law = {"median": 256, "sigma": 0.8, "min": 32, "max": 2048}
    x = serve().lognormal_lengths(2001, law)
    assert np.median(x) == 256
    assert x.min() >= 32 and x.max() <= 2048
    assert (x == 32).any() and (x == 2048).any()       # both tails clip
    wide = serve().lognormal_lengths(2001, dict(law, min=1, max=10**9))
    assert np.exp(np.log(wide).std()) == pytest.approx(np.exp(0.8), rel=0.02)


def test_arrivals_keep_the_rate():
    params = dict(R.workload("qwen3-qks.chat")["params"], rate_per_s=5.0)
    reqs = serve().schedule(params, 7, 60.0, 1000)
    assert len(reqs) == pytest.approx(300, abs=3)
    assert all(0 <= r.due < 60.0 for r in reqs)
    assert [r.due for r in reqs] == sorted(r.due for r in reqs)


def test_peaks_refuse_an_unknown_device_kind():
    assert R.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        R.peaks("TPU v9 imaginary")


def test_cells_configs_and_metrics_are_found_by_their_files(tmp_path):
    """Adding a configuration, a cell and a per-layer metric is adding
    files: nothing else is edited."""
    name = "pytest-added.cell"
    files = {
        R.BENCH / "workloads" / f"{name}.json": json.dumps(
            {"config": "pytest-added-config", "traffic": "batch_infer",
             "chips": 1, "why": "test", "params": {"limits": {}}}),
        R.BENCH / "configs" / "pytest-added-config.json":
            json.dumps({"reduced": [], "n": 3}),
        R.BENCH / "configs" / "pytest-added-config.py": "WIDTH = 7\n",
        R.BENCH / "metrics" / "pytest_added.metric.py":
            "LAYER = 'device'\nSOURCE = 'device_trace'\n"
            "MOVES = 'setup_s'\nUNIT = '%'\n"
            "def read(run):\n    return 42.0\n",
    }
    try:
        for path, text in files.items():
            path.write_text(text)
        wl = R.workload(name)
        mod, sizes = R.config(wl["config"])
        assert (mod.WIDTH, sizes["n"]) == (7, 3)
        assert R.traffic(wl["traffic"]).end_to_end
        assert R.metric("pytest_added.metric").read(None) == 42.0
        spec = {"per_layer": [{"name": "pytest_added.metric",
                               "workloads": [name]}]}
        assert R.cell_metrics(spec, name, "per_layer")[0]["name"] == \
            "pytest_added.metric"
        assert R.cell_metrics(spec, "other", "per_layer") == []
    finally:
        for path in files:
            path.unlink(missing_ok=True)


def test_run_exits_nonzero_and_prints_no_result_without_a_tpu():
    p = subprocess.run(
        [sys.executable, str(R.BENCH / "run.py"), "--workload",
         "vgg11-cifar.deploy", "--seed", "1", "--seconds", "1"],
        env=ENV, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "correct" not in p.stdout
    assert "needs a TPU" in p.stderr


def test_no_module_touches_a_device_at_import():
    code = """
import sys
sys.path.insert(0, {root!r})
from bench import run as R, trace, common, control
spec = R.benchmark_spec()
for c in spec["configs"]:
    R.config(c["name"])
for w in spec["workloads"]:
    R.traffic(R.workload(w["name"])["traffic"])
for m in spec["per_layer"]:
    R.metric(m["name"])
from jax._src import xla_bridge
assert not xla_bridge._backends, xla_bridge._backends
print("clean")
""".format(root=str(R.ROOT))
    p = subprocess.run([sys.executable, "-c", code], env=ENV,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr
    assert "clean" in p.stdout


def test_set_up_ends_frozen_and_the_window_records_its_stalls():
    import gc
    import time

    _, run = R.prepare("vgg11-cifar.deploy", 3, 1.0, False,
                       require_tpu=False)
    R.settle()
    try:
        assert gc.get_freeze_count() > 0
        t0 = time.perf_counter()
        with run.span("call"):
            gc.collect()
    finally:
        gc.unfreeze()
    assert gc.get_freeze_count() == 0
    s = run.stalls_since(t0)
    assert s["gc_pauses"] >= 1
    assert 0 < s["gc_max_ms"] <= s["gc_total_ms"]
    assert s["span_max_ms"] >= s["gc_max_ms"]
    assert run.stalls_since(time.perf_counter())["gc_pauses"] == 0


def test_sweep_row_counts_the_backlog_and_both_halves():
    import importlib.util

    spec = importlib.util.spec_from_file_location("bench_sweep",
                                                  R.BENCH / "sweep.py")
    sweep = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(sweep)
    Req = serve().Req
    reqs = [Req(due=d, prompt=np.zeros(4, np.int32), max_new=2, uid=i,
                first=f)
            for i, (d, f) in enumerate([(0.0, 0.1), (1.0, 1.3), (6.0, 6.5),
                                        (8.0, 11.0), (9.0, float("nan"))])]

    class St:
        pass

    st = St()
    st.reqs, st.drain_end = reqs, 12.0
    st.steps = [{"dt": 0.04}, {"dt": 0.06}]
    r = sweep.row(2.0, st, 10.0)
    assert r["due"] == 5
    assert r["backlog_at_close"] == 2           # first after 10 s, and none
    assert r["ttft_p50_first_half_ms"] == pytest.approx(200.0)
    assert r["ttft_p50_second_half_ms"] == pytest.approx(3000.0)
    assert r["ttft_p50_ms"] == pytest.approx(500.0)
    assert r["step_mean_ms"] == pytest.approx(50.0)
