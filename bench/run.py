#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell is ``bench/workloads/<cell>.json``. It names a configuration
(``bench/configs/<config>.json`` with ``<config>.py`` beside it) and a
traffic kind (``bench/traffic/<kind>.py``). ``BENCHMARK.json`` says which
end-to-end metrics the cell reports (``--trace 0``) and which per-layer
metrics (``--trace 1``, each read by ``bench/metrics/<metric>.py`` from the
profiler trace of a part of the window and the harness's spans).

The run builds everything from ``--seed``, warms up every shape its traffic
uses (set-up, reported as ``setup_s``), measures for ``--seconds``, checks
what the timed path produced against the configuration's plain reference,
and prints each compared number beside its limit on stderr and, under
``checks``, in the result line. Without a TPU, or with fewer chips than the
cell asks for, it exits 1 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Optional  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import trace as tracemod  # noqa: E402


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# ------------------------------------------------------------ finding files
def load_module(path: Path, name: str):
    if not path.is_file():
        raise FileNotFoundError(path)
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def benchmark_spec() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def workload(name: str) -> dict:
    wl = load_json(BENCH / "workloads" / f"{name}.json")
    wl["name"] = name
    return wl


def config(name: str) -> tuple[Any, dict]:
    """The configuration's module and its file of sizes, as run."""
    sizes = load_json(BENCH / "configs" / f"{name}.json")
    mod = load_module(BENCH / "configs" / f"{name}.py",
                      "bench_config_" + re.sub(r"\W", "_", name))
    return mod, sizes


def traffic(kind: str):
    return load_module(BENCH / "traffic" / f"{kind}.py",
                       f"bench_traffic_{kind}")


def metric(name: str):
    return load_module(BENCH / "metrics" / f"{name}.py",
                       "bench_metric_" + re.sub(r"\W", "_", name))


def peaks(device_kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"bench/peaks.json has {sorted(table)}")
    return table[device_kind]


def cell_metrics(spec: dict, cell: str, section: str) -> list:
    """The entries of ``spec[section]`` that this cell reports."""
    return [m for m in spec[section]
            if cell in m.get("workloads", [cell])]


# ------------------------------------------------------------------ the run
@dataclasses.dataclass
class Check:
    """One number compared with its limit; ``ok`` is value <= limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(math.isfinite(self.value) and self.value <= self.limit)


@dataclasses.dataclass
class Run:
    """What a traffic kind and the metric readers share during one run."""
    workload: dict
    config: Any                       # the configuration's module
    sizes: dict                       # its file of sizes
    seed: int
    seconds: float
    trace: bool
    peaks: dict
    devices: list
    spans: list = dataclasses.field(default_factory=list)
    summary: Optional[tracemod.Summary] = None
    state: Any = None                 # what the traffic kind's setup built
    _tracing: bool = False
    _trace_dir: Optional[str] = None
    _window_ann: Any = None
    _compiles: list = dataclasses.field(default_factory=list)
    _gc: list = dataclasses.field(default_factory=list)
    _longest: tuple = (0.0, 0.0, 0.0)  # (seconds, CPU seconds, start)

    @property
    def params(self) -> dict:
        return self.workload["params"]

    def note(self, **kv) -> None:
        """An earlier line of the run's record (stdout, not the result)."""
        print("bench: " + json.dumps(kv), flush=True)

    @contextlib.contextmanager
    def span(self, name: str):
        """Host span of the harness; inside the traced window it is also a
        ``bench.<name>`` annotation in the profiler's trace."""
        ann = None
        if self._tracing:
            import jax

            ann = jax.profiler.TraceAnnotation(f"{tracemod.SPAN_PREFIX}{name}")
            ann.__enter__()
        t0, c0 = time.perf_counter(), time.process_time()
        try:
            yield
        finally:
            t1 = time.perf_counter()
            if ann is not None:
                ann.__exit__(None, None, None)
            self.spans.append((name, t0, t1, self._tracing))
            if t1 - t0 > self._longest[0]:
                self._longest = (t1 - t0, time.process_time() - c0, t0)

    def trace_plan(self) -> tuple[float, float]:
        """Where in the window the trace runs, in seconds from its start:
        a quarter in, for half the window and at most 8 seconds."""
        return 0.25 * self.seconds, min(8.0, 0.5 * self.seconds)

    def trace_tick(self, t_rel: float) -> None:
        """Start or stop the profiler at the planned points of the window;
        a traffic loop calls this between units of work."""
        if not self.trace:
            return
        start, length = self.trace_plan()
        if not self._tracing and self._trace_dir is None and t_rel >= start:
            import jax

            self._trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
            jax.profiler.start_trace(self._trace_dir)
            self._window_ann = jax.profiler.TraceAnnotation(
                tracemod.WINDOW_SPAN)
            self._window_ann.__enter__()
            self._tracing = True
        elif self._tracing and t_rel >= start + length:
            self.trace_stop()

    def trace_stop(self) -> None:
        if not self._tracing:
            return
        import jax

        self._window_ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self._tracing = False

    def trace_reduce(self) -> None:
        """Read the trace once the window is over, and delete it."""
        self.trace_stop()
        if self._trace_dir is None or self.summary is not None:
            return
        try:
            self.summary = tracemod.reduce(tracemod.load(self._trace_dir))
        finally:
            shutil.rmtree(self._trace_dir, ignore_errors=True)

    def compiles_since(self, t0: float) -> list:
        """Names of the programs compiled or loaded since ``t0``."""
        return [name for t, name in self._compiles if t >= t0]

    def stalls_since(self, t0: float) -> dict:
        """What could have held the host since ``t0``: the garbage
        collector's pauses, the harness spans over 100 ms, and the longest
        span with the CPU time the process spent in it (near its length:
        the host computed; near 0: it waited)."""
        gcs = [d for t, d, _ in self._gc if t >= t0]
        spans = [b - a for _, a, b, _ in self.spans if a >= t0]
        longest, cpu, at = self._longest
        return {"gc_pauses": len(gcs),
                "gc_max_ms": 1e3 * max(gcs, default=0.0),
                "gc_total_ms": 1e3 * sum(gcs),
                "spans_over_100ms": sum(d > 0.1 for d in spans),
                "span_max_ms": 1e3 * max(spans, default=0.0),
                "span_max_cpu_ms": 1e3 * cpu if at >= t0 else 0.0,
                "span_max_at_s": at - t0 if at >= t0 else 0.0}


def _watch_compiles(run: Run) -> None:
    import jax

    seen = run._compiles              # not the run: the listener outlives it

    def on_event(event: str, duration: float, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            seen.append((time.perf_counter(), kw.get("fun_name")))

    jax.monitoring.register_event_duration_secs_listener(on_event)


def _watch_gc(run: Run) -> None:
    pauses = run._gc                  # (start, seconds, generation)
    started = []

    def on_gc(phase: str, info: dict) -> None:
        if phase == "start":
            started.append(time.perf_counter())
        elif started:
            t0 = started.pop()
            pauses.append((t0, time.perf_counter() - t0, info["generation"]))

    gc.callbacks.append(on_gc)


def device_info(devices: list, trace_summary=None) -> dict:
    out = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices)}
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    out["memory_peak_bytes"] = peak
    if trace_summary is not None:
        out["busy_s"] = trace_summary.busy_s
        out["window_s"] = trace_summary.window_s
    return out


def find_devices(chips: int, require_tpu: bool = True) -> list:
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def prepare(cell: str, seed: int, seconds: float, trace: bool, *,
            require_tpu: bool = True, sizes_override: Optional[dict] = None,
            params_override: Optional[dict] = None) -> tuple:
    """(traffic kind module, Run) for one run of one cell. The overrides
    let the CPU tests drive a run at a size a test can hold."""
    wl = workload(cell)
    if params_override:
        wl["params"] = dict(wl["params"], **params_override)
    cfg_mod, sizes = config(wl["config"])
    if sizes_override:
        sizes = dict(sizes, **sizes_override)
    kind = traffic(wl["traffic"])
    devices = find_devices(int(wl["chips"]), require_tpu)
    if require_tpu:
        from repro.launch.compile_cache import use_compile_cache

        use_compile_cache()
        import jax

        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    run = Run(workload=wl, config=cfg_mod, sizes=sizes, seed=int(seed),
              seconds=float(seconds), trace=bool(trace),
              peaks=peaks(devices[0].device_kind) if require_tpu
              else {"bf16_flops": 1.0, "hbm_bytes_per_s": 1.0},
              devices=devices)
    _watch_compiles(run)
    _watch_gc(run)
    return kind, run


def settle() -> None:
    """End of set-up: collect what set-up left behind, then freeze every
    surviving object, so that a collection in the window scans only what
    the window made and not the whole heap that JAX and set-up built.
    ``gc.unfreeze()`` after the window gives them back to the collector."""
    gc.collect()
    gc.freeze()


def run_cell(cell: str, seed: int, seconds: float, trace: bool,
             **overrides) -> dict:
    """One run of one cell; returns the result line as a dict."""
    spec = benchmark_spec()
    kind, run = prepare(cell, seed, seconds, trace, **overrides)
    state = run.state = kind.setup(run)
    settle()
    setup_s = time.perf_counter() - T_START
    t_window = time.perf_counter()
    kind.measure(run, state)
    gc.unfreeze()
    run.note(**run.stalls_since(t_window))
    run.trace_reduce()
    compiles = run.compiles_since(t_window)
    dev = device_info(run.devices, run.summary)
    attempted, failed = kind.attempts(run, state)
    e2e = dict(kind.end_to_end(run, state), setup_s=setup_s)
    limits = run.params["limits"]
    checks = [Check(k, v, limits[k])
              for k, v in kind.readings(run, state).items()]
    run.note(compiles_in_window=compiles, attempted=attempted, failed=failed)
    correct = bool(checks) and all(c.ok for c in checks) and failed == 0
    metrics = {}
    if trace:
        for m in cell_metrics(spec, cell, "per_layer"):
            v = metric(m["name"]).read(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in cell_metrics(spec, cell, "end_to_end"):
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": dev}
    if run.summary is not None:
        out["breakdown"] = tracemod.breakdown(run.summary)
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in checks}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    for name, c in out["checks"].items():
        ok = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {name} = {c['value']!r} limit {c['limit']!r} {ok}",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
