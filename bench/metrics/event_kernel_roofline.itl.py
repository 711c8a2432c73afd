"""Roofline share of the event kernels in the serving cell's traced
window: the least time the chip could take for the work of every
event-kernel call the traced engine steps made (the configuration's count
at the op's entry in ``repro.ops``: ``dense_lif`` for Q and for the masked
K, ``matmul`` with Wo, per layer, for each pool-wide decode and each
prefill chunk) over the summed device time of those kernels' events.

The kernels' events in the trace are the Pallas custom calls named in
``KERNELS`` (the kernels' function names, which the ops keep)."""
from bench.common import least_time_s

LAYER = "kernels (kernels/* via repro.ops)"
SOURCE = "device_trace"
MOVES = "itl_p95_ms"
UNIT = "%"
KERNELS = r"%(fused_pe_pallas|spike_matmul_pallas)\b"


def read(run):
    steps = [s for s in run.state.steps if s["traced"]]
    rows = run.sizes["engine"]["max_slots"]
    calls = []
    for s in steps:
        if s["decoded"]:
            calls += run.config.event_kernel_calls(run.sizes, rows)
        for r in s["chunk_rows"]:
            calls += run.config.event_kernel_calls(run.sizes, r)
    ns, n = run.summary.op_ns(KERNELS)
    if not calls or not n or ns <= 0:
        return None
    return 100.0 * least_time_s(calls, run.peaks) / (ns / 1e9)
