"""Roofline share of the fused-PE kernels of deployed inference: the least
time the chip could take for the configuration's count of every fused-PE
call (``ops.fused_pe_layer`` of the binary-input convs) in the deployed
forwards the trace holds, over the summed device time of the fused-PE
kernel events (named in ``KERNELS``)."""
from bench.common import least_time_s

LAYER = "kernels (kernels/* via repro.ops)"
SOURCE = "device_trace"
MOVES = "deploy_images_per_s"
UNIT = "%"
PROGRAM = "jit_classify_batch"
KERNELS = r"%fused_pe_pallas\b"


def read(run):
    s = run.summary
    calls = sum(c for name, (_, c) in s.modules.items() if PROGRAM in name)
    ns, n = s.op_ns(KERNELS)
    if not calls or not n or ns <= 0:
        return None
    work = run.config.deploy_event_calls(run.sizes, run.params["batch"])
    return 100.0 * calls * least_time_s(work, run.peaks) / (ns / 1e9)
