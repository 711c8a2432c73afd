"""Roofline share of the hybrid decode program (``jit_decode_step``): the
least time the chip could take for one pool-wide decode call, the larger
of its FLOPs over the bf16 peak and its bytes over the HBM bandwidth (the
configuration's ``decode_work`` for the pool's ``max_slots``: weights,
SSM and conv state read and written for every slot, KV rows read over
``max_len``), over the device time of one call in the trace."""
from bench.common import least_time_s
from bench.run import metric

LAYER = "model step (models/lm.py, models/snn_cnn.py)"
SOURCE = "device_trace"
MOVES = "itl_p95_ms"
UNIT = "%"


def read(run):
    ns = metric("decode_step_ms.rag").per_call_ns(run)
    work = getattr(run.config, "decode_work", None)
    if ns is None or work is None:
        return None
    parts = work(run.sizes, run.sizes["engine"]["max_slots"]).values()
    call = (sum(f for f, _ in parts), sum(b for _, b in parts))
    return 100.0 * least_time_s([call], run.peaks) / (ns / 1e9)
