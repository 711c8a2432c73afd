"""Device time per call of the engine's pool-wide decode program
(``jit_decode_step``), from the trace's ``XLA Modules`` line: one token for
every slot of the pool, through 36 Mamba-2 layers (SSM and conv state read
and written for every slot) and 4 attention layers (KV rows over
``max_len``)."""
LAYER = "model step (models/lm.py, models/snn_cnn.py)"
SOURCE = "device_trace"
MOVES = "itl_p95_ms"
UNIT = "ms"
PROGRAM = "decode_step"


def per_call_ns(run):
    """Mean device ns of one decode call in the traced window, or None."""
    s = run.summary
    if s is None:
        return None
    calls = [(n, c) for name, (n, c) in s.modules.items() if PROGRAM in name]
    count = sum(c for _, c in calls)
    if not count:
        return None
    return sum(n for n, _ in calls) / count


def read(run):
    ns = per_call_ns(run)
    return None if ns is None else ns / 1e6
