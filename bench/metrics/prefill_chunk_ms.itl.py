"""Device time per call of the engine's prefill-chunk program
(``jit_prefill_chunk``), from the trace's ``XLA Modules`` line. A chunk
runs inside the engine step that then decodes, so the steps that carry
one are the long gaps between tokens."""
LAYER = "model step (models/lm.py, models/snn_cnn.py)"
SOURCE = "device_trace"
MOVES = "itl_p95_ms"
UNIT = "ms"
PROGRAM = "prefill_chunk"


def read(run):
    s = run.summary
    calls = [(n, c) for name, (n, c) in s.modules.items() if PROGRAM in name]
    count = sum(c for _, c in calls)
    if not count:
        return None
    return sum(n for n, _ in calls) / count / 1e6
