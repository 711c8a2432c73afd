"""Model FLOP utilisation of the engine steps that decoded, inside the
traced window: the configuration's model FLOPs of every token those steps
processed (one per live slot decoded, plus the valid prompt tokens of the
prefill chunks they ran) over their summed host spans times the chip's
bf16 peak. Idle slots of the pool-wide decode and chunk padding do not
count."""
LAYER = "model step (models/lm.py, models/snn_cnn.py)"
SOURCE = "host_clock"
MOVES = "itl_p95_ms"
UNIT = "%"


def read(run):
    steps = [s for s in run.state.steps if s["traced"] and s["decoded"]]
    span = sum(s["dt"] for s in steps)
    if not steps or span <= 0:
        return None
    tokens = sum(s["decode_tokens"] + s["prompt_tokens"] for s in steps)
    flops = tokens * run.config.flops_per_token(run.sizes)
    return 100.0 * flops / (span * run.peaks["bf16_flops"])
