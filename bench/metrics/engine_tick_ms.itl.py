"""Mean host time of an engine step that decoded, inside the traced window:
the harness's span around ``Engine.step()``, which ends when the decode's
logits are on the host and every live slot has its token."""
LAYER = "serve engine (serve/engine.py, serve/router.py)"
SOURCE = "host_clock"
MOVES = "itl_p95_ms"
UNIT = "ms"


def read(run):
    steps = [s for s in run.state.steps
             if s["traced"] and s["decoded"]]
    if not steps:
        return None
    return 1e3 * sum(s["dt"] for s in steps) / len(steps)
