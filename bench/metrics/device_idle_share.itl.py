"""Share of the traced window in which no operation ran on the device,
averaged over the cell's chips."""
LAYER = "device"
SOURCE = "device_trace"
MOVES = "itl_p95_ms"
UNIT = "%"


def read(run):
    s = run.summary
    if s is None or s.window_ns <= 0:
        return None
    return 100.0 * (1.0 - s.busy_s / s.window_s)
