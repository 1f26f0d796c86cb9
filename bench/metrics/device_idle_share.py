"""Share of the traced window in which no operation ran on the device.
Moves tpot_p50_ms."""


def read(w):
    if w.trace is None or w.trace_busy_s <= 0:
        return None
    return 100.0 * (1.0 - w.trace_busy_s / w.trace_window_s)
