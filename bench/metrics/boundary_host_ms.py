"""Median host time per decode boundary: the boundary's wall time less the
spans in which the host waits on the device (the chunk's and the decode
block's device-to-host copies), from the engine's span table over the whole
window. A median, so the boundaries that the profiler's start and stop
stall drop out. Nothing where the engine keeps no boundary table. Moves
tpot_p50_ms."""


def read(w):
    b = w.stats.get("boundary")
    return b["host_p50_ms"] if b and b["n"] else None
