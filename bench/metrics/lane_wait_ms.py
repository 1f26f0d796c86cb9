"""Median wait of a completed request between its admission to a slot and
the start of its first prefill chunk: the wait for the chunk lane, from the
engine's per-request stamps over the whole window. Nothing where the engine
stamps no first chunk. Moves ttft_p50_ms."""
import statistics


def read(w):
    waits = [m["lane_wait_ms"] for m in w.stats.get("per_request", ())
             if "lane_wait_ms" in m]
    return statistics.median(waits) if waits else None
