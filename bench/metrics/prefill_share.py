"""Share of the window the engine spent in the chunked-prefill lane: its
own host-clock sum of chunk dispatches, each ending in a device-to-host
copy, over the window. Moves ttft_p50_ms."""


def read(w):
    return 100.0 * w.stats["prefill_time_ms"] * 1e-3 / w.window_s
