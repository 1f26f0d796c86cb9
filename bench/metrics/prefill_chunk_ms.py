"""Device time per execution of the chunked-prefill program, in the traced
window. Moves ttft_p50_ms."""


def read(w):
    n, secs = w.program_time("serve_prefill_chunk")
    if not n:
        return None
    return 1e3 * secs / n
