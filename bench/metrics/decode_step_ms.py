"""Device time of the decode-block program per micro-step, in the traced
window: its executions' device time over (executions x block size).
Moves tpot_p50_ms."""


def read(w):
    n, secs = w.program_time("serve_decode_block")
    if not n:
        return None
    return 1e3 * secs / (n * w.block_size)
