"""Share of the decode-block program's device time that the work decode
needs would take at the chip's peaks, in the traced window. The needed
work of each block is the sum of the architecture module's
``micro_step_need`` over the block's micro-steps that produced a token (for
a dense model: the weights once, the KV of each generated token's true
context and the operations, at the configuration's dtype). Moves
output_tokens_per_s."""


def read(w):
    n, secs = w.program_time("serve_decode_block")
    if not n:
        return None
    need = w.decode_need
    if need is None or need["blocks"] != n:
        raise ValueError(f"the trace holds {n} decode blocks, the work was "
                         f"counted for {need and need['blocks']}")
    least = max(need["bytes"] / w.peaks["hbm_bytes_per_s"],
                need["flops"] / w.peaks["bf16_flops_per_s"])
    return 100.0 * least / secs
