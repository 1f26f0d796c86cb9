"""Share of decode slot-steps that produced a token: decode tokens over
(macro-steps x block size x slots), from the engine's counters over the
whole window. Moves output_tokens_per_s."""


def read(w):
    steps = w.stats["macro_steps"] * w.block_size * w.slots
    if not steps:
        return None
    return 100.0 * w.stats["decode_tokens"] / steps
