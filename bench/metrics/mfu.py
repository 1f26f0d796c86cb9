"""Model operations of every token processed in the window (each prompt,
and each decode step at its true context; the unembedding only where
logits are needed) over the window and the chip's bf16 peak. Moves
output_tokens_per_s."""

from bench import work


def read(w):
    flops = sum(work.prompt_flops(w.cfg, p) + work.decode_flops(w.cfg, p, n)
                for p, n in w.completed)
    return 100.0 * flops / (w.window_s * w.peaks["bf16_flops_per_s"])
