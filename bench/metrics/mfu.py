"""Model operations of every token processed in the window (each prompt,
and each decode step at its true context; the unembedding only where
logits are needed; the architecture module's counts) over the window and
the chip's bf16 peak. Moves output_tokens_per_s."""


def read(w):
    flops = sum(w.arch.prompt_flops(w.cfg, p)
                + w.arch.decode_flops(w.cfg, p, n) for p, n in w.completed)
    return 100.0 * flops / (w.window_s * w.peaks["bf16_flops_per_s"])
