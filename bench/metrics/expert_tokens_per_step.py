"""Tokens each held expert's weights serve per decode micro-step: the
window's decode token-expert picks over (layers x held experts x decode
micro-steps that produced a token). From the engine's counters of a model
that holds experts; nothing elsewhere. More tokens per read of an
expert's weights moves output_tokens_per_s."""
import numpy as np


def read(w):
    moe = w.stats.get("moe")
    if not moe or not moe["decode_micro_steps"]:
        return None
    picks = np.asarray(moe["decode_picks"], dtype=np.float64)
    return float(picks.sum() / (picks.size * moe["decode_micro_steps"]))
