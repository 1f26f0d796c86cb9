"""How unevenly the window's decode loads the held experts: in each layer,
the busiest held expert's token-expert picks over the mean held expert's,
averaged over the layers (1 is even). From the engine's counters of a
model that holds experts; nothing elsewhere. The busiest expert sets the
time of the expert layer's weighted work, so this moves tpot_p50_ms."""
import numpy as np


def read(w):
    picks = np.asarray(w.stats.get("moe", {}).get("decode_picks", []),
                       dtype=np.float64)
    if picks.ndim != 2 or not picks.sum(axis=1).all():
        return None
    return float(np.mean(picks.max(axis=1) / picks.mean(axis=1)))
