"""End-to-end metrics of one measured window, from per-request host times.

The window runs from the first request sent to the last completion, ramp-up
and drain included. Under the closed loop with ``concurrency`` clients, a
client sends its next request when its previous one completes; the engine
serves the backlog through exactly ``concurrency`` slots, so the k-th
request admitted (in admission order) was sent at the (k - concurrency)-th
completion, or at the window's start for the first ``concurrency``.

Percentiles are numpy's linear interpolation over all requests of the
window.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Sequence

import numpy as np


@dataclass(frozen=True)
class Served:
    """One request as the window saw it (host monotonic seconds)."""
    t_admitted: float
    t_first_token: float
    t_done: float
    n_tokens: int


def send_times(served: Sequence[Served], concurrency: int,
               t_start: float) -> List[float]:
    """Closed-loop send time of each request, in the order given."""
    done = sorted(r.t_done for r in served)
    order = sorted(range(len(served)), key=lambda i: served[i].t_admitted)
    sent = [0.0] * len(served)
    for k, i in enumerate(order):
        sent[i] = t_start if k < concurrency else done[k - concurrency]
    return sent


def metrics(served: Sequence[Served], concurrency: int, t_start: float,
            t_end: float) -> dict:
    window = t_end - t_start
    sent = send_times(served, concurrency, t_start)
    ttft = np.array([r.t_first_token - s for r, s in zip(served, sent)])
    tpot = np.array([(r.t_done - r.t_first_token) / (r.n_tokens - 1)
                     for r in served if r.n_tokens > 1])
    return {
        "output_tokens_per_s": sum(r.n_tokens for r in served) / window,
        "ttft_p50_ms": float(np.percentile(ttft, 50)) * 1e3,
        "ttft_p90_ms": float(np.percentile(ttft, 90)) * 1e3,
        "tpot_p50_ms": float(np.percentile(tpot, 50)) * 1e3,
        "tpot_p90_ms": float(np.percentile(tpot, 90)) * 1e3,
    }
