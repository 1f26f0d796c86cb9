"""The comparison that decides ``correct``: served greedy tokens against the
plain float32 reference.

After the window, a sample of the completed requests drawn from the seed
(the longest prompt-plus-output and the longest output always in it) is
run through the reference once, each prompt followed by its served tokens.
At each served position the reference's logits say how far the served
token's logit lies below the reference's best. The widest such gap over
the sample is the number compared. A greedy program that computes the
model faithfully in bf16 leaves only near-ties flipped; a wrong cache
position, a skipped chunk or an altered token opens a gap of the order of
the logits' spread.

The control puts the reference in the program's place one precision step
below: at the same positions it reads the gap of the token that the
lower-precision reference puts first.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Sequence

import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class Sample:
    tokens: np.ndarray      # (k, S) int32: prompt, then served tokens but last
    score_pos: np.ndarray   # (k, n) positions whose logits chose a token
    served: np.ndarray      # (k, n) the token served there
    mask: np.ndarray        # (k, n) bool: a real served token

    @property
    def n_tokens(self) -> int:
        return int(self.mask.sum())


def pick(requests: Sequence, k: int, rng: np.random.Generator) -> List[int]:
    """Indices of ``k`` requests: the longest prompt-plus-output, the
    longest output, then others drawn from ``rng``."""
    n = len(requests)
    total = [len(r.prompt) + len(r.generated) for r in requests]
    chosen = [int(np.argmax(total))]
    longest_out = int(np.argmax([len(r.generated) for r in requests]))
    if longest_out not in chosen:
        chosen.append(longest_out)
    rest = [i for i in rng.permutation(n) if i not in chosen]
    return chosen + [int(i) for i in rest[:max(0, k - len(chosen))]]


def build(requests: Sequence, extent: int) -> Sample:
    """Right-padded rows of prompt + served tokens (the last served token is
    never input), with the positions whose logits chose each served token."""
    k = len(requests)
    n = max(len(r.generated) for r in requests)
    tokens = np.zeros((k, extent), np.int32)
    score = np.zeros((k, n), np.int32)
    served = np.zeros((k, n), np.int32)
    mask = np.zeros((k, n), bool)
    for i, r in enumerate(requests):
        seq = list(r.prompt) + list(r.generated[:-1])
        if len(seq) > extent:
            raise ValueError(f"request of {len(seq)} tokens exceeds {extent}")
        tokens[i, :len(seq)] = seq
        g = len(r.generated)
        pos = len(r.prompt) - 1 + np.arange(g)
        score[i, :g], served[i, :g], mask[i, :g] = pos, r.generated, True
        score[i, g:] = pos[-1]
    return Sample(tokens, score, served, mask)


def widest_gap(ref_logits, chosen, mask) -> float:
    """Largest (reference best - reference logit of the chosen token) over
    the real positions."""
    ref = jnp.asarray(ref_logits)
    picked = jnp.take_along_axis(ref, jnp.asarray(chosen)[..., None],
                                 -1)[..., 0]
    gap = jnp.max(ref, -1) - picked
    return float(jnp.max(jnp.where(jnp.asarray(mask), gap, -jnp.inf)))


def control_gap(ref_logits, ctl_logits, mask) -> float:
    """Widest gap of the token the control puts first."""
    return widest_gap(ref_logits, jnp.argmax(jnp.asarray(ctl_logits), -1),
                      mask)
