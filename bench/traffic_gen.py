"""The one traffic generator: turns a mix file (``bench/traffic/<name>.json``)
and a seed into requests.

Lengths are drawn stratified: for n requests, each length distribution is
evaluated at the n mid-quantiles (i + 0.5) / n and clipped, so every seed
serves the same multiset of prompt and output lengths. They are sent in a
spread order (``spread_order``): request i gets the prompt length whose
rank among all n is that of frac(i * a) among frac(j * a), j < n, with
a = (sqrt 5 - 1) / 2, and the output length ranked likewise with
a = sqrt 2 - 1. Any run of consecutive requests then holds short and long
ones alike, and, the two numbers being rationally independent, prompt and
output lengths pair as if drawn independently. The order is the same for
every seed, so every seed serves the same work and the seed changes only
the token ids, which are uniform over the vocabulary (and the weights).
A stream number keeps the warm-up, the measured backlog and the
correctness sample on separate draws of one seed.
"""
from __future__ import annotations

from dataclasses import dataclass
from statistics import NormalDist
from typing import List

import numpy as np

MEASURED, WARMUP, SAMPLE = 0, 1, 2
PROMPT_STEP, OUTPUT_STEP = (5 ** 0.5 - 1) / 2, 2 ** 0.5 - 1


@dataclass(frozen=True)
class Draw:
    prompt: np.ndarray              # (prompt_len,) int32 token ids
    max_new_tokens: int


def _quantile(spec: dict, q: float) -> int:
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    x = spec["median"] * float(np.exp(spec["sigma"] * NormalDist().inv_cdf(q)))
    return int(min(max(round(x), spec["min"]), spec["max"]))


def stratified_lengths(spec: dict, n: int) -> List[int]:
    return [_quantile(spec, (i + 0.5) / n) for i in range(n)]


def spread_order(n: int, step: float) -> np.ndarray:
    """A permutation of range(n): the rank of frac(i * step) for each i, a
    low-discrepancy order that places neighbours far apart."""
    return np.argsort(np.argsort(np.mod(np.arange(n) * step, 1.0),
                                 kind="stable"), kind="stable")


def rng_for(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([seed, stream])


def draw(mix: dict, n: int, seed: int, stream: int, vocab: int) -> List[Draw]:
    rng = rng_for(seed, stream)
    prompts = np.array(stratified_lengths(mix["prompt_tokens"], n))[
        spread_order(n, PROMPT_STEP)]
    outputs = np.array(stratified_lengths(mix["output_tokens"], n))[
        spread_order(n, OUTPUT_STEP)]
    return [Draw(rng.integers(0, vocab, int(p), dtype=np.int32), int(o))
            for p, o in zip(prompts, outputs)]


def kv_extent(mix: dict, max_new_tokens: int) -> int:
    """Cache positions per slot: the longest prompt plus the output cap."""
    return mix["prompt_tokens"]["max"] + max_new_tokens
