"""Hierarchical (bounded fan-in) collectives — the paper's §4.3 two-level
CCD synchronization mapped to mesh axes.

Flat all-reduce over (pod × data) moves every byte across the slow inter-pod
links. The hierarchical form:

    1. reduce-scatter within the pod (fast ICI ring, fan-in 2/step),
    2. all-reduce ACROSS pods on the 1/|data|-sized shard (slow link),
    3. all-gather within the pod,

cuts cross-pod bytes by |data|× — "keep highly contended state local and
limit cross-domain ownership transfer" (paper §4.3), with the ICI ring playing
the role of the bounded fan-in tree. Used by the shard_map paths (pipeline,
WA routing) and measurable in the dry-run per-axis collective split.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax


def hierarchical_psum(x: jax.Array, fast_axis: str, slow_axis: str,
                      scatter_dim: int = 0) -> jax.Array:
    """psum over (fast_axis × slow_axis) with slow-link traffic ÷ fast_size.
    Requires x.shape[scatter_dim] % fast_size == 0 (falls back to flat psum
    otherwise)."""
    fast = lax.axis_size(fast_axis)
    if x.shape[scatter_dim] % fast != 0:
        return lax.psum(x, (fast_axis, slow_axis))
    shard = lax.psum_scatter(x, fast_axis, scatter_dimension=scatter_dim,
                             tiled=True)
    shard = lax.psum(shard, slow_axis)
    return lax.all_gather(shard, fast_axis, axis=scatter_dim, tiled=True)


def hierarchical_pmean(x, fast_axis: str, slow_axis: str, scatter_dim: int = 0):
    total = lax.axis_size(fast_axis) * lax.axis_size(slow_axis)
    return hierarchical_psum(x, fast_axis, slow_axis, scatter_dim) / total


def ring_all_gather(x: jax.Array, axis: str, concat_dim: int = 0) -> jax.Array:
    """Explicit ring all-gather via ppermute (fan-in 2 per step) — the
    shard_map building block when we schedule collectives by hand."""
    n = lax.axis_size(axis)
    idx = lax.axis_index(axis)
    perm = [(i, (i + 1) % n) for i in range(n)]
    pieces = [x]
    cur = x
    for _ in range(n - 1):
        cur = lax.ppermute(cur, axis, perm)
        pieces.append(cur)
    # rotate into rank order: piece j originated at (idx - j) mod n
    ordered = [None] * n
    for j, p in enumerate(pieces):
        ordered[j] = p
    # stack in origin order using static rotation per rank is data-dependent;
    # concatenating in arrival order then rolling by idx keeps it static:
    out = jnp.concatenate(ordered, axis=concat_dim)
    shard = x.shape[concat_dim]
    return jnp.roll(out, shift=idx * shard, axis=concat_dim)


def grad_sync(grads, dp_axes: Sequence[str], pod_axis: Optional[str] = None):
    """Gradient synchronization for the pipeline/shard_map training path:
    hierarchical when a pod axis exists, flat psum otherwise."""
    def one(g):
        if pod_axis is None:
            return lax.pmean(g, tuple(dp_axes))
        return hierarchical_pmean(g, dp_axes[0], pod_axis, scatter_dim=0)
    return jax.tree.map(one, grads)
