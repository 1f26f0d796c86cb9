"""jit'd wrapper with platform dispatch for decode attention."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import use_pallas_kernel
from repro.kernels.flash_decode.combine import (combine_partial_stats,
                                                merge_partial_stats)
from repro.kernels.flash_decode.flash_decode import flash_decode_pallas
from repro.kernels.flash_decode.ref import (flash_decode_ref,
                                            flash_decode_ref_partial)

__all__ = ["flash_decode", "flash_decode_partial", "combine_partial_stats",
           "merge_partial_stats"]


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret",
                                             "block_s"))
def flash_decode(q, k, v, mask, k_scale=None, v_scale=None, *,
                 use_pallas: bool = None, interpret: bool = False,
                 block_s: int = 512, kv_limit=None) -> jax.Array:
    """Decode attention. q: (B,Hq,hd); k/v: (B,n_kv,S,hd); mask: (B,S).

    ``kv_limit`` (optional, traced int32): max live KV extent — the Pallas
    kernel skips tiles wholly past it (length-aware walk); the jnp reference
    applies it as a mask cut so both paths agree numerically."""
    if use_pallas_kernel(use_pallas, interpret):
        return flash_decode_pallas(q, k, v, k_scale, v_scale, mask,
                                   block_s=block_s, interpret=interpret,
                                   kv_limit=kv_limit)
    if k_scale is not None:
        k = k.astype(jnp.float32) * k_scale
        v = v.astype(jnp.float32) * v_scale
    return flash_decode_ref(q, k, v, mask, kv_limit=kv_limit)


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret",
                                             "block_s"))
def flash_decode_partial(q, k, v, mask, k_scale=None, v_scale=None, *,
                         use_pallas: bool = None, interpret: bool = False,
                         block_s: int = 512, kv_limit=None):
    """Split-KV shard-local decode attention: same dispatch as
    ``flash_decode`` but returns the UN-normalized flash statistics
    ``(o (B,Hq,hd), m (B,Hq), l (B,Hq))`` f32 for a cross-shard
    ``combine_partial_stats`` merge. ``kv_limit`` here is the SHARD-LOCAL
    live extent; a shard with ``kv_limit <= 0`` yields the merge identity
    ``(0, NEG_INF, 0)`` on both paths."""
    if use_pallas_kernel(use_pallas, interpret):
        return flash_decode_pallas(q, k, v, k_scale, v_scale, mask,
                                   block_s=block_s, interpret=interpret,
                                   kv_limit=kv_limit, partial_stats=True)
    if k_scale is not None:
        k = k.astype(jnp.float32) * k_scale
        v = v.astype(jnp.float32) * v_scale
    return flash_decode_ref_partial(q, k, v, mask, kv_limit=kv_limit)
