"""Flash-style decode attention Pallas TPU kernel.

TPU adaptation of the paper's attention kernel (§4.2): "KV-cache blocks are
processed in a tiled fashion, computing attention scores and value aggregation
without materializing large intermediate matrices ... we rely on LLC streaming
for KV blocks while maintaining query vectors in private cache." Here:
- KV tiles stream HBM→VMEM via BlockSpec, touched exactly once;
- the (G, hd) query group block is VMEM-pinned across the S grid walk;
- online softmax (running max / normalizer) in the revisited output block —
  no (H, S) score matrix is ever materialized.

Grid: (B, n_kv, n_S) — S innermost; per-(batch, kv-head) accumulators
(o, m, l) are carried as revisited output blocks (interpret-mode friendly).
GQA folds the head group G = Hq // n_kv into the query block.
Supports INT8 KV via per-position scales (paper runs fully-INT8 KV).

Length-aware tile skipping: ``kv_limit`` (a traced (1,1) int32 operand — NO
recompile as cursors advance) is the max live KV extent; every tile whose
first position is past it skips the whole score/PV body under ``pl.when``.
In a serving cache padded to prompt_len + slack the live prefix is usually a
small fraction of S_max, so most tiles retire after one scalar compare —
the kernel-level twin of the engine's chunk-bucketed program selection.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, ks_ref, vs_ref, mask_ref, lim_ref,
            o_ref, m_ref, l_ref, *, n_s: int, block_s: int, scale: float,
            quantized: bool, partial_stats: bool = False):
    s_idx = pl.program_id(2)

    @pl.when(s_idx == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # tile early-out: positions [s_idx*bs, ...) wholly past every live
    # cursor contribute nothing — skip scores AND value aggregation
    @pl.when(s_idx * block_s < lim_ref[0, 0])
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)              # (G, hd)
        k = k_ref[0, 0].astype(jnp.float32)              # (S_blk, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        if quantized:
            k = k * ks_ref[0, 0].astype(jnp.float32)     # (S_blk,1) scales
            v = v * vs_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        mask = mask_ref[0]                               # (1, S_blk)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[0, 0]                             # (G, 1)
        m_blk = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_blk)
        p = jnp.exp(s - m_new)                           # (G, S_blk)
        corr = jnp.exp(m_prev - m_new)                   # (G, 1)
        l_ref[0, 0] = l_ref[0, 0] * corr + jnp.sum(p, axis=1, keepdims=True)
        o_ref[0, 0] = (o_ref[0, 0] * corr
                       + jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                             preferred_element_type=jnp.float32))
        m_ref[0, 0] = m_new

    # split-KV partial mode defers normalization to the cross-shard combine
    # (combine.py): the raw (o, m, l) triple IS the kernel's output
    if not partial_stats:
        @pl.when(s_idx == n_s - 1)
        def _norm():
            o_ref[0, 0] /= jnp.maximum(l_ref[0, 0], 1e-30)


@functools.partial(jax.jit,
                   static_argnames=("block_s", "scale", "interpret",
                                    "partial_stats"))
def flash_decode_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                        k_scale, v_scale, mask: jax.Array, *,
                        block_s: int = 512, scale: float = None,
                        interpret: bool = False,
                        kv_limit=None, partial_stats: bool = False):
    """q: (B,Hq,hd); k/v: (B,n_kv,S,hd) (int8 ⇒ scales (B,n_kv,S,1) f32,
    else pass None); mask: (B,S) bool → (B,Hq,hd) f32.

    ``kv_limit``: optional scalar/0-d/(1,1) int32 — max live KV extent over
    the batch (e.g. ``max(positions) + 1`` after the append). Tiles wholly
    past it are skipped. TRACED, not static: callers pass a fresh value
    every step with zero recompilation. The caller must guarantee the mask
    is already False at positions >= kv_limit — the limit is a fast-path
    hint, never a semantic mask.

    ``partial_stats`` (static): split-KV mode — skip the final
    normalization and return the raw ``(o, m, l)`` flash statistics as
    ``((B,Hq,hd), (B,Hq), (B,Hq))`` f32 for a cross-shard
    ``combine_partial_stats`` merge. A call whose ``kv_limit`` skips every
    tile returns the exact merge identity ``(0, NEG_INF, 0)``."""
    B, Hq, hd = q.shape
    _, n_kv, S, _ = k.shape
    G = Hq // n_kv
    bs = min(block_s, S)
    assert S % bs == 0, (S, bs)
    n_s = S // bs
    sc = scale if scale is not None else 1.0 / math.sqrt(hd)
    quantized = k_scale is not None
    qg = q.reshape(B, n_kv, G, hd)
    if not quantized:                 # feed dummies so the arity is static
        k_scale = jnp.ones((B, n_kv, 1, 1), jnp.float32)
        v_scale = jnp.ones((B, n_kv, 1, 1), jnp.float32)
    ss = k_scale.shape[2]
    # (B, 1, S): a (1, 1, bs) block keeps the last two block dims at
    # (full, multiple of 128) — Mosaic refuses a (1, bs) block of (B, S)
    mask = mask.reshape(B, 1, S)
    if kv_limit is None:
        kv_limit = jnp.full((1, 1), S, jnp.int32)
    else:
        kv_limit = jnp.asarray(kv_limit, jnp.int32).reshape(1, 1)

    grid = (B, n_kv, n_s)
    o, m, l = pl.pallas_call(
        functools.partial(_kernel, n_s=n_s, block_s=bs, scale=sc,
                          quantized=quantized, partial_stats=partial_stats),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, G, hd), lambda b, h, s: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bs, hd), lambda b, h, s: (b, h, s, 0)),
            pl.BlockSpec((1, 1, bs, hd), lambda b, h, s: (b, h, s, 0)),
            pl.BlockSpec((1, 1, bs if quantized else ss, 1),
                         (lambda b, h, s: (b, h, s, 0)) if quantized
                         else (lambda b, h, s: (b, h, 0, 0))),
            pl.BlockSpec((1, 1, bs if quantized else ss, 1),
                         (lambda b, h, s: (b, h, s, 0)) if quantized
                         else (lambda b, h, s: (b, h, 0, 0))),
            pl.BlockSpec((1, 1, bs), lambda b, h, s: (b, 0, s)),
            pl.BlockSpec((1, 1), lambda b, h, s: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, G, hd), lambda b, h, s: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, G, 1), lambda b, h, s: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, G, 1), lambda b, h, s: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, n_kv, G, hd), jnp.float32),
            jax.ShapeDtypeStruct((B, n_kv, G, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, n_kv, G, 1), jnp.float32),
        ],
        interpret=interpret,
    )(qg, k, v, k_scale, v_scale, mask, kv_limit)
    if partial_stats:
        return (o.reshape(B, Hq, hd), m.reshape(B, Hq), l.reshape(B, Hq))
    return o.reshape(B, Hq, hd)
