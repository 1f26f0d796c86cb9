"""Attention: GQA projections + flash-style chunked attention.

Two regimes, mirroring the paper's kernel split (§4.2):

- ``flash_attention`` (train/prefill): block-chunked online-softmax attention
  implemented as a scan over a STATIC (q-chunk, kv-chunk) pair list — only
  causally/window-reachable blocks are enumerated, so HLO FLOPs equal the true
  triangular/banded cost (no 2× causal waste). Custom VJP recomputes blocks in
  the backward pass (FlashAttention-2 style) instead of saving (S×S) residuals.

- ``decode_attention`` (serve): one query against the contiguous KV cache,
  masked softmax. Under sequence-sharded KV rules the softmax reductions
  become the LSE-merge collectives (the §3.1 "add attention nodes" scaling).

The Pallas TPU kernel in ``repro.kernels.flash_decode`` serves the slotted
decode of a flat cache on a TPU, reading each row's live range only
(``models/transformer.py::live_range_block``); this module is the
mathematically identical jnp form for every other read and for CPU dry-runs
(DESIGN.md §10).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.flash_decode.combine import combine_partial_stats
from repro.models import common
from repro.models.common import scan_unroll
from repro.models.sharding import ShardingCtx

NEG_INF = -1e30


def q_chunk_for(S: int) -> int:
    """Block size for banded flash: ≥512, ≤S/16 blocks per axis — bounds the
    static pair list (compile size) while keeping VMEM-friendly tiles."""
    return max(512, S // 16)


# ---------------------------------------------------------------------------
# Static pair list for banded block attention
# ---------------------------------------------------------------------------

def band_pairs(n_q: int, n_kv: int, q_chunk: int, kv_chunk: int,
               causal: bool, window: int, q_offset: int = 0):
    """Enumerate (i, j) blocks that contain at least one unmasked entry.
    ``q_offset``: absolute position of q block 0 (cross/self alignment)."""
    pairs = []
    for i in range(n_q):
        q_lo = q_offset + i * q_chunk
        q_hi = q_lo + q_chunk - 1
        for j in range(n_kv):
            k_lo, k_hi = j * kv_chunk, j * kv_chunk + kv_chunk - 1
            if causal and k_lo > q_hi:
                continue
            if window and k_hi < q_lo - window + 1:
                continue
            pairs.append((i, j))
    return pairs


# ---------------------------------------------------------------------------
# Block kernel (shared by fwd + bwd): returns scores-mask for a block
# ---------------------------------------------------------------------------

def _block_mask(i, j, q_chunk, kv_chunk, causal, window, q_offset):
    qpos = q_offset + i * q_chunk + jnp.arange(q_chunk)
    kpos = j * kv_chunk + jnp.arange(kv_chunk)
    m = jnp.ones((q_chunk, kv_chunk), bool)
    if causal:
        m &= kpos[None, :] <= qpos[:, None]
    if window:
        m &= kpos[None, :] > (qpos[:, None] - window)
    return m


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, window: int = 0,
                    q_chunk: int = 512, kv_chunk: int = 512,
                    q_offset: int = 0, scale: Optional[float] = None,
                    kv_limit: int = 0) -> jax.Array:
    """q: (B, Sq, Hq, hd); k/v: (B, Sk, Hkv, hd); Hq % Hkv == 0. → (B,Sq,Hq,hd).
    Seq lens must be chunk multiples — use flash_attention_padded otherwise.
    kv_limit > 0 masks KV positions ≥ kv_limit (padding)."""
    o, _ = _flash_fwd_impl(q, k, v, causal, window, q_chunk, kv_chunk,
                           q_offset, scale, kv_limit)
    return o


def flash_attention_padded(q, k, v, causal=True, window=0, q_chunk=512,
                           kv_chunk=512, q_offset=0, scale=None):
    """Pads Sq/Sk up to chunk multiples (masked), slices the result back."""
    B, Sq = q.shape[:2]
    Sk = k.shape[1]
    qc, kc = min(q_chunk, Sq), min(kv_chunk, Sk)
    Sq_p = -(-Sq // qc) * qc
    Sk_p = -(-Sk // kc) * kc
    if Sq_p != Sq:
        q = jnp.pad(q, ((0, 0), (0, Sq_p - Sq), (0, 0), (0, 0)))
    if Sk_p != Sk:
        k = jnp.pad(k, ((0, 0), (0, Sk_p - Sk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, Sk_p - Sk), (0, 0), (0, 0)))
    out = flash_attention(q, k, v, causal, window, qc, kc, q_offset, scale,
                          Sk if Sk_p != Sk else 0)
    return out[:, :Sq]


def _prep(q, k, q_chunk, kv_chunk, scale):
    B, Sq, Hq, hd = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    qc = min(q_chunk, Sq)
    kc = min(kv_chunk, Sk)
    # pad S to chunk multiples is the caller's job; assert here
    assert Sq % qc == 0 and Sk % kc == 0, (Sq, qc, Sk, kc)
    sc = scale if scale is not None else 1.0 / math.sqrt(hd)
    return B, Sq, Sk, Hq, Hkv, G, hd, qc, kc, sc


def _flash_fwd_impl(q, k, v, causal, window, q_chunk, kv_chunk, q_offset, scale, kv_limit=0):
    B, Sq, Sk, Hq, Hkv, G, hd, qc, kc, sc = _prep(q, k, q_chunk, kv_chunk, scale)
    pairs = band_pairs(Sq // qc, Sk // kc, qc, kc, causal, window, q_offset)
    ii = jnp.array([p[0] for p in pairs], jnp.int32)
    jj = jnp.array([p[1] for p in pairs], jnp.int32)

    qg = q.reshape(B, Sq, Hkv, G, hd)
    o = jnp.zeros((B, Sq, Hkv, G, hd), jnp.float32)
    m = jnp.full((B, Sq, Hkv, G), NEG_INF, jnp.float32)
    l = jnp.zeros((B, Sq, Hkv, G), jnp.float32)

    def body(carry, ij):
        o, m, l = carry
        i, j = ij
        qi = jax.lax.dynamic_slice_in_dim(qg, i * qc, qc, axis=1)
        kj = jax.lax.dynamic_slice_in_dim(k, j * kc, kc, axis=1)
        vj = jax.lax.dynamic_slice_in_dim(v, j * kc, kc, axis=1)
        s = jnp.einsum("bqkgh,btkh->bkgqt", qi, kj,
                       preferred_element_type=jnp.float32) * sc  # (B,Hkv,G,qc,kc)
        mask = _block_mask_dyn(i, j, qc, kc, causal, window, q_offset, kv_limit)
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        mi = jax.lax.dynamic_slice_in_dim(m, i * qc, qc, 1)    # (B,qc,Hkv,G)
        li = jax.lax.dynamic_slice_in_dim(l, i * qc, qc, 1)
        oi = jax.lax.dynamic_slice_in_dim(o, i * qc, qc, 1)
        m_blk = jnp.max(s, axis=-1).transpose(0, 3, 1, 2)      # (B,qc,Hkv,G)
        m_new = jnp.maximum(mi, m_blk)
        p = jnp.exp(s - m_new.transpose(0, 2, 3, 1)[..., None])
        corr = jnp.exp(mi - m_new)
        l_new = li * corr + jnp.sum(p, -1).transpose(0, 3, 1, 2)
        pv = jnp.einsum("bkgqt,btkh->bqkgh", p.astype(vj.dtype), vj,
                        preferred_element_type=jnp.float32)
        o_new = oi * corr[..., None] + pv
        o = jax.lax.dynamic_update_slice_in_dim(o, o_new, i * qc, 1)
        m = jax.lax.dynamic_update_slice_in_dim(m, m_new, i * qc, 1)
        l = jax.lax.dynamic_update_slice_in_dim(l, l_new, i * qc, 1)
        return (o, m, l), None

    (o, m, l), _ = jax.lax.scan(body, (o, m, l), (ii, jj), unroll=scan_unroll())
    l_safe = jnp.maximum(l, 1e-30)
    out = (o / l_safe[..., None]).reshape(B, Sq, Hq, hd).astype(q.dtype)
    lse = m + jnp.log(l_safe)                                  # (B,Sq,Hkv,G)
    return out, lse


def _block_mask_dyn(i, j, qc, kc, causal, window, q_offset, kv_limit=0):
    qpos = q_offset + i * qc + jnp.arange(qc)
    kpos = j * kc + jnp.arange(kc)
    m = jnp.ones((qc, kc), bool)
    if causal:
        m &= kpos[None, :] <= qpos[:, None]
    if window:
        m &= kpos[None, :] > (qpos[:, None] - window)
    if kv_limit:
        m &= kpos[None, :] < kv_limit
    return m


def _flash_fwd(q, k, v, causal, window, q_chunk, kv_chunk, q_offset, scale,
               kv_limit):
    out, lse = _flash_fwd_impl(q, k, v, causal, window, q_chunk, kv_chunk,
                               q_offset, scale, kv_limit)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, window, q_chunk, kv_chunk, q_offset, scale, kv_limit,
               res, do):
    q, k, v, out, lse = res
    B, Sq, Sk, Hq, Hkv, G, hd, qc, kc, sc = _prep(q, k, q_chunk, kv_chunk, scale)
    pairs = band_pairs(Sq // qc, Sk // kc, qc, kc, causal, window, q_offset)
    ii = jnp.array([p[0] for p in pairs], jnp.int32)
    jj = jnp.array([p[1] for p in pairs], jnp.int32)

    qg = q.reshape(B, Sq, Hkv, G, hd).astype(jnp.float32)
    og = out.reshape(B, Sq, Hkv, G, hd).astype(jnp.float32)
    dog = do.reshape(B, Sq, Hkv, G, hd).astype(jnp.float32)
    D = jnp.sum(og * dog, axis=-1)                             # (B,Sq,Hkv,G)

    dq = jnp.zeros_like(qg)
    dk = jnp.zeros((B, Sk, Hkv, hd), jnp.float32)
    dv = jnp.zeros((B, Sk, Hkv, hd), jnp.float32)

    def body(carry, ij):
        dq, dk, dv = carry
        i, j = ij
        qi = jax.lax.dynamic_slice_in_dim(qg, i * qc, qc, 1)
        kj = jax.lax.dynamic_slice_in_dim(k, j * kc, kc, 1).astype(jnp.float32)
        vj = jax.lax.dynamic_slice_in_dim(v, j * kc, kc, 1).astype(jnp.float32)
        lse_i = jax.lax.dynamic_slice_in_dim(lse, i * qc, qc, 1)
        Di = jax.lax.dynamic_slice_in_dim(D, i * qc, qc, 1)
        doi = jax.lax.dynamic_slice_in_dim(dog, i * qc, qc, 1)
        s = jnp.einsum("bqkgh,btkh->bkgqt", qi, kj) * sc
        mask = _block_mask_dyn(i, j, qc, kc, causal, window, q_offset, kv_limit)
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        p = jnp.exp(s - lse_i.transpose(0, 2, 3, 1)[..., None])   # (B,Hkv,G,qc,kc)
        dvj = jnp.einsum("bkgqt,bqkgh->btkh", p, doi)
        dp = jnp.einsum("bqkgh,btkh->bkgqt", doi, vj)
        ds = p * (dp - Di.transpose(0, 2, 3, 1)[..., None]) * sc
        dqi = jnp.einsum("bkgqt,btkh->bqkgh", ds, kj)
        dkj = jnp.einsum("bkgqt,bqkgh->btkh", ds, qi)
        dq = jax.lax.dynamic_update_slice_in_dim(
            dq, jax.lax.dynamic_slice_in_dim(dq, i * qc, qc, 1) + dqi, i * qc, 1)
        dk = jax.lax.dynamic_update_slice_in_dim(
            dk, jax.lax.dynamic_slice_in_dim(dk, j * kc, kc, 1) + dkj, j * kc, 1)
        dv = jax.lax.dynamic_update_slice_in_dim(
            dv, jax.lax.dynamic_slice_in_dim(dv, j * kc, kc, 1) + dvj, j * kc, 1)
        return (dq, dk, dv), None

    (dq, dk, dv), _ = jax.lax.scan(body, (dq, dk, dv), (ii, jj), unroll=scan_unroll())
    return (dq.reshape(B, Sq, Hq, hd).astype(q.dtype),
            dk.astype(k.dtype), dv.astype(v.dtype))


flash_attention.defvjp(_flash_fwd, _flash_bwd)


# ---------------------------------------------------------------------------
# Decode attention (one query position against the KV cache)
# ---------------------------------------------------------------------------

def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     mask: jax.Array, ctx: ShardingCtx,
                     scale: Optional[float] = None) -> jax.Array:
    """q: (B, Hq, hd); k/v: (B, n_kv, S, hd); mask: (S,) or (B,S) bool.

    Plain masked softmax; when the rules shard S ("kv_seq"→data) the compiler
    turns the max/sum reductions into the distributed-flash LSE merge.
    """
    B, Hq, hd = q.shape
    n_kv = k.shape[1]
    G = Hq // n_kv
    sc = scale if scale is not None else 1.0 / math.sqrt(hd)
    qg = q.reshape(B, n_kv, G, hd)
    # bf16 operands, f32 accumulation — the MXU path; no materialized upcast
    s = jnp.einsum("bkgh,bksh->bkgs", qg, k,
                   preferred_element_type=jnp.float32) * sc
    s = ctx.ann(s, "batch", "kv_heads", None, "kv_seq")
    if mask.ndim == 1:
        mask = mask[None]
    s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bkgs,bksh->bkgh",
                   (p / jnp.maximum(l, 1e-30)).astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, Hq, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# Chunk-prefill attention (C queries against the KV cache)
#
# The chunked-prefill lane (DESIGN.md §7) runs a fixed (1, C) program per
# prompt chunk: the chunk's C queries attend the full cache prefix the chunk
# just extended. The chunk offset is a TRACED scalar, so the static band-pair
# enumeration of ``flash_attention`` does not apply — this is the multi-query
# generalization of ``decode_attention`` (plain masked softmax over the cache
# extent), sharing its sharding annotations and its -inf/underflow masking
# semantics so bucketed and full-extent reads stay bit-identical.
# ---------------------------------------------------------------------------

def chunk_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    mask: jax.Array, ctx: ShardingCtx,
                    scale: Optional[float] = None) -> jax.Array:
    """q: (B, C, Hq, hd); k/v: (B, n_kv, S, hd); mask: (C, S) or (B, C, S)
    bool. → (B, C, Hq, hd). ``decode_attention`` is the C == 1 special case
    (modulo the query axis layout)."""
    B, C, Hq, hd = q.shape
    n_kv = k.shape[1]
    G = Hq // n_kv
    sc = scale if scale is not None else 1.0 / math.sqrt(hd)
    qg = q.reshape(B, C, n_kv, G, hd)
    s = jnp.einsum("bqkgh,bksh->bkgqs", qg, k,
                   preferred_element_type=jnp.float32) * sc  # (B,n_kv,G,C,S)
    s = ctx.ann(s, "batch", "kv_heads", None, None, "kv_seq")
    if mask.ndim == 2:
        mask = mask[None]
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bkgqs,bksh->bqkgh",
                   (p / jnp.maximum(l, 1e-30)).astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, C, Hq, hd).astype(q.dtype)


def chunk_attention_tiered(q: jax.Array, k_hot: jax.Array, v_hot: jax.Array,
                           k_cold: jax.Array, v_cold: jax.Array,
                           hot_mask: jax.Array, mask: jax.Array,
                           ctx: ShardingCtx,
                           scale: Optional[float] = None) -> jax.Array:
    """``chunk_attention`` over a TIERED cache image: key position j of
    query i resolves to the exact hot value when ``hot_mask[b, i, j]`` and
    to the quantize-roundtrip cold value otherwise. The demotion boundary is
    per QUERY (it advances with each query's own count), so unlike the
    decode path the hot/cold select cannot be folded into one pre-selected
    (B,n_kv,S,hd) image — instead both tiers are scored and the (C,S)
    selection happens on the score/weight planes. Each (i, j) entry of the
    softmax sees exactly one tier, so the result equals ``chunk_attention``
    run on the per-query where-selected image.

    q: (B,C,Hq,hd); k/v tiers: (B,n_kv,S,hd) in compute dtype (cold already
    dequantized); hot_mask: (B,C,S) bool; mask: (C,S) or (B,C,S) bool."""
    B, C, Hq, hd = q.shape
    n_kv = k_hot.shape[1]
    G = Hq // n_kv
    sc = scale if scale is not None else 1.0 / math.sqrt(hd)
    qg = q.reshape(B, C, n_kv, G, hd)
    s_hot = jnp.einsum("bqkgh,bksh->bkgqs", qg, k_hot,
                       preferred_element_type=jnp.float32) * sc
    s_cold = jnp.einsum("bqkgh,bksh->bkgqs", qg, k_cold,
                        preferred_element_type=jnp.float32) * sc
    hm = hot_mask[:, None, None]                         # (B,1,1,C,S)
    s = jnp.where(hm, s_hot, s_cold)                     # (B,n_kv,G,C,S)
    s = ctx.ann(s, "batch", "kv_heads", None, None, "kv_seq")
    if mask.ndim == 2:
        mask = mask[None]
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    w = (p / jnp.maximum(l, 1e-30))
    zero = jnp.zeros((), w.dtype)
    o = jnp.einsum("bkgqs,bksh->bqkgh",
                   jnp.where(hm, w, zero).astype(v_hot.dtype), v_hot,
                   preferred_element_type=jnp.float32) \
      + jnp.einsum("bkgqs,bksh->bqkgh",
                   jnp.where(hm, zero, w).astype(v_cold.dtype), v_cold,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, C, Hq, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# Length-aware (chunk-bucketed) decode attention
#
# A freshly admitted request sits at position ~prompt_len while the cache is
# sized for prompt_len + slack: scanning the full padded extent every step
# wastes bandwidth exactly where the paper says coordination/cache-path cost
# dominates decode (§5). The bucketed variant slices the KV to the smallest
# chunk multiple covering every live cursor — the bucket is a STATIC python
# int, so each bucket is its own compiled program (the serving engine fixes
# the bucket set at prepare time and picks per macro-step on the host).
# ---------------------------------------------------------------------------

def kv_buckets(s_max: int, chunk: int, shards: int = 1) -> Tuple[int, ...]:
    """Static bucket set for a cache of extent ``s_max``: chunk multiples
    ``(chunk, 2*chunk, ...)`` with ``s_max`` always the last (full) bucket.
    ``chunk <= 0`` disables bucketing (single full-extent program).

    ``shards > 1`` (split-KV decode): every bucket must cut into ``shards``
    equal shard-local blocks, so the chunk stride is rounded UP to a shard
    multiple and ``s_max`` itself must divide — a coarser-but-divisible
    bucket never loses tokens, it only reads a slightly longer prefix."""
    if shards > 1 and s_max % shards:
        raise ValueError(
            f"KV extent {s_max} not divisible by shards={shards}")
    if chunk <= 0 or chunk >= s_max:
        return (s_max,)
    if shards > 1:
        chunk = -(-chunk // shards) * shards
        if chunk >= s_max:
            return (s_max,)
    return tuple(range(chunk, s_max, chunk)) + (s_max,)


def bucket_for(needed: int, buckets: Tuple[int, ...]) -> int:
    """Smallest bucket covering ``needed`` KV positions (falls back to the
    full extent — the engine guarantees needed <= s_max)."""
    for b in buckets:
        if b >= needed:
            return b
    return buckets[-1]


def decode_attention_bucketed(q: jax.Array, k: jax.Array, v: jax.Array,
                              mask: jax.Array, ctx: ShardingCtx,
                              kv_bucket: int = 0,
                              scale: Optional[float] = None) -> jax.Array:
    """``decode_attention`` over only the first ``kv_bucket`` KV positions
    (static slice). The caller must guarantee every attendable position is
    < kv_bucket — the mask cannot recover positions sliced away.
    ``kv_bucket`` of 0 or >= S is the identity (full extent).

    This is the bucketed form for callers holding DEQUANTIZED (B,n_kv,S,hd)
    tensors. The serving decode path slices one level lower instead —
    ``kv/cache.py::layer_read_bucket`` cuts the stored (possibly int8)
    buffers before dequantization — and then calls plain decode_attention.
    The two slices must keep identical semantics (first-``kv_bucket``
    prefix); test_macro_step.py pins both against the full-extent walk."""
    S = k.shape[2]
    if kv_bucket and kv_bucket < S:
        k = jax.lax.slice_in_dim(k, 0, kv_bucket, axis=2)
        v = jax.lax.slice_in_dim(v, 0, kv_bucket, axis=2)
        mask = jax.lax.slice_in_dim(mask, 0, kv_bucket, axis=mask.ndim - 1)
    return decode_attention(q, k, v, mask, ctx, scale)


# ---------------------------------------------------------------------------
# Split-KV flash decode (sequence-sharded bucketed read, DESIGN.md §3)
#
# Flash-decoding for the A domain: one slot's KV walk is cut into n_shards
# contiguous shard-local blocks; every shard computes its partial flash
# statistics (running max / normalizer / weighted accumulator) with purely
# shard-local reductions, and one LSE merge (kernels/flash_decode/combine.py)
# folds the shards. Under the ``seq_sharded_kv`` rules the "kv_shard" axis
# maps onto the A submesh, so the per-shard einsums stay device-local and
# only the tiny (o, m, l) triples cross devices in the combine — attention
# latency then scales with A-domain width independently of pipeline depth
# (the paper's §2.3 decoupling claim, now *within* a sequence).
# ---------------------------------------------------------------------------

def decode_attention_split(q: jax.Array, k: jax.Array, v: jax.Array,
                           mask: jax.Array, ctx: ShardingCtx,
                           scale: Optional[float] = None) -> jax.Array:
    """q: (B, Hq, hd); k/v SHARD-MAJOR (B, n_kv, n_shards, Sb, hd); mask:
    (B, n_shards*Sb) or (B, n_shards, Sb) bool → (B, Hq, hd).

    Shard s owns the contiguous absolute positions [s*Sb, (s+1)*Sb) of the
    (bucketed) cache prefix. Token-exact vs the sequential walk: a shard
    wholly past a slot's true length contributes exp(NEG_INF - m*) == 0
    weight against any live shard, and shard 0 always holds position 0 of
    a live slot, so the merge never sees an all-empty row that matters."""
    B, Hq, hd = q.shape
    n_kv, n, Sb = k.shape[1], k.shape[2], k.shape[3]
    G = Hq // n_kv
    sc = scale if scale is not None else 1.0 / math.sqrt(hd)
    qg = q.reshape(B, n_kv, G, hd)
    k = ctx.ann(k, "batch", "kv_heads", "kv_shard", "kv_seq", "head_dim")
    v = ctx.ann(v, "batch", "kv_heads", "kv_shard", "kv_seq", "head_dim")
    s = jnp.einsum("bkgh,bknsh->bkgns", qg, k,
                   preferred_element_type=jnp.float32) * sc  # (B,n_kv,G,n,Sb)
    s = ctx.ann(s, "batch", "kv_heads", None, "kv_shard", "kv_seq")
    if mask.ndim == 2:
        mask = mask.reshape(B, n, Sb)
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    # per-shard partial flash statistics — reductions over Sb only (local)
    m = jnp.max(s, axis=-1)                                  # (B,n_kv,G,n)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bkgns,bknsh->bkgnh", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)       # (B,n_kv,G,n,hd)
    o = ctx.ann(o, "batch", "kv_heads", None, "kv_shard", "head_dim")
    # cross-shard reduction: the LSE merge over the shard axis — on a live
    # A submesh this is the only place shards exchange data
    out = combine_partial_stats(o, m, l, axis=3)
    return out.reshape(B, Hq, hd).astype(q.dtype)


def decode_attention_split_bucketed(q: jax.Array, k: jax.Array, v: jax.Array,
                                    mask: jax.Array, ctx: ShardingCtx,
                                    n_shards: int, kv_bucket: int = 0,
                                    scale: Optional[float] = None) -> jax.Array:
    """Bucketed split-KV read for callers holding DEQUANTIZED 4-D KV: the
    same static bucket-prefix slice as ``decode_attention_bucketed``, then a
    contiguous reshape to shard-major and the split flash walk. The serving
    path slices/reshapes one level lower (``kv/cache.py::layer_read_shards``,
    pre-dequantization) with identical slice semantics."""
    S = k.shape[2]
    if kv_bucket and kv_bucket < S:
        k = jax.lax.slice_in_dim(k, 0, kv_bucket, axis=2)
        v = jax.lax.slice_in_dim(v, 0, kv_bucket, axis=2)
        mask = jax.lax.slice_in_dim(mask, 0, kv_bucket, axis=mask.ndim - 1)
    B, n_kv, Se, hd = k.shape
    if Se % n_shards:
        raise ValueError(
            f"KV extent {Se} not divisible by n_shards={n_shards}")
    Sb = Se // n_shards
    k = k.reshape(B, n_kv, n_shards, Sb, hd)
    v = v.reshape(B, n_kv, n_shards, Sb, hd)
    if mask.ndim == 1:
        mask = mask[None]
    return decode_attention_split(q, k, v, mask, ctx, scale)


# ---------------------------------------------------------------------------
# GQA projection parameter bundle
# ---------------------------------------------------------------------------

def make_attn_params(key, cfg, d_model: Optional[int] = None) -> dict:
    d = d_model or cfg.d_model
    dt = common.dtype_of(cfg)
    ks = jax.random.split(key, 6)
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    p = {
        "wq": common.make_linear(ks[0], d, hq * hd, dt, bias=cfg.qkv_bias,
                                 int8=cfg.weight_int8),
        "wk": common.make_linear(ks[1], d, hkv * hd, dt, bias=cfg.qkv_bias,
                                 int8=cfg.weight_int8),
        "wv": common.make_linear(ks[2], d, hkv * hd, dt, bias=cfg.qkv_bias,
                                 int8=cfg.weight_int8),
        "wo": common.make_linear(ks[3], hq * hd, d, dt, int8=cfg.weight_int8),
    }
    if getattr(cfg, "qk_norm", False) or cfg.name.startswith("qwen3-moe"):
        p["q_norm"] = common.make_norm("rmsnorm", hd, dt)
        p["k_norm"] = common.make_norm("rmsnorm", hd, dt)
    return p


def qkv_project(p: dict, x: jax.Array, cfg, ctx: ShardingCtx,
                positions: jax.Array, rope: Optional[Tuple] = None
                ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x: (B,S,D) → q (B,S,Hq,hd), k/v (B,S,Hkv,hd) with RoPE applied;
    ``rope`` = (inverse frequencies, cos/sin scale) replaces the plain
    table of ``cfg.rope_theta`` (``transformer._layer_rope``).

    NOTE (paper §3.2 "head independence"): there is deliberately NO sharding
    annotation forcing materialization between this projection and attention —
    each head's Q/K/V stays on the shard that owns the head ("act_heads").
    """
    B, S, _ = x.shape
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = common.linear(p["wq"], x).reshape(B, S, hq, hd)
    k = common.linear(p["wk"], x).reshape(B, S, hkv, hd)
    v = common.linear(p["wv"], x).reshape(B, S, hkv, hd)
    if "q_norm" in p:
        q = common.apply_norm("rmsnorm", p["q_norm"], q, cfg.norm_eps)
        k = common.apply_norm("rmsnorm", p["k_norm"], k, cfg.norm_eps)
    if cfg.pos == "rope":
        freqs, scale = rope if rope is not None else (None, None)
        q = common.apply_rope(q, positions, cfg.rope_theta, freqs, scale)
        k = common.apply_rope(k, positions, cfg.rope_theta, freqs, scale)
    q = ctx.ann(q, "batch", "seq", "act_heads", "head_dim")
    k = ctx.ann(k, "batch", "seq", "kv_heads", "head_dim")
    v = ctx.ann(v, "batch", "seq", "kv_heads", "head_dim")
    return q, k, v
