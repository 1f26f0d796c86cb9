"""KV-cache management.

Layout is CONTIGUOUS (L, B, n_kv, S_max, head_dim) — the paper (§7.1) explicitly
rejects paged layouts because address indirection lands on the decode critical
path; we follow that choice and isolate KV by *placement* instead (WA
separation / sequence sharding), not by virtual-memory tricks.

Supports:
- full-context caches (global attention),
- ring-buffer sliding-window caches (recurrentgemma local attention),
- INT8-quantized storage with per-(b, head, pos) scales (paper runs fully INT8),
- TIERED storage (DESIGN.md §7): a hot ring of the most recent
  ``hot_window`` tokens at the compute dtype plus a cold tier holding every
  position quantized at ``cold_dtype`` (bf16 passthrough, int8, or packed
  int4). The hot→cold boundary advances in ``cold_block`` steps inside the
  compiled programs — per-QUERY, from traced cursors — so chunked prefill,
  monolithic admission and macro-step decode all attend the identical
  hot/cold image for every (key, query) pair.

The cache is a pytree; decode steps donate it (buffer reuse — no double
allocation of the GB-scale KV in steady state).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.quant.int4 import dequantize_kv_int4, quantize_kv_int4
from repro.quant.int8 import dequantize_kv, quantize_kv


@jax.tree_util.register_pytree_with_keys_class
class KVCache:
    """Pytree: (k, v, k_scale, v_scale, hot_k, hot_v, length) children;
    ``window`` / tier geometry (hot_window, cold_block, cold_dtype) static.
    Untiered caches carry ``hot_k = hot_v = None`` — k/v are then the one
    flat tier; tiered caches store the cold image in k/v (+scales for
    int8/int4) and the exact recents in the hot ring."""

    _FIELDS = ("k", "v", "k_scale", "v_scale", "hot_k", "hot_v", "length")

    def __init__(self, k, v, k_scale, v_scale, length, window: int = 0,
                 hot_k=None, hot_v=None, hot_window: int = 0,
                 cold_block: int = 0, cold_dtype: str = "bfloat16"):
        self.k = k                       # (L,B,n_kv,S,hd_c)  cold/flat tier
        self.v = v
        self.k_scale = k_scale           # (L,B,n_kv,S,1) f32 — int8/int4 only
        self.v_scale = v_scale
        self.hot_k = hot_k               # (L,B,n_kv,H,hd) compute dtype ring
        self.hot_v = hot_v               # H = hot_window + cold_block
        self.length = length             # () int32 — tokens appended so far
        self.window = window             # 0 → full ctx; >0 → ring buffer
        self.hot_window = hot_window     # 0 → flat (untiered)
        self.cold_block = cold_block     # demotion granularity (tokens)
        self.cold_dtype = cold_dtype     # bfloat16 | int8 | int4

    def tree_flatten_with_keys(self):
        kids = tuple((jax.tree_util.GetAttrKey(f), getattr(self, f))
                     for f in self._FIELDS)
        return kids, (self.window, self.hot_window, self.cold_block,
                      self.cold_dtype)

    def tree_flatten(self):
        return (tuple(getattr(self, f) for f in self._FIELDS),
                (self.window, self.hot_window, self.cold_block,
                 self.cold_dtype))

    @classmethod
    def tree_unflatten(cls, aux, children):
        k, v, k_scale, v_scale, hot_k, hot_v, length = children
        window, hot_window, cold_block, cold_dtype = aux
        return cls(k, v, k_scale, v_scale, length, window=window,
                   hot_k=hot_k, hot_v=hot_v, hot_window=hot_window,
                   cold_block=cold_block, cold_dtype=cold_dtype)

    def _replace(self, **kw):
        d = dict(k=self.k, v=self.v, k_scale=self.k_scale,
                 v_scale=self.v_scale, length=self.length, window=self.window,
                 hot_k=self.hot_k, hot_v=self.hot_v,
                 hot_window=self.hot_window, cold_block=self.cold_block,
                 cold_dtype=self.cold_dtype)
        d.update(kw)
        return KVCache(**d)

    @property
    def max_len(self) -> int:
        return self.k.shape[3]

    @property
    def is_quantized(self) -> bool:
        return self.k_scale is not None

    @property
    def is_tiered(self) -> bool:
        return self.hot_k is not None


def hot_extent(hot_window: int, cold_block: int) -> int:
    """Hot-ring size: the live hot region spans [cold_boundary, cursor] whose
    length is at most hot_window + cold_block − 1 (the boundary advances in
    cold_block jumps), so a ring of hot_window + cold_block slots always
    holds every hot position distinctly."""
    return hot_window + cold_block


def cold_boundary(counts, hot_window: int, cold_block: int):
    """First position still HOT for a row holding ``counts`` tokens —
    positions < boundary resolve to the cold tier, positions >= boundary to
    the exact hot ring. The boundary only moves at cold_block multiples:
    floor((counts − hot_window) / cold_block) · cold_block, clamped at 0.
    Depends only on the row's token count, never on chunk/block geometry, so
    every serving lane computes the identical per-query image."""
    over = jnp.maximum(jnp.asarray(counts, jnp.int32) - hot_window, 0)
    return (over // cold_block) * cold_block


def cold_pack_dim(head_dim: int, cold_dtype: str) -> int:
    """Stored head_dim of the cold tier (int4 packs two nibbles per byte)."""
    if cold_dtype == "int4":
        if head_dim % 2:
            raise ValueError(f"int4 cold tier needs even head_dim, "
                             f"got {head_dim}")
        return head_dim // 2
    return head_dim


def quantize_cold(x, cold_dtype: str):
    """(values, scale) at the cold dtype; bf16 cold stores verbatim."""
    if cold_dtype == "int4":
        return quantize_kv_int4(x)
    if cold_dtype == "int8":
        return quantize_kv(x)
    return x, None


def cold_read(k_l, v_l, k_scale_l, v_scale_l, cold_dtype: str,
              dtype=jnp.bfloat16):
    """Dequantize a cold-tier slice to the compute dtype (format-aware
    ``layer_read``: int4 unpacks, int8 rescales, bf16 casts)."""
    if k_scale_l is None:
        return k_l.astype(dtype), v_l.astype(dtype)
    if cold_dtype == "int4":
        return (dequantize_kv_int4(k_l, k_scale_l, dtype),
                dequantize_kv_int4(v_l, v_scale_l, dtype))
    return (dequantize_kv(k_l, k_scale_l, dtype),
            dequantize_kv(v_l, v_scale_l, dtype))


def init_kv_cache(n_layers: int, batch: int, n_kv: int, max_len: int,
                  head_dim: int, dtype=jnp.bfloat16, quantized: bool = False,
                  window: int = 0, hot_window: int = 0, cold_block: int = 0,
                  cold_dtype: str = "bfloat16") -> KVCache:
    size = min(window, max_len) if window else max_len
    # k/v (and the scales) must be DISTINCT buffers: the serving engine
    # donates the whole cache pytree per step, and XLA rejects donating one
    # buffer twice
    def mk(s, dt):
        return jnp.zeros(s, dt)

    if hot_window:
        if quantized:
            raise ValueError("tiered KV (hot_window > 0) subsumes the flat "
                             "int8 cache; use kv_cold_dtype instead of "
                             "kv_dtype='int8'")
        if window:
            raise ValueError("tiered KV does not compose with sliding-window "
                             "(ring) caches")
        if cold_block < 1:
            raise ValueError(f"cold_block must be >= 1, got {cold_block}")
        if cold_dtype not in ("bfloat16", "int8", "int4"):
            raise ValueError(f"unknown kv_cold_dtype {cold_dtype!r}")
        cold_scaled = cold_dtype in ("int8", "int4")
        cshape = (n_layers, batch, n_kv, size,
                  cold_pack_dim(head_dim, cold_dtype))
        sshape = cshape[:-1] + (1,)
        hshape = (n_layers, batch, n_kv, hot_extent(hot_window, cold_block),
                  head_dim)
        return KVCache(mk(cshape, jnp.int8 if cold_scaled else dtype),
                       mk(cshape, jnp.int8 if cold_scaled else dtype),
                       mk(sshape, jnp.float32) if cold_scaled else None,
                       mk(sshape, jnp.float32) if cold_scaled else None,
                       jnp.zeros((), jnp.int32), window=0,
                       hot_k=mk(hshape, dtype), hot_v=mk(hshape, dtype),
                       hot_window=hot_window, cold_block=cold_block,
                       cold_dtype=cold_dtype)
    store = jnp.int8 if quantized else dtype
    shape = (n_layers, batch, n_kv, size, head_dim)
    sshape = shape[:-1] + (1,)
    return KVCache(mk(shape, store), mk(shape, store),
                   mk(sshape, jnp.float32) if quantized else None,
                   mk(sshape, jnp.float32) if quantized else None,
                   jnp.zeros((), jnp.int32), window=window)


def _slot(cache: KVCache, pos: jax.Array) -> jax.Array:
    return jax.lax.rem(pos, cache.k.shape[3]) if cache.window else pos


def append_kv(cache: KVCache, layer: jax.Array, k_new: jax.Array,
              v_new: jax.Array) -> KVCache:
    """Append ONE position for one layer. k_new/v_new: (B, n_kv, hd).

    Used inside the per-layer scan: ``layer`` is the scan index. The write is
    a dynamic_update_slice — O(1), no relayout (contiguity preserved).
    """
    pos = cache.length
    slot = _slot(cache, pos)
    if cache.is_quantized:
        kq, ks = quantize_kv(k_new)
        vq, vs = quantize_kv(v_new)
        k = jax.lax.dynamic_update_slice(
            cache.k, kq[None, :, :, None, :], (layer, 0, 0, slot, 0))
        v = jax.lax.dynamic_update_slice(
            cache.v, vq[None, :, :, None, :], (layer, 0, 0, slot, 0))
        k_scale = jax.lax.dynamic_update_slice(
            cache.k_scale, ks[None, :, :, None, :], (layer, 0, 0, slot, 0))
        v_scale = jax.lax.dynamic_update_slice(
            cache.v_scale, vs[None, :, :, None, :], (layer, 0, 0, slot, 0))
        return cache._replace(k=k, v=v, k_scale=k_scale, v_scale=v_scale)
    k = jax.lax.dynamic_update_slice(
        cache.k, k_new[None, :, :, None, :].astype(cache.k.dtype),
        (layer, 0, 0, slot, 0))
    v = jax.lax.dynamic_update_slice(
        cache.v, v_new[None, :, :, None, :].astype(cache.v.dtype),
        (layer, 0, 0, slot, 0))
    return cache._replace(k=k, v=v)


def bump_length(cache: KVCache) -> KVCache:
    """Advance the write cursor once per decode step (after all layers)."""
    return cache._replace(length=cache.length + 1)


def read_kv(cache: KVCache, layer: jax.Array, dtype=jnp.bfloat16):
    """Return (k, v) for a layer as compute dtype: (B, n_kv, S, hd)."""
    k = jax.lax.dynamic_index_in_dim(cache.k, layer, axis=0, keepdims=False)
    v = jax.lax.dynamic_index_in_dim(cache.v, layer, axis=0, keepdims=False)
    if cache.is_quantized:
        ks = jax.lax.dynamic_index_in_dim(cache.k_scale, layer, 0, keepdims=False)
        vs = jax.lax.dynamic_index_in_dim(cache.v_scale, layer, 0, keepdims=False)
        return dequantize_kv(k, ks, dtype), dequantize_kv(v, vs, dtype)
    return k.astype(dtype), v.astype(dtype)


# ---------------------------------------------------------------------------
# Per-layer API. Reads take one layer's (B,n_kv,S,hd) slice. The slotted
# writes below also take a write ADDRESS that may lead with a layer index:
# the serving layer loop (models/transformer.py) carries the whole
# (L,B,n_kv,S,hd) stacks and writes layer l's new tokens into them in place,
# so a donated cache aliases from program entry to exit and no stack is ever
# rebuilt or copied.
# ---------------------------------------------------------------------------

def layer_append(k_l: jax.Array, v_l: jax.Array, k_scale_l, v_scale_l,
                 k_new: jax.Array, v_new: jax.Array, pos: jax.Array,
                 window: int):
    """k_l/v_l: (B,n_kv,S,hd); k_new/v_new: (B,n_kv,hd). Returns updated
    slices. Quantizes when scale slices are present."""
    size = k_l.shape[2]
    slot = jax.lax.rem(pos, size) if window else pos
    if k_scale_l is not None:
        kq, ks = quantize_kv(k_new)
        vq, vs = quantize_kv(v_new)
        k_l = jax.lax.dynamic_update_slice(k_l, kq[:, :, None, :], (0, 0, slot, 0))
        v_l = jax.lax.dynamic_update_slice(v_l, vq[:, :, None, :], (0, 0, slot, 0))
        k_scale_l = jax.lax.dynamic_update_slice(
            k_scale_l, ks[:, :, None, :], (0, 0, slot, 0))
        v_scale_l = jax.lax.dynamic_update_slice(
            v_scale_l, vs[:, :, None, :], (0, 0, slot, 0))
        return k_l, v_l, k_scale_l, v_scale_l
    k_l = jax.lax.dynamic_update_slice(
        k_l, k_new[:, :, None, :].astype(k_l.dtype), (0, 0, slot, 0))
    v_l = jax.lax.dynamic_update_slice(
        v_l, v_new[:, :, None, :].astype(v_l.dtype), (0, 0, slot, 0))
    return k_l, v_l, None, None


def layer_read(k_l, v_l, k_scale_l, v_scale_l, dtype=jnp.bfloat16):
    if k_scale_l is not None:
        return (dequantize_kv(k_l, k_scale_l, dtype),
                dequantize_kv(v_l, v_scale_l, dtype))
    return k_l.astype(dtype), v_l.astype(dtype)


def layer_read_bucket(k_l, v_l, k_scale_l, v_scale_l, bucket: int,
                      dtype=jnp.bfloat16):
    """``layer_read`` over only the first ``bucket`` positions (static slice
    of the STORED buffers, so int8 caches dequantize just the bucket — the
    length-aware decode path never upcasts KV it will not attend).
    ``bucket`` of 0 or >= S is the full-extent read."""
    S = k_l.shape[2]
    if bucket and bucket < S:
        def cut(a):
            return (None if a is None
                    else jax.lax.slice_in_dim(a, 0, bucket, axis=2))
        k_l, v_l = cut(k_l), cut(v_l)
        k_scale_l, v_scale_l = cut(k_scale_l), cut(v_scale_l)
    return layer_read(k_l, v_l, k_scale_l, v_scale_l, dtype)


# ---------------------------------------------------------------------------
# Split-KV shard-local layout (DESIGN.md §3) — one slot's contiguous
# (B,n_kv,S,hd) extent cut into n_shards equal sequence blocks for the
# A-domain split flash walk. Sharding is a READ-time view: the stored layout
# stays contiguous (no paging, §7.1), writes and cursors remain absolute.
# ---------------------------------------------------------------------------

def shard_extent(extent: int, n_shards: int) -> int:
    """Shard-local block length for a (bucketed) extent; validates that the
    extent cuts into ``n_shards`` equal contiguous blocks."""
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    if extent % n_shards:
        raise ValueError(
            f"KV extent {extent} not divisible by n_shards={n_shards}")
    return extent // n_shards


def shard_kv_limits(kv_limit: jax.Array, n_shards: int,
                    block: int) -> jax.Array:
    """Per-shard live extents for a GLOBAL limit over a contiguous split:
    shard s owns absolute positions [s*block, (s+1)*block), so its local
    live extent is clamp(kv_limit - s*block, 0, block). Returns (n_shards,)
    int32 — traced, advancing cursors never recompile. A shard whose limit
    clamps to 0 is fully skippable (the flash kernel then reports the exact
    merge identity)."""
    lim = jnp.asarray(kv_limit, jnp.int32).reshape(())
    starts = jnp.arange(n_shards, dtype=jnp.int32) * block
    return jnp.clip(lim - starts, 0, block)


def layer_read_shards(k_l, v_l, k_scale_l, v_scale_l, bucket: int,
                      n_shards: int, dtype=jnp.bfloat16):
    """Shard-major bucketed read: ``layer_read_bucket``'s static prefix cut
    of the STORED buffers (int8 dequantizes just the bucket), then a
    contiguous reshape (B,n_kv,Se,hd) -> (B,n_kv,n_shards,Se/n_shards,hd).
    Identical prefix semantics to the sequential read — the two only differ
    in the shard axis the split flash walk reduces over."""
    k, v = layer_read_bucket(k_l, v_l, k_scale_l, v_scale_l, bucket, dtype)
    B, n_kv, Se, hd = k.shape
    Sb = shard_extent(Se, n_shards)
    return (k.reshape(B, n_kv, n_shards, Sb, hd),
            v.reshape(B, n_kv, n_shards, Sb, hd))


# ---------------------------------------------------------------------------
# Per-slot (continuous-batching) API — the serving engine admits a request
# into ONE batch slot while the other slots keep decoding (DESIGN.md §7).
# Shapes stay static: the slot index and per-row cursors are traced scalars /
# (B,) vectors, so every program below compiles exactly once.
# ---------------------------------------------------------------------------

def _split_address(at):
    """``(layer, index)`` → ((layer,), index): write into layer ``layer`` of
    (L, B, …) stacks. A bare ``index`` → ((), index): write into a (B, …)
    layer slice."""
    if isinstance(at, tuple):
        layer, at = at
        return (layer,), at
    return (), at


def _write_rows(dst, new, slots, active, lead=()):
    """Row b of ``dst`` (*lead, B, n_kv, S, d) takes ``new[b]`` (n_kv, d) at
    position ``slots[b]``, where ``active[b]``; ``lead`` indexes the leading
    (layer) axes. One dynamic_update_slice per row, of the tile-aligned
    window of W positions that holds the slot: the window is read back and
    changed at the slot alone, so an inactive row rewrites its own bytes and
    the rest of the slice is never read or written. W is one (8,128) tile's
    rows (8 for 4-byte types, 16 for bf16, 32 for int8): an unaligned
    one-position write makes XLA's TPU layout assignment relayout the whole
    carried stack at program entry and exit. Out-of-range slots clamp to
    [0, S) as ``dynamic_update_slice`` clamps them."""
    B, n_kv, d = new.shape
    S = dst.shape[-2]
    W = min(S, 8 * max(1, 4 // dst.dtype.itemsize))
    offs = jnp.arange(W, dtype=jnp.int32)
    new = new.astype(dst.dtype)
    for b in range(B):
        slot = jnp.clip(slots[b], 0, S - 1)
        start = jnp.minimum(slot - jax.lax.rem(slot, W), S - W)
        at = lead + (b, 0, start, 0)
        old = jax.lax.dynamic_slice(dst, at,
                                    (1,) * (len(lead) + 1) + (n_kv, W, d))
        hit = ((start + offs == slot) & active[b])[:, None]       # (W,1)
        dst = jax.lax.dynamic_update_slice(
            dst, jnp.where(hit, new[b][:, None, :], old), at)
    return dst


def layer_append_slotted(k_l: jax.Array, v_l: jax.Array, k_scale_l, v_scale_l,
                         k_new: jax.Array, v_new: jax.Array,
                         positions, window: int,
                         active: Optional[jax.Array] = None):
    """Per-row append: row ``b`` writes ``k_new[b]`` at its OWN cursor
    ``positions[b]`` (rows may sit at different depths). k_new/v_new:
    (B,n_kv,hd); active: (B,) bool — an inactive row writes nothing
    (retired slots must not pollute the cache). ``positions``: (B,) int32
    cursors into (B,n_kv,S,hd) layer slices, or ``(layer, cursors)`` to
    append to layer ``layer`` of (L,B,n_kv,S,hd) stacks in place. Each row
    writes one tile-aligned window (``_write_rows``): the active mask
    applies to the written token, never to the slice. Returns the updated
    buffers; quantizes when scale buffers are present."""
    lead, positions = _split_address(positions)
    size = k_l.shape[-2]
    slots = jax.lax.rem(positions, size) if window else positions
    if active is None:
        active = jnp.ones(positions.shape, bool)

    def put(dst, new):
        return _write_rows(dst, new, slots, active, lead)

    if k_scale_l is not None:
        kq, ks = quantize_kv(k_new)
        vq, vs = quantize_kv(v_new)
        return put(k_l, kq), put(v_l, vq), put(k_scale_l, ks), \
            put(v_scale_l, vs)
    return put(k_l, k_new), put(v_l, v_new), None, None


def _put_chunk(dst, new, at, keep):
    """Write ``new`` (n_kv, C, d) at ``at`` = lead + (slot, 0, start, 0) of
    ``dst``; chunk positions where ``keep`` is False keep their bytes."""
    size = (1,) * (len(at) - 3) + new.shape
    cur = jax.lax.dynamic_slice(dst, at, size).reshape(new.shape)
    new = jnp.where(keep, new.astype(dst.dtype), cur)
    return jax.lax.dynamic_update_slice(dst, new.reshape(size), at)


def layer_write_chunk(k_l: jax.Array, v_l: jax.Array, k_scale_l, v_scale_l,
                      k_new: jax.Array, v_new: jax.Array, slot,
                      start, valid_len):
    """Chunked-prefill write: ONE slot's (C,)-wide chunk lands at cache
    positions [start, start+C) of row ``slot``. k_l/v_l: (B,n_kv,S,hd)
    layer slices, or (L,B,n_kv,S,hd) stacks with ``slot`` given as
    ``(layer, slot)``, written in place; k_new/v_new: (n_kv,C,hd);
    slot/start/valid_len are traced scalars — one compiled program serves
    every chunk of every prompt. Chunk positions >= ``valid_len``
    (last-chunk padding) keep their previous bytes, so the cache past a
    prompt's true length is never touched and per-row cursor masks stay the
    single source of validity. Quantizes per position when scale buffers
    are present (int8 caches store the chunk pre-dequant)."""
    lead, slot = _split_address(slot)
    at = lead + (slot, 0, start, 0)
    C = k_new.shape[1]
    keep = (jnp.arange(C, dtype=jnp.int32) < valid_len)[None, :, None]

    def put(dst, new):
        return None if dst is None else _put_chunk(dst, new, at, keep)

    if k_scale_l is not None:
        kq, ks = quantize_kv(k_new)
        vq, vs = quantize_kv(v_new)
        return (put(k_l, kq), put(v_l, vq),
                put(k_scale_l, ks), put(v_scale_l, vs))
    return put(k_l, k_new), put(v_l, v_new), None, None


def layer_read_slot(k_l, v_l, k_scale_l, v_scale_l, slot,
                    dtype=jnp.bfloat16):
    """``layer_read`` over ONE batch row (traced ``slot``): returns the
    slot's (1,n_kv,S,hd) K/V in compute dtype — the chunk-prefill attention
    reads the prefix it just extended without touching other slots."""
    def take(a):
        if a is None:
            return None
        return jax.lax.dynamic_slice(
            a, (slot,) + (0,) * (a.ndim - 1), (1,) + a.shape[1:])

    return layer_read(take(k_l), take(v_l), take(k_scale_l),
                      take(v_scale_l), dtype)


# ---------------------------------------------------------------------------
# Tiered (hot ring + quantized cold) per-layer API — DESIGN.md §7.
#
# Every position is STAGED into the cold tier at write time (quantization of
# a given bf16 vector is deterministic, so staging eagerly at append is
# byte-identical to lazily re-quantizing the aging block at the demotion
# boundary — with uniform per-step cost and no gather). The hot ring holds
# the exact values of the most recent positions; "demotion" is the read-side
# boundary ``cold_boundary(count)`` advancing by cold_block inside the
# compiled program. Both writes are slot-extent-1 dynamic_update_slices, the
# same isolation contract the kernel-bounds pass audits for flat caches, and
# take the same layer-leading write address as the flat writes.
# ---------------------------------------------------------------------------

def layer_append_tiered(k_l, v_l, k_scale_l, v_scale_l, hot_k_l, hot_v_l,
                        k_new, v_new, positions, cold_dtype: str,
                        active: Optional[jax.Array] = None):
    """Decode append for a tiered layer: stage the new position into the
    cold tier (quantized at ``cold_dtype``) AND write it exactly into the
    hot ring at slot position % H. k_l/v_l: (B,n_kv,S,hd_c); hot rings
    (B,n_kv,H,hd); k_new/v_new: (B,n_kv,hd); positions: (B,) int32, or
    ``(layer, positions)`` for (L,B,…) stacks written in place — the same
    per-row window writes as ``layer_append_slotted``."""
    lead, positions = _split_address(positions)
    H = hot_k_l.shape[-2]
    ring = jax.lax.rem(positions, H)
    if active is None:
        active = jnp.ones(positions.shape, bool)

    def put(dst, new, slots):
        return _write_rows(dst, new, slots, active, lead)

    kq, ks = quantize_cold(k_new, cold_dtype)
    vq, vs = quantize_cold(v_new, cold_dtype)
    k_l = put(k_l, kq, positions)
    v_l = put(v_l, vq, positions)
    if k_scale_l is not None:
        k_scale_l = put(k_scale_l, ks, positions)
        v_scale_l = put(v_scale_l, vs, positions)
    hot_k_l = put(hot_k_l, k_new, ring)
    hot_v_l = put(hot_v_l, v_new, ring)
    return k_l, v_l, k_scale_l, v_scale_l, hot_k_l, hot_v_l


def layer_read_tiered(k_l, v_l, k_scale_l, v_scale_l, hot_k_l, hot_v_l,
                      counts: jax.Array, bucket: int, hot_window: int,
                      cold_block: int, cold_dtype: str, dtype=jnp.bfloat16):
    """Tiered bucketed read: (B,n_kv,Se,hd) image where position j of row b
    resolves to the exact hot-ring value when j >= cold_boundary(counts[b])
    and to the dequantized cold bytes otherwise. The bucket prefix is cut
    from the STORED buffers first — only the touched prefix of each tier is
    ever dequantized/tiled. ``counts``: (B,) tokens stored per row (cursors
    + 1, post-append)."""
    S = k_l.shape[2]
    Se = bucket if (bucket and bucket < S) else S

    def cut(a):
        if a is None or Se == S:
            return a
        return jax.lax.slice_in_dim(a, 0, Se, axis=2)
    kc, vc = cold_read(cut(k_l), cut(v_l), cut(k_scale_l), cut(v_scale_l),
                       cold_dtype, dtype)
    H = hot_k_l.shape[2]
    idx = jnp.arange(Se, dtype=jnp.int32)
    kh = jnp.take(hot_k_l, jax.lax.rem(idx, H), axis=2).astype(dtype)
    vh = jnp.take(hot_v_l, jax.lax.rem(idx, H), axis=2).astype(dtype)
    cb = cold_boundary(counts, hot_window, cold_block)          # (B,)
    hot = (idx[None, :] >= cb[:, None])[:, None, :, None]       # (B,1,Se,1)
    return jnp.where(hot, kh, kc), jnp.where(hot, vh, vc)


def layer_read_tiered_shards(k_l, v_l, k_scale_l, v_scale_l, hot_k_l,
                             hot_v_l, counts, bucket: int, n_shards: int,
                             hot_window: int, cold_block: int,
                             cold_dtype: str, dtype=jnp.bfloat16):
    """Shard-major tiered read: the tiered image select is positionwise, so
    the split-KV layout is the same contiguous reshape as
    ``layer_read_shards`` applied AFTER the hot/cold resolve — shard s owns
    absolute positions [s·Sb, (s+1)·Sb) of the concatenated image."""
    k, v = layer_read_tiered(k_l, v_l, k_scale_l, v_scale_l, hot_k_l,
                             hot_v_l, counts, bucket, hot_window, cold_block,
                             cold_dtype, dtype)
    B, n_kv, Se, hd = k.shape
    Sb = shard_extent(Se, n_shards)
    return (k.reshape(B, n_kv, n_shards, Sb, hd),
            v.reshape(B, n_kv, n_shards, Sb, hd))


def layer_write_chunk_tiered(k_l, v_l, k_scale_l, v_scale_l, hot_k_l,
                             hot_v_l, k_new, v_new, slot, start, valid_len,
                             cold_dtype: str):
    """Chunked-prefill write into BOTH tiers: the chunk's positions are
    staged into the cold container (quantized at the cold dtype, with
    ``layer_write_chunk``'s keep-past-valid masking) and the hot ring takes
    a residue write — ring slot s receives the LAST valid chunk position
    ≡ s (mod H); ring slots the chunk does not cover keep their bytes (they
    hold still-hot positions of earlier chunks). k_new/v_new: (n_kv,C,hd);
    ``slot`` may be ``(layer, slot)`` for (L,B,…) stacks written in
    place."""
    lead, slot = _split_address(slot)
    C = k_new.shape[1]
    keep = (jnp.arange(C, dtype=jnp.int32) < valid_len)[None, :, None]

    def put(dst, new):
        return None if dst is None else \
            _put_chunk(dst, new, lead + (slot, 0, start, 0), keep)

    kq, ks = quantize_cold(k_new, cold_dtype)
    vq, vs = quantize_cold(v_new, cold_dtype)
    k_l, v_l = put(k_l, kq), put(v_l, vq)
    k_scale_l, v_scale_l = put(k_scale_l, ks), put(v_scale_l, vs)

    H = hot_k_l.shape[-2]
    s_idx = jnp.arange(H, dtype=jnp.int32)
    # r = (ring slot − start) mod H: chunk index of the FIRST position that
    # lands in ring slot s; the last valid one is r + H·⌊(valid−1−r)/H⌋
    r = jax.lax.rem(s_idx - jax.lax.rem(start, H) + H, H)
    i_star = jnp.clip(r + H * ((valid_len - 1 - r) // H), 0, C - 1)
    keep_h = (r < valid_len)[None, :, None]

    def put_hot(dst, new):
        return _put_chunk(dst, jnp.take(new, i_star, axis=1),  # (n_kv,H,hd)
                          lead + (slot, 0, 0, 0), keep_h)

    return (k_l, v_l, k_scale_l, v_scale_l,
            put_hot(hot_k_l, k_new), put_hot(hot_v_l, v_new))


def layer_read_slot_cold(k_l, v_l, k_scale_l, v_scale_l, slot,
                         cold_dtype: str, dtype=jnp.bfloat16):
    """``layer_read_slot`` for the COLD tier: one slot's (1,n_kv,S,hd)
    dequantized cold image, format-aware (int4 unpacks, int8 rescales,
    bf16 casts). The chunk program attends this against the per-query
    ``chunk_hot_image`` under the ``cold_boundary`` select."""
    def take(a):
        if a is None:
            return None
        return jax.lax.dynamic_slice(
            a, (slot,) + (0,) * (a.ndim - 1), (1,) + a.shape[1:])

    return cold_read(take(k_l), take(v_l), take(k_scale_l),
                     take(v_scale_l), cold_dtype, dtype)


def chunk_hot_image(hot_k_l, hot_v_l, k_new, v_new, slot, start, valid_len,
                    extent: int, dtype=jnp.bfloat16):
    """(1,n_kv,S,hd) exact-value image for the chunk program's per-query hot
    reads, built from the PRE-write ring: positions < start tile from the
    ring (the incoming chunk may overwrite exactly those ring slots), and
    positions in [start, start+valid) come from the incoming chunk itself.
    The pre-write ring holds every position >= cold_boundary(start) — a
    superset of every query's hot tail — because the hot region never
    exceeds H − 1 positions."""
    idx = jnp.arange(extent, dtype=jnp.int32)
    in_chunk = ((idx >= start) & (idx < start + valid_len))[None, None, :,
                                                            None]

    def one(h_l, new):
        H = h_l.shape[2]
        row = jax.lax.dynamic_slice(
            h_l, (slot, 0, 0, 0), (1,) + h_l.shape[1:])     # (1,n_kv,H,hd)
        tiled = jnp.take(row, jax.lax.rem(idx, H), axis=2).astype(dtype)
        placed = jax.lax.dynamic_update_slice(
            jnp.zeros_like(tiled), new[None].astype(dtype), (0, 0, start, 0))
        return jnp.where(in_chunk, placed, tiled)

    return one(hot_k_l, k_new), one(hot_v_l, v_new)


def batch_valid_mask(size: int, window: int, positions: jax.Array) -> jax.Array:
    """(B,S) bool — per-row ``slot_valid_mask`` (decode order: append→attend);
    row b attends exactly the positions its own cursor has written."""
    return jax.vmap(lambda p: slot_valid_mask(size, window, p))(positions)


def write_slot_kv(dst: KVCache, src: KVCache, slot) -> KVCache:
    """Admission: copy the batch-1 cache ``src`` (a fresh prefill) into batch
    slot ``slot`` of ``dst``. ``slot`` may be traced — ONE compiled program
    serves every slot. Seq lengths may differ (registry prefill sizes its
    cache as prompt+slack): the first min(S_src, S_dst) positions are copied,
    which covers the prompt for non-windowed caches. The cursor ``length``
    is NOT per-slot here — slotted decode threads per-row positions
    explicitly — so it is kept as max() purely as an upper bound."""
    n = min(src.k.shape[3], dst.k.shape[3])

    def put(d, s, m=None):
        if d is None:
            return None
        s = jax.lax.slice_in_dim(s, 0, m or n, axis=3).astype(d.dtype)
        return jax.lax.dynamic_update_slice(d, s, (0, slot, 0, 0, 0))

    nh = None if dst.hot_k is None \
        else min(src.hot_k.shape[3], dst.hot_k.shape[3])
    return dst._replace(k=put(dst.k, src.k), v=put(dst.v, src.v),
                        k_scale=put(dst.k_scale, src.k_scale),
                        v_scale=put(dst.v_scale, src.v_scale),
                        hot_k=put(dst.hot_k, src.hot_k, nh)
                        if dst.hot_k is not None else None,
                        hot_v=put(dst.hot_v, src.hot_v, nh)
                        if dst.hot_v is not None else None,
                        length=jnp.maximum(dst.length, src.length))


def export_slot_kv(cache: KVCache, slot):
    """Preemption swap-out: ONE batch slot's full-extent stored K/V stacks
    as a ``(k, v, k_scale, v_scale, hot_k, hot_v)`` tuple of (L,1,n_kv,S,hd)
    slices (scales (L,1,n_kv,S,1); hot rings (L,1,n_kv,H,hd); ``None``
    entries for dense/untiered caches). ``slot`` is a traced scalar — one
    compiled program swaps out every slot. Tiered victims export BOTH
    tiers: the quantized cold bytes + scales verbatim and the exact hot
    ring, so restore reproduces the tier state bit-for-bit.

    The slices are the STORED bytes — int8 caches export the quantized
    values and their per-(b,head,pos) scales verbatim, never a dequantized
    image — so a later ``import_slot_kv`` of the same tuple is
    byte-identical, the contract token-exact preemption rests on
    (DESIGN.md §7). The host keeps the full static extent and carries the
    TRUE length separately (cursors are the source of validity, exactly as
    in the chunk lane)."""
    def take(a):
        if a is None:
            return None
        return jax.lax.dynamic_slice(
            a, (0, slot, 0, 0, 0), (a.shape[0], 1) + a.shape[2:])

    return (take(cache.k), take(cache.v),
            take(cache.k_scale), take(cache.v_scale),
            take(cache.hot_k), take(cache.hot_v))


def import_slot_kv(cache: KVCache, saved, slot, valid_len) -> KVCache:
    """Preemption restore: write an ``export_slot_kv`` tuple back into
    ``slot``, masked to the sequence's TRUE length — positions
    >= ``valid_len`` keep the bytes already in the cache, mirroring
    ``layer_write_chunk``'s keep-past-valid semantics (the restore is the
    chunk lane's masked write at full width). ``slot``/``valid_len`` are
    traced scalars; the saved bytes land verbatim (stored dtype, scales
    included), so restore ∘ export is byte-identical below the cursor.
    The hot ring restores VERBATIM at full ring width: ring slots are only
    ever read for positions inside the restored row's hot region, and the
    export captured exactly the victim's pre-swap ring state."""
    k_s, v_s, ks_s, vs_s, hk_s, hv_s = saved
    S = cache.k.shape[3]
    keep = (jnp.arange(S, dtype=jnp.int32) < valid_len)\
        .reshape(1, 1, 1, S, 1)

    def put(dst, new, masked=True):
        if dst is None:
            return None
        cur = jax.lax.dynamic_slice(
            dst, (0, slot, 0, 0, 0), new.shape)
        merged = jnp.where(keep, new.astype(dst.dtype), cur) if masked \
            else new.astype(dst.dtype)
        return jax.lax.dynamic_update_slice(dst, merged, (0, slot, 0, 0, 0))

    return cache._replace(k=put(cache.k, k_s), v=put(cache.v, v_s),
                          k_scale=put(cache.k_scale, ks_s),
                          v_scale=put(cache.v_scale, vs_s),
                          hot_k=put(cache.hot_k, hk_s, masked=False)
                          if hk_s is not None else cache.hot_k,
                          hot_v=put(cache.hot_v, hv_s, masked=False)
                          if hv_s is not None else cache.hot_v,
                          length=jnp.maximum(cache.length,
                                             jnp.asarray(valid_len,
                                                         jnp.int32)))


def reset_slot(cache: KVCache, slot) -> KVCache:
    """Zero one batch slot's K/V (retire) — both tiers for tiered caches.
    Not required for correctness — masked attention never reads past a
    slot's cursor and admission overwrites the prompt region — but keeps
    retired garbage out of cache dumps and makes slot-state invariants
    checkable."""
    def zero(d):
        if d is None:
            return None
        z = jnp.zeros((d.shape[0], 1) + d.shape[2:], d.dtype)
        return jax.lax.dynamic_update_slice(d, z, (0, slot, 0, 0, 0))

    return cache._replace(k=zero(cache.k), v=zero(cache.v),
                          k_scale=zero(cache.k_scale),
                          v_scale=zero(cache.v_scale),
                          hot_k=zero(cache.hot_k), hot_v=zero(cache.hot_v))


def slot_valid_mask(size: int, window: int, query_pos: jax.Array) -> jax.Array:
    """(S,) bool — standalone form of valid_mask (decode order: append→attend)."""
    count = query_pos + 1
    idx = jnp.arange(size, dtype=jnp.int32)
    if not window:
        return idx < count
    head = jax.lax.rem(count + size - 1 - idx, size)
    p = count - 1 - head
    ok = (p >= 0) & (p <= query_pos) & (p > query_pos - window)
    return ok


def window_slots(cache: KVCache, count: jax.Array) -> jax.Array:
    """Absolute position held in each slot given ``count`` stored tokens
    (−1 if empty). Ring slot s holds the largest p < count with p ≡ s (mod W).
    """
    size = cache.k.shape[3]
    idx = jnp.arange(size, dtype=jnp.int32)
    if not cache.window:
        return jnp.where(idx < count, idx, -1)
    head = jax.lax.rem(count + size - 1 - idx, size)  # distance back from cursor
    p = count - 1 - head
    return jnp.where(p >= 0, p, -1)


def valid_mask(cache: KVCache, query_pos: jax.Array) -> jax.Array:
    """(S,) bool — slots attendable by a query at ``query_pos``, ASSUMING the
    query's own KV has been appended (decode order: append → attend).
    Window semantics inclusive: positions in [query_pos−W+1, query_pos]."""
    slots = window_slots(cache, query_pos + 1)
    ok = (slots >= 0) & (slots <= query_pos)
    if cache.window:
        ok &= slots > (query_pos - cache.window)
    return ok
