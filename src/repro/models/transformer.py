"""Decoder-only transformer (dense + MoE + VLM backbone).

One code path serves train_step (full-seq + chunked CE), prefill (full-seq,
cache write) and decode (single-token, cache read/append). Layers execute via
``lax.scan`` over stacked params (HLO size O(1) in depth — required to compile
94-layer configs on the CPU dry-run host) with ``jax.checkpoint`` remat.

The paper's execution-model choice enters ONLY through the ShardingCtx rules
(operator-centric vs sub-operator; see models/sharding.py) — the math is
identical, the collective schedule is not.
"""
from __future__ import annotations

from collections import Counter
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.kernels import use_pallas_kernel
from repro.kernels.flash_decode.flash_decode import (flash_decode_pallas,
                                                     live_tiles)
from repro.kv.cache import KVCache, init_kv_cache
from repro.models import common
from repro.models.attention import (decode_attention, flash_attention,
                                    make_attn_params, qkv_project)
from repro.models.sharding import ShardingCtx
from repro.quant.int8 import quantize_kv


# ---------------------------------------------------------------------------
# FFN (dense gated / plain MLP); MoE plugs in via models.moe
# ---------------------------------------------------------------------------

def make_ffn_params(key, cfg: ModelConfig, d_ff: Optional[int] = None) -> dict:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    dt = common.dtype_of(cfg)
    ks = jax.random.split(key, 3)
    if cfg.act == "gelu_mlp":
        return {"w_in": common.make_linear(ks[0], d, f, dt, bias=True,
                                           int8=cfg.weight_int8),
                "w_out": common.make_linear(ks[1], f, d, dt, bias=True,
                                            int8=cfg.weight_int8)}
    return {"w_gate": common.make_linear(ks[0], d, f, dt, int8=cfg.weight_int8),
            "w_up": common.make_linear(ks[1], d, f, dt, int8=cfg.weight_int8),
            "w_down": common.make_linear(ks[2], f, d, dt, int8=cfg.weight_int8)}


def ffn_apply(p: dict, x: jax.Array, cfg: ModelConfig, ctx: ShardingCtx) -> jax.Array:
    """Gated FFN. Per the paper (§4.2/Fig 6b): weights are streamed ONCE —
    both GEMVs read the same gathered activation and partial down-proj results
    merge in a single bounded-fan-in reduction (the trailing annotation)."""
    if cfg.act == "gelu_mlp":
        h = common.linear(p["w_in"], x)
        h = jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype)
        h = ctx.ann(h, "batch", "seq", "mlp")
        return common.linear(p["w_out"], h)
    up = common.linear(p["w_up"], x)
    gate = common.linear(p["w_gate"], x)
    h = ctx.ann(common.gated_act(cfg.act, up, gate), "batch", "seq", "mlp")
    return common.linear(p["w_down"], h)


def _layer_rope(cfg: ModelConfig, window) -> Optional[Tuple]:
    """The rotary table of a layer for ``qkv_project``: (inverse
    frequencies, cos/sin scale), or None for the plain table of
    ``rope_theta``. Full-attention layers take the YaRN table where the
    configuration has one: every layer when ``window`` is None, the layers
    whose traced ``window`` is 0 otherwise."""
    if cfg.yarn is None:
        return None
    freqs, scale = common.yarn_freqs(cfg.head_dim, cfg.rope_theta, cfg.yarn)
    if window is None:
        return jnp.asarray(freqs), scale
    full = window == 0
    return (jnp.where(full, freqs, common.rope_freqs(cfg.head_dim,
                                                     cfg.rope_theta)),
            jnp.where(full, scale, 1.0))


def _in_window(mask: jax.Array, query_pos: jax.Array, window) -> jax.Array:
    """``mask`` (..., S) over cache positions, kept only at the ``window``
    positions ending at each query (itself included), i.e. keys after
    ``query_pos - window``; a window of 0 keeps it whole."""
    keys = jnp.arange(mask.shape[-1], dtype=jnp.int32)
    return mask & ((window == 0) | (keys > query_pos - window))


def _windows(cfg: ModelConfig) -> Optional[jax.Array]:
    """Each layer's attention window (L,) int32 for the layer loop, or None
    where every layer attends its whole prefix."""
    if not any(cfg.layer_windows):
        return None
    if len(cfg.layer_windows) != cfg.n_layers:
        raise ValueError(f"{len(cfg.layer_windows)} layer windows for "
                         f"{cfg.n_layers} layers")
    return jnp.asarray(cfg.layer_windows, jnp.int32)


def _refuse_layer_kinds(cfg: ModelConfig, what: str):
    if any(cfg.layer_windows) or cfg.yarn is not None:
        raise ValueError(f"{what} has no per-layer attention windows or "
                         "YaRN tables; the slotted decode and chunk "
                         "programs serve them")


# ---------------------------------------------------------------------------
# Transformer block
# ---------------------------------------------------------------------------

def make_block_params(key, cfg: ModelConfig) -> dict:
    ks = jax.random.split(key, 4)
    dt = common.dtype_of(cfg)
    p = {
        "ln1": common.make_norm(cfg.norm, cfg.d_model, dt),
        "attn": make_attn_params(ks[0], cfg),
        "ln2": common.make_norm(cfg.norm, cfg.d_model, dt),
    }
    if cfg.moe is not None:
        from repro.models.moe import make_moe_params
        p["moe"] = make_moe_params(ks[1], cfg)
    else:
        p["ffn"] = make_ffn_params(ks[1], cfg)
    return p


def _held(cfg: ModelConfig) -> bool:
    """The FFN is one chip's share of an expert-parallel layer."""
    return cfg.moe is not None and cfg.moe.held_experts > 0


def _mix_ffn(p: dict, x: jax.Array, cfg: ModelConfig, ctx: ShardingCtx,
             train: bool, valid: Optional[jax.Array] = None
             ) -> Tuple[jax.Array, jax.Array]:
    """FFN half of the block; returns (out, aux): the load-balance loss, or
    for a held-expert layer the picks per held expert over the ``valid``
    (B,S) tokens (``moe.moe_held``)."""
    if _held(cfg):
        from repro.models.moe import moe_held
        return moe_held(p["moe"], x, cfg, valid)
    if cfg.moe is not None:
        from repro.models.moe import moe_ffn
        return moe_ffn(p["moe"], x, cfg, ctx, train=train)
    return ffn_apply(p["ffn"], x, cfg, ctx), jnp.zeros((), jnp.float32)


def block_full_seq(p: dict, x: jax.Array, cfg: ModelConfig, ctx: ShardingCtx,
                   positions: jax.Array, causal: bool = True,
                   window: int = 0, train: bool = True,
                   q_chunk: int = 0,
                   kv_quant_roundtrip: bool = False) -> Tuple[jax.Array, jax.Array]:
    """Full-sequence block (train/prefill path). x: (B,S,D).

    ``kv_quant_roundtrip`` (int8-KV prefill only): attend the
    quantize→dequantize image of K/V — the exact values the cache will store
    — so prefill logits are a function of what decode will actually attend.
    Without it a chunked prefill (which reads its prefix back from the int8
    cache) could not be token-exact against the monolithic program. The
    ORIGINAL fp K/V still flow to the caller: ``write_prefill`` quantizes
    them identically (same per-position scales), keeping stored bytes
    byte-for-byte what they always were."""
    from repro.models.attention import q_chunk_for
    from repro.quant.int8 import dequantize_kv
    qc = q_chunk or q_chunk_for(x.shape[1])
    h = common.apply_norm(cfg.norm, p["ln1"], x, cfg.norm_eps)
    h = ctx.ann(h, "batch", "seq", "embed")
    q, k, v = qkv_project(p["attn"], h, cfg, ctx, positions)
    k_att, v_att = k, v
    if kv_quant_roundtrip:
        k_att = dequantize_kv(*quantize_kv(k), dtype=k.dtype)
        v_att = dequantize_kv(*quantize_kv(v), dtype=v.dtype)
    o = flash_attention(q, k_att, v_att, causal, window,
                        min(qc, x.shape[1]), min(qc, x.shape[1]))
    o = ctx.ann(o, "batch", "seq", "act_heads", "head_dim")
    o = common.linear(p["attn"]["wo"], o.reshape(x.shape[0], x.shape[1], -1))
    x = ctx.ann(x + o, "batch", "seq", "embed_shard")
    h = common.apply_norm(cfg.norm, p["ln2"], x, cfg.norm_eps)
    h = ctx.ann(h, "batch", "seq", "embed")
    f, aux = _mix_ffn(p, h, cfg, ctx, train)
    if _held(cfg):
        aux = jnp.zeros((), jnp.float32)    # picks: the serving programs'
    x = ctx.ann(x + f, "batch", "seq", "embed_shard")
    return x, (q, k, v, aux)


def block_decode(p: dict, x: jax.Array, cfg: ModelConfig, ctx: ShardingCtx,
                 kv_slices: Tuple, pos: jax.Array,
                 window: int = 0) -> Tuple[jax.Array, Tuple]:
    """Single-token block over ONE layer's cache slices.
    x: (B,1,D); kv_slices = (k_l, v_l, k_scale_l, v_scale_l) with k_l
    (B,n_kv,S,hd). Returns (x', updated slices)."""
    from repro.kv.cache import layer_append, layer_read, slot_valid_mask
    B = x.shape[0]
    k_l, v_l, ks_l, vs_l = kv_slices
    positions = jnp.full((B, 1), pos, jnp.int32)
    h = common.apply_norm(cfg.norm, p["ln1"], x, cfg.norm_eps)
    h = ctx.ann(h, "batch", "seq", "embed")
    q, k, v = qkv_project(p["attn"], h, cfg, ctx, positions)
    k_l, v_l, ks_l, vs_l = layer_append(k_l, v_l, ks_l, vs_l,
                                        k[:, 0], v[:, 0], pos, window)
    kc, vc = layer_read(k_l, v_l, ks_l, vs_l, dtype=x.dtype)
    kc = ctx.ann(kc, "batch", "kv_heads", "kv_seq", "head_dim")
    vc = ctx.ann(vc, "batch", "kv_heads", "kv_seq", "head_dim")
    mask = slot_valid_mask(k_l.shape[2], window, pos)
    o = decode_attention(q[:, 0], kc, vc, mask, ctx)
    o = common.linear(p["attn"]["wo"], o.reshape(B, 1, -1))
    x = ctx.ann(x + o, "batch", "seq", "embed_shard")
    h = common.apply_norm(cfg.norm, p["ln2"], x, cfg.norm_eps)
    h = ctx.ann(h, "batch", "seq", "embed")
    f, _ = _mix_ffn(p, h, cfg, ctx, train=False)
    x = ctx.ann(x + f, "batch", "seq", "embed_shard")
    return x, (k_l, v_l, ks_l, vs_l)


_KV_LEAVES = ("k", "v", "k_scale", "v_scale", "hot_k", "hot_v")


def _slice_dims(name: str) -> Tuple:
    """Logical dims of one layer's slice of a cache leaf (``cache_specs``
    without the layer dim); the hot ring's dim 2 is the ring, not kv_seq."""
    if name.startswith("hot"):
        return ("batch", "kv_heads", None, None)
    return ("batch", "kv_heads", "kv_seq", None)


def _layer_slices(cache: KVCache, layer, ctx: ShardingCtx) -> Tuple:
    """Layer ``layer``'s slices of the (k, v, k_scale, v_scale, hot_k,
    hot_v) stacks (``None`` where the cache has no such leaf), pinned to the
    cache's layout: on a mesh the pin keeps a later per-slot slice from
    folding into one slice of the whole stack, which GSPMD would gather."""
    out = []
    for name in _KV_LEAVES:
        a = getattr(cache, name)
        if a is not None:
            a = ctx.ann(jax.lax.dynamic_index_in_dim(a, layer, 0,
                                                     keepdims=False),
                        *_slice_dims(name))
        out.append(a)
    return tuple(out)


def _write_kv(ctx: ShardingCtx, cache: KVCache, write, rows: Tuple = (),
              shared: Tuple = ()) -> KVCache:
    """Apply ``write(cache, rows, shared, row0) -> cache`` to the KV stacks
    in place. Where the mesh shards the batch, each batch shard runs
    ``write`` on its own stack shard, its own ``rows`` (operands that lead
    with the batch dim) and the replicated ``shared`` operands; ``row0`` is
    the shard's first global row (0 off a mesh). A slot's write reads back
    the window it changes, and GSPMD gathers the whole operand of a slice
    taken along a sharded dim: here every layer of the stacks."""
    names = tuple(n for n in _KV_LEAVES if getattr(cache, n) is not None)

    def run(stacks, rows, shared, row0):
        c = write(cache._replace(length=None, **dict(zip(names, stacks))),
                  rows, shared, row0)
        return tuple(getattr(c, n) for n in names)

    stacks = tuple(getattr(cache, n) for n in names)
    dp = ()
    if ctx.mesh is not None and not ctx.mesh.empty:
        ax = ctx.spec((None, "batch"), cache.k.shape[:2])[1]
        dp = () if ax is None else (tuple(ax) if isinstance(ax, tuple)
                                    else (ax,))
    if not dp:
        out = run(stacks, rows, shared, 0)
    else:
        from jax.sharding import PartitionSpec as P
        ax = dp if len(dp) > 1 else dp[0]
        n_rows = cache.k.shape[1] // int(
            np.prod([ctx.mesh.shape[a] for a in dp]))
        out = jax.shard_map(
            lambda s, r, sh: run(s, r, sh, jax.lax.axis_index(dp) * n_rows),
            mesh=ctx.mesh, in_specs=(P(None, ax), P(ax), P()),
            out_specs=P(None, ax), axis_names=frozenset(dp),
            check_vma=False)(stacks, rows, shared)
    return cache._replace(**dict(zip(names, out)))


LIVE_TILE_BYTES = 256 * 1024


def _kernel_interpret() -> Optional[bool]:
    """How the slotted decode runs the flash-decode kernel: None where it
    attends with the jnp form (off a TPU), else the kernel's ``interpret``
    flag (False: the kernel runs natively on a TPU)."""
    return False if use_pallas_kernel(None, False) else None


def live_range_block(cache: KVCache, ctx: ShardingCtx, kv_bucket: int = 0,
                     kv_shards: int = 1) -> int:
    """The tile, in positions, in which the slotted decode reads each row's
    live KV range through the flash-decode kernel; 0 where it reads every
    row's full extent with the jnp form instead. The kernel serves a flat
    cache (bf16, or int8 with scales) on one device, read whole: not the
    tiered, ring, bucketed or split-KV reads, and not off a TPU. Its tile
    copies at most ``LIVE_TILE_BYTES`` of K (all KV heads of a row at
    once), 128 to 1024 positions that divide the extent."""
    if (_kernel_interpret() is None or kv_bucket or kv_shards != 1
            or (ctx.mesh is not None and ctx.mesh.size > 1)):
        return 0
    return live_range_tile(cache)


def live_range_tile(cache: KVCache) -> int:
    """``live_range_block``'s tile for a flat cache of this shape on a
    TPU, read whole on one device; 0 for a tiered or ring cache, or an
    extent no tile of at least 128 positions divides."""
    if cache.is_tiered or cache.window:
        return 0
    _, _, n_kv, S, hd = cache.k.shape
    per_pos = n_kv * hd * cache.k.dtype.itemsize
    bs = 128
    while (bs < 1024 and bs * 2 * per_pos <= LIVE_TILE_BYTES
           and S % (bs * 2) == 0):
        bs *= 2
    return bs if S % bs == 0 else 0


def decode_kv_read(cfg: ModelConfig, cache: KVCache, ctx: ShardingCtx,
                   positions: np.ndarray, active: np.ndarray,
                   kv_bucket: int = 0, kv_shards: int = 1) -> float:
    """KV positions that one slotted decode micro-step at cursors
    ``positions`` reads, summed over rows and averaged over layers: on the
    kernel's path the positions its tiles cover (``live_tiles`` of each
    active row's ``[lo, hi)``, a windowed layer from its lower bound), else
    every row's whole read extent. ``cache`` may be an abstract value: only
    its structure is read."""
    bs = live_range_block(cache, ctx, kv_bucket, kv_shards)
    L, B, _, S, _ = cache.k.shape
    if not bs:
        return float(B * (kv_bucket or S))
    positions = np.asarray(positions, np.int64)
    hi = np.clip(np.where(active, positions + 1, 0), 0, S)
    total = 0
    for w, n in Counter(cfg.layer_windows or (0,) * L).items():
        lo = np.clip(positions + 1 - w, 0, S) if w else np.zeros_like(hi)
        total += n * int(live_tiles(lo, hi, bs)[1].sum()) * bs
    return total / L


def block_decode_slotted(p: dict, x: jax.Array, cfg: ModelConfig,
                         ctx: ShardingCtx, cache: KVCache, layer,
                         positions: jax.Array, active: jax.Array,
                         window: int = 0, kv_bucket: int = 0,
                         kv_shards: int = 1, attn_window=None
                         ) -> Tuple[jax.Array, KVCache, Optional[jax.Array]]:
    """``block_decode`` with PER-ROW cursors (continuous batching) for layer
    ``layer`` of the carried ``cache`` stacks: row b writes its new K/V
    into the stacks in place at (layer, b, positions[b])
    (``layer_append_slotted`` / ``layer_append_tiered``), then attends
    over the row's own prefix. Inactive rows write nothing (their KV stays
    byte-identical); their activations still flow — static shapes — but
    the engine masks the resulting logits. Returns (x', cache', picks): a
    held-expert layer's picks per held expert over the active rows, else
    None.

    Where ``live_range_block`` names a tile (a flat cache on a TPU, read
    whole), the flash-decode kernel reads each active row's live range
    ``[lo, hi)`` (``hi`` = cursor + 1, ``lo`` the window's first position)
    straight from the carried stacks, and an inactive row reads nothing.
    Otherwise the layer's slice is read back and attended with the jnp
    form over its whole extent.

    ``attn_window`` (traced, None for a stack of full layers): this layer
    attends only the ``attn_window`` positions ending at each row's cursor
    (0 → all of them), masked over the full-extent stack, and takes its
    kind's rotary table (``_layer_rope``).

    ``kv_bucket`` > 0 (non-windowed caches only) reads and attends only the
    first ``kv_bucket`` cache positions — the length-aware decode path. The
    caller must guarantee max(positions) < kv_bucket; the serving engine
    picks the bucket per macro-step from the live cursors.

    ``kv_shards`` > 1 (static, non-windowed only): split-KV flash decode —
    the bucketed read returns shard-major KV (``layer_read_shards``) and
    ``decode_attention_split`` combines the per-shard partial softmax
    statistics with the LSE merge. Token-exact vs the sequential walk; the
    engine guarantees every bucket divides by ``kv_shards``.

    A twin of ``block_decode`` (shared cursor, per-layer slices) rather
    than its replacement: drain serving, the hybrid family and pipeline
    decode keep that form. Keep the math in sync — the equality
    decode_step == decode_step_slotted under a uniform cursor is enforced by
    tests/test_serving_scheduler.py."""
    from repro.kv.cache import (batch_valid_mask, layer_append_slotted,
                                layer_append_tiered, layer_read_bucket,
                                layer_read_shards, layer_read_tiered,
                                layer_read_tiered_shards)
    from repro.models.attention import decode_attention_split
    tiered = cache.is_tiered
    if window:
        kv_bucket = 0                       # ring buffers have no prefix order
        kv_shards = 1                       # ... and no contiguous shard cut
    h = common.apply_norm(cfg.norm, p["ln1"], x, cfg.norm_eps)
    h = ctx.ann(h, "batch", "seq", "embed")
    q, k, v = qkv_project(p["attn"], h, cfg, ctx, positions[:, None],
                          _layer_rope(cfg, attn_window))

    def write(c, rows, shared, _row0):
        k_new, v_new, pos, act = rows
        at = (shared[0], pos)
        if tiered:
            kv = layer_append_tiered(c.k, c.v, c.k_scale, c.v_scale,
                                     c.hot_k, c.hot_v, k_new, v_new, at,
                                     cfg.kv_cold_dtype, act)
        else:
            kv = layer_append_slotted(c.k, c.v, c.k_scale, c.v_scale, k_new,
                                      v_new, at, window, act) + (None, None)
        return c._replace(**dict(zip(_KV_LEAVES, kv)))

    cache = _write_kv(ctx, cache, write, (k[:, 0], v[:, 0], positions, active),
                      (layer,))
    bs = live_range_block(cache, ctx, kv_bucket, kv_shards)
    if bs:
        # each active row's live range [lo, hi), read from the carried
        # stacks in place: no layer slice is made
        hi = jnp.where(active, positions + 1, 0)
        lo = jnp.zeros_like(hi) if attn_window is None else jnp.where(
            attn_window > 0, jnp.maximum(hi - attn_window, 0), 0)
        o = flash_decode_pallas(q[:, 0], cache.k, cache.v, cache.k_scale,
                                cache.v_scale, None, block_s=bs,
                                interpret=_kernel_interpret(), lo=lo, hi=hi,
                                layer=layer).astype(x.dtype)
        return _decode_out(p, x, o, cfg, ctx, cache, active)
    k_l, v_l, ks_l, vs_l, hk_l, hv_l = _layer_slices(cache, layer, ctx)
    if tiered:
        counts = positions + 1              # append→attend: row b has p+1 toks
        if kv_shards > 1:
            kc, vc = layer_read_tiered_shards(
                k_l, v_l, ks_l, vs_l, hk_l, hv_l, counts, kv_bucket,
                kv_shards, cfg.hot_window, cfg.kv_cold_block,
                cfg.kv_cold_dtype, dtype=x.dtype)
        else:
            kc, vc = layer_read_tiered(
                k_l, v_l, ks_l, vs_l, hk_l, hv_l, counts, kv_bucket,
                cfg.hot_window, cfg.kv_cold_block, cfg.kv_cold_dtype,
                dtype=x.dtype)
    else:
        if kv_shards > 1:
            kc, vc = layer_read_shards(k_l, v_l, ks_l, vs_l, kv_bucket,
                                       kv_shards, dtype=x.dtype)
        else:
            kc, vc = layer_read_bucket(k_l, v_l, ks_l, vs_l, kv_bucket,
                                       dtype=x.dtype)
    if kv_shards > 1:
        kc = ctx.ann(kc, "batch", "kv_heads", "kv_shard", "kv_seq",
                     "head_dim")
        vc = ctx.ann(vc, "batch", "kv_heads", "kv_shard", "kv_seq",
                     "head_dim")
        mask = batch_valid_mask(kc.shape[2] * kc.shape[3], window, positions)
    else:
        kc = ctx.ann(kc, "batch", "kv_heads", "kv_seq", "head_dim")
        vc = ctx.ann(vc, "batch", "kv_heads", "kv_seq", "head_dim")
        mask = batch_valid_mask(kc.shape[2], window, positions)    # (B,Sb)
    if attn_window is not None:
        mask = _in_window(mask, positions[:, None], attn_window)
    attend = decode_attention_split if kv_shards > 1 else decode_attention
    o = attend(q[:, 0], kc, vc, mask, ctx)
    return _decode_out(p, x, o, cfg, ctx, cache, active)


def _decode_out(p: dict, x: jax.Array, o: jax.Array, cfg: ModelConfig,
                ctx: ShardingCtx, cache: KVCache, active: jax.Array
                ) -> Tuple[jax.Array, KVCache, Optional[jax.Array]]:
    """The rest of ``block_decode_slotted`` after attention ``o`` (B,Hq,hd):
    output projection, FFN or held experts, residuals."""
    o = common.linear(p["attn"]["wo"], o.reshape(x.shape[0], 1, -1))
    x = ctx.ann(x + o, "batch", "seq", "embed_shard")
    h = common.apply_norm(cfg.norm, p["ln2"], x, cfg.norm_eps)
    h = ctx.ann(h, "batch", "seq", "embed")
    f, aux = _mix_ffn(p, h, cfg, ctx, train=False, valid=active[:, None])
    x = ctx.ann(x + f, "batch", "seq", "embed_shard")
    return x, cache, (aux if _held(cfg) else None)


def block_prefill_chunk(p: dict, x: jax.Array, cfg: ModelConfig,
                        ctx: ShardingCtx, cache: KVCache, layer,
                        slot: jax.Array, start: jax.Array,
                        valid_len: jax.Array, attn_window=None
                        ) -> Tuple[jax.Array, KVCache, Optional[jax.Array]]:
    """Chunk-prefill block for layer ``layer`` of the carried ``cache``
    stacks (DESIGN.md §7 chunked-prefill lane). x: (1,C,D) — slot
    ``slot``'s prompt chunk with absolute positions [start, start+C).
    Writes the chunk's K/V in place at (layer, slot, start)
    (``layer_write_chunk``; positions >= valid_len are last-chunk padding
    and never touch the cache), reads the slot's full prefix back from the
    STORED buffers (int8 caches dequantize — the same values every later
    decode step will attend) and runs causal chunk attention against it.
    slot/start/valid_len are traced: one compiled program serves every
    chunk of every prompt. Non-ring caches only (ring order has no stable
    per-position offset to write at); a per-layer ``attn_window`` masks the
    full-extent stack as in ``block_decode_slotted``. Returns (x', cache',
    picks): a held-expert layer's picks over the chunk's valid tokens, else
    None."""
    from repro.kv.cache import (chunk_hot_image, cold_boundary,
                                layer_read_slot, layer_read_slot_cold,
                                layer_write_chunk, layer_write_chunk_tiered)
    from repro.models.attention import chunk_attention, chunk_attention_tiered
    _, C, _ = x.shape
    positions = start + jnp.arange(C, dtype=jnp.int32)[None]          # (1,C)
    h = common.apply_norm(cfg.norm, p["ln1"], x, cfg.norm_eps)
    h = ctx.ann(h, "batch", "seq", "embed")
    q, k, v = qkv_project(p["attn"], h, cfg, ctx, positions,
                          _layer_rope(cfg, attn_window))
    S = cache.k.shape[3]
    k_ch = jnp.swapaxes(k[0], 0, 1)                              # (n_kv,C,hd)
    v_ch = jnp.swapaxes(v[0], 0, 1)
    # causal over absolute positions: query i attends cache slots <= start+i
    # (padding queries i >= valid_len attend zeros/stale slots — their
    # outputs are discarded; valid queries only ever reach real positions)
    mask = jnp.arange(S, dtype=jnp.int32)[None, :] \
        <= positions[0][:, None]                                      # (C,S)
    if attn_window is not None:
        mask = _in_window(mask, positions[0][:, None], attn_window)
    tiered = cache.is_tiered
    if tiered:
        # exact hot image from the PRE-write ring + the incoming chunk (the
        # write below may overwrite exactly the ring slots early queries'
        # hot tails live in)
        *_, hk_l, hv_l = _layer_slices(cache, layer, ctx)
        kh, vh = chunk_hot_image(hk_l, hv_l, k_ch, v_ch, slot, start,
                                 valid_len, S, dtype=x.dtype)

    def write(c, _rows, shared, row0):
        # stage the chunk into the slot's row (both tiers when tiered); a
        # batch shard that does not hold the slot rewrites nothing
        layer_, slot_, start_, valid_, k_ch_, v_ch_ = shared
        local = slot_ - row0
        rows = c.k.shape[1]
        valid_ = jnp.where((local >= 0) & (local < rows), valid_, 0)
        at = (layer_, jnp.clip(local, 0, rows - 1))
        if tiered:
            kv = layer_write_chunk_tiered(c.k, c.v, c.k_scale, c.v_scale,
                                          c.hot_k, c.hot_v, k_ch_, v_ch_, at,
                                          start_, valid_, cfg.kv_cold_dtype)
        else:
            kv = layer_write_chunk(c.k, c.v, c.k_scale, c.v_scale, k_ch_,
                                   v_ch_, at, start_, valid_) + (None, None)
        return c._replace(**dict(zip(_KV_LEAVES, kv)))

    cache = _write_kv(ctx, cache, write,
                      shared=(layer, slot, start, valid_len, k_ch, v_ch))
    k_l, v_l, ks_l, vs_l, _, _ = _layer_slices(cache, layer, ctx)
    if tiered:
        kc, vc = layer_read_slot_cold(k_l, v_l, ks_l, vs_l, slot,
                                      cfg.kv_cold_dtype, dtype=x.dtype)
        kh = ctx.ann(kh, "batch", "kv_heads", "kv_seq", "head_dim")
        vh = ctx.ann(vh, "batch", "kv_heads", "kv_seq", "head_dim")
        kc = ctx.ann(kc, "batch", "kv_heads", "kv_seq", "head_dim")
        vc = ctx.ann(vc, "batch", "kv_heads", "kv_seq", "head_dim")
        # per-QUERY demotion boundary: query i has count start+i+1 tokens
        hot_mask = (jnp.arange(S, dtype=jnp.int32)[None, :] >=
                    cold_boundary(positions[0] + 1, cfg.hot_window,
                                  cfg.kv_cold_block)[:, None])[None]  # (1,C,S)
        o = chunk_attention_tiered(q, kh, vh, kc, vc, hot_mask, mask, ctx)
    else:
        kc, vc = layer_read_slot(k_l, v_l, ks_l, vs_l, slot, dtype=x.dtype)
        kc = ctx.ann(kc, "batch", "kv_heads", "kv_seq", "head_dim")
        vc = ctx.ann(vc, "batch", "kv_heads", "kv_seq", "head_dim")
        o = chunk_attention(q, kc, vc, mask, ctx)
    o = common.linear(p["attn"]["wo"], o.reshape(1, C, -1))
    x = ctx.ann(x + o, "batch", "seq", "embed_shard")
    h = common.apply_norm(cfg.norm, p["ln2"], x, cfg.norm_eps)
    h = ctx.ann(h, "batch", "seq", "embed")
    f, aux = _mix_ffn(p, h, cfg, ctx, train=False,
                      valid=(jnp.arange(C) < valid_len)[None])
    x = ctx.ann(x + f, "batch", "seq", "embed_shard")
    return x, cache, (aux if _held(cfg) else None)


# ---------------------------------------------------------------------------
# Whole-model parameter init
# ---------------------------------------------------------------------------

def init_params(key, cfg: ModelConfig) -> Dict[str, Any]:
    ks = jax.random.split(key, 4)
    dt = common.dtype_of(cfg)
    params: Dict[str, Any] = {
        "embed": common.make_embedding(ks[0], cfg.vocab_size, cfg.d_model, dt),
        "blocks": common.stacked_init(
            ks[1], cfg.n_layers, lambda k: make_block_params(k, cfg)),
        "ln_f": common.make_norm(cfg.norm, cfg.d_model, dt),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = common.make_embedding(ks[2], cfg.vocab_size,
                                                  cfg.d_model, dt)
    if cfg.pos == "learned":
        # sized for the largest decode cell (+slack for appended tokens)
        params["pos_embed"] = common.dense_init(
            ks[3], (32768 + 256, cfg.d_model), dt, fan_in=1)
    return params


def unembed_table(params, cfg: ModelConfig) -> jax.Array:
    return (params["embed"] if cfg.tie_embeddings else params["unembed"])["table"]


# ---------------------------------------------------------------------------
# Full-sequence forward (train / prefill)
# ---------------------------------------------------------------------------

def forward_hidden(params, tokens: jax.Array, cfg: ModelConfig,
                   ctx: ShardingCtx, train: bool,
                   vision_embeds: Optional[jax.Array] = None,
                   collect_kv: bool = False):
    """tokens: (B,S_text). Returns (hidden (B,S,D), aux_loss[, kv list])."""
    _refuse_layer_kinds(cfg, "the full-sequence forward")
    x = common.embed(params["embed"], tokens, ctx)
    if vision_embeds is not None:                     # VLM stub frontend
        x = jnp.concatenate([vision_embeds.astype(x.dtype), x], axis=1)
        x = ctx.ann(x, "batch", "seq", "embed")
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    if cfg.pos == "learned":
        x = x + params["pos_embed"][:S][None].astype(x.dtype)
    elif cfg.pos == "sinusoidal":
        x = x + common.sinusoidal_pos(S, cfg.d_model)[None].astype(x.dtype)

    # int8-KV prefill: attention sees the quantized image of K/V (what the
    # cache stores) so prefill logits and chunked-prefill logits agree
    roundtrip = collect_kv and not train and cfg.kv_dtype == "int8"

    def _blk(lp, h):
        y, extras = block_full_seq(lp, h, cfg, ctx, positions, causal=True,
                                   train=train,
                                   kv_quant_roundtrip=roundtrip)
        q, k, v, a = extras
        return y, (k, v, None, a)

    if train:
        _blk_r = jax.checkpoint(_blk,
                                policy=jax.checkpoint_policies.nothing_saveable)
    else:
        _blk_r = _blk

    def scan_body(carry, lp):
        h, aux = carry
        y, (k_, v_, _, a) = _blk_r(lp, h)
        out = (k_, v_) if collect_kv else None
        return (y, aux + a), out

    (x, aux), kvs = jax.lax.scan(scan_body, (x, jnp.zeros((), jnp.float32)),
                                 params["blocks"], unroll=common.scan_unroll())
    x = common.apply_norm(cfg.norm, params["ln_f"], x, cfg.norm_eps)
    x = ctx.ann(x, "batch", "seq", "embed")
    if collect_kv:
        return x, aux, kvs
    return x, aux


# ---------------------------------------------------------------------------
# Step functions
# ---------------------------------------------------------------------------

def loss_fn(params, batch: Dict[str, jax.Array], cfg: ModelConfig,
            ctx: ShardingCtx) -> jax.Array:
    tokens, labels = batch["tokens"], batch["labels"]
    vis = batch.get("vision_embeds")
    x, aux = forward_hidden(params, tokens, cfg, ctx, train=True,
                            vision_embeds=vis)
    if vis is not None:
        x = x[:, vis.shape[1]:]                      # loss over text positions
    table = unembed_table(params, cfg)
    ce = common.chunked_ce_loss(table, x, labels, ctx,
                                chunk=common.ce_chunk(x.shape[1]))
    return ce + 0.01 * aux


def prefill(params, tokens: jax.Array, cfg: ModelConfig, ctx: ShardingCtx,
            cache: KVCache, vision_embeds: Optional[jax.Array] = None
            ) -> Tuple[KVCache, jax.Array]:
    """Encode context, fill the cache, return last-position logits."""
    x, _, kvs = forward_hidden(params, tokens, cfg, ctx, train=False,
                               vision_embeds=vision_embeds, collect_kv=True)
    k_all, v_all = kvs                                # (L,B,S,n_kv,hd)
    k_all = jnp.swapaxes(k_all, 2, 3)                 # (L,B,n_kv,S,hd)
    v_all = jnp.swapaxes(v_all, 2, 3)
    S = k_all.shape[3]
    cache = write_prefill(cache, k_all, v_all, S)
    table = unembed_table(params, cfg)
    logits = common.unembed_logits(table, x[:, -1:, :], ctx)
    return cache, logits


def write_prefill(cache: KVCache, k_all, v_all, S: int) -> KVCache:
    """Bulk-write a prefilled context into the cache (window-aware)."""
    if cache.is_tiered:
        raise ValueError(
            "monolithic write_prefill does not support tiered caches — the "
            "serving engine routes tiered admissions through the chunk "
            "program (full-width), which stages both tiers")
    size = cache.k.shape[3]
    if cache.window and S > size:
        k_all = k_all[:, :, :, S - size:, :]
        v_all = v_all[:, :, :, S - size:, :]
        # ring alignment: slot of position p is p % size; after S tokens the
        # oldest kept position is S-size ≡ (S-size) % size. Roll so that
        # slot order matches position % size.
        shift = (S - size) % size
        k_all = jnp.roll(k_all, shift, axis=3)
        v_all = jnp.roll(v_all, shift, axis=3)
    if cache.is_quantized:
        kq, ks = quantize_kv(k_all)
        vq, vs = quantize_kv(v_all)
        k = jax.lax.dynamic_update_slice(cache.k, kq, (0, 0, 0, 0, 0))
        v = jax.lax.dynamic_update_slice(cache.v, vq, (0, 0, 0, 0, 0))
        k_s = jax.lax.dynamic_update_slice(cache.k_scale, ks, (0, 0, 0, 0, 0))
        v_s = jax.lax.dynamic_update_slice(cache.v_scale, vs, (0, 0, 0, 0, 0))
        return cache._replace(k=k, v=v, k_scale=k_s, v_scale=v_s,
                              length=jnp.asarray(S, jnp.int32))
    k = jax.lax.dynamic_update_slice(cache.k, k_all.astype(cache.k.dtype),
                                     (0, 0, 0, 0, 0))
    v = jax.lax.dynamic_update_slice(cache.v, v_all.astype(cache.v.dtype),
                                     (0, 0, 0, 0, 0))
    return cache._replace(k=k, v=v, length=jnp.asarray(S, jnp.int32))


def decode_step(params, cache: KVCache, tokens: jax.Array, cfg: ModelConfig,
                ctx: ShardingCtx) -> Tuple[KVCache, jax.Array]:
    """tokens: (B,) last emitted token ids → (cache', logits (B,1,V)).

    Drain serving's shared-cursor step. Its layer scan still takes the
    per-layer cache slices as xs and emits the updated slices as ys, which
    rebuilds both stacks every step; the serving programs carry the stacks
    instead (``_layer_loop``)."""
    _refuse_layer_kinds(cfg, "the shared-cursor decode step")
    x = common.embed(params["embed"], tokens[:, None], ctx)
    pos = cache.length
    if cfg.pos == "learned":
        x = x + jax.lax.dynamic_index_in_dim(
            params["pos_embed"], pos, 0, keepdims=True)[None].astype(x.dtype)
    quant = cache.is_quantized

    def body(h, xs):
        if quant:
            lp, k_l, v_l, ks_l, vs_l = xs
        else:
            lp, k_l, v_l = xs
            ks_l = vs_l = None
        h, (k_l, v_l, ks_l, vs_l) = block_decode(
            lp, h, cfg, ctx, (k_l, v_l, ks_l, vs_l), pos, window=cache.window)
        ys = (k_l, v_l, ks_l, vs_l) if quant else (k_l, v_l)
        return h, ys

    xs = (params["blocks"], cache.k, cache.v) + \
        ((cache.k_scale, cache.v_scale) if quant else ())
    x, ys = jax.lax.scan(body, x, xs, unroll=common.scan_unroll())
    if quant:
        k_new, v_new, ks_new, vs_new = ys
    else:
        (k_new, v_new), (ks_new, vs_new) = ys, (None, None)
    cache = cache._replace(k=k_new, v=v_new, k_scale=ks_new, v_scale=vs_new,
                           length=pos + 1)
    x = common.apply_norm(cfg.norm, params["ln_f"], x, cfg.norm_eps)
    logits = common.unembed_logits(unembed_table(params, cfg), x, ctx)
    return cache, logits


def _layer_loop(params, x: jax.Array, cache: KVCache, block,
                windows: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, KVCache, Any]:
    """The layer loop of the serving programs (slotted decode and the chunk
    program). The KV stacks ride in the scan CARRY beside the hidden state,
    never as xs/ys: ``block(lp, h, cache, l[, window]) -> (h, cache, ys)``
    writes layer l's new tokens into the stacks in place at index l and
    reads its own slice back. A donated cache therefore aliases from
    program entry to exit — no stack is built, zeroed, sliced out per layer
    or copied back. Serves every cache variant (bf16, int8 + scales, tiered
    hot/cold, windowed ring, split-KV reads). ``windows`` (L,), each
    layer's attention window (``_windows``), is scanned beside its params
    and passed to ``block``; None passes nothing. Returns (x, cache, the
    layers' ys stacked; None where the blocks return None)."""
    layers = jnp.arange(cache.k.shape[0], dtype=jnp.int32)
    xs = (params["blocks"], layers) + (() if windows is None else (windows,))

    def body(carry, xs):
        h, c = carry
        h, c, ys = block(xs[0], h, c, *xs[1:])
        return (h, c), ys

    (x, cache), ys = jax.lax.scan(body, (x, cache), xs,
                                  unroll=common.scan_unroll())
    return x, cache, ys


def decode_step_slotted(params, cache: KVCache, tokens: jax.Array,
                        positions: jax.Array, active: jax.Array,
                        cfg: ModelConfig, ctx: ShardingCtx,
                        kv_bucket: int = 0,
                        kv_shards: int = 1) -> Tuple[KVCache, jax.Array]:
    """Continuous-batching decode step (DESIGN.md §7). tokens/positions/
    active: (B,). Mirrors ``decode_step`` but each row carries its OWN
    cursor: row b appends at positions[b] and attends 0..positions[b]; the
    shared ``cache.length`` is kept only as an upper bound. Equal to
    ``decode_step`` when all rows share one cursor and are active. The KV
    stacks are carried through ``_layer_loop`` and written in place.
    ``kv_bucket``: static length-aware KV extent; ``kv_shards``: static
    split-KV shard count (see block_decode_slotted). A held-expert
    configuration returns a third output, the picks per layer and held
    expert (L, held) int32 of the active rows."""
    x = common.embed(params["embed"], tokens[:, None], ctx)
    if cfg.pos == "learned":
        x = x + jnp.take(params["pos_embed"], positions,
                         axis=0)[:, None].astype(x.dtype)

    def block(lp, h, c, layer, attn_window=None):
        return block_decode_slotted(
            lp, h, cfg, ctx, c, layer, positions, active,
            window=cache.window, kv_bucket=kv_bucket, kv_shards=kv_shards,
            attn_window=attn_window)

    x, cache, picks = _layer_loop(params, x, cache, block, _windows(cfg))
    new_len = jnp.maximum(
        cache.length, jnp.max(jnp.where(active, positions, 0)) + 1)
    cache = cache._replace(length=new_len)
    x = common.apply_norm(cfg.norm, params["ln_f"], x, cfg.norm_eps)
    logits = common.unembed_logits(unembed_table(params, cfg), x, ctx)
    return (cache, logits) if picks is None else (cache, logits, picks)


def prefill_chunk(params, cache: KVCache, tokens: jax.Array, slot: jax.Array,
                  start: jax.Array, valid_len: jax.Array, cfg: ModelConfig,
                  ctx: ShardingCtx) -> Tuple[KVCache, jax.Array]:
    """Chunked prefill: ONE fixed-(1,C) program reused for every chunk of
    every prompt (DESIGN.md §7 chunked-prefill lane). tokens: (1,C) — the
    chunk of slot ``slot``'s prompt covering absolute positions
    [start, start+valid_len); chunk positions >= valid_len are last-chunk
    padding (masked out of both the KV write and the returned logits).
    Returns (cache', logits (1,1,V)) — logits at the chunk's LAST VALID
    position, meaningful only on a prompt's final chunk (the first decoded
    token). slot/start/valid_len are traced scalars: zero retracing across
    chunks, prompts and slots. The KV stacks are carried through
    ``_layer_loop`` and written in place. A held-expert configuration
    returns a third output, the picks per layer and held expert (L, held)
    int32 of the chunk's valid tokens."""
    if cache.window:
        raise ValueError(
            "chunked prefill refuses a ring cache (KVCache.window > 0): ring "
            "order has no per-position write offset. Per-layer windows "
            "(ModelConfig.layer_windows) mask a full-extent cache instead")
    x = common.embed(params["embed"], tokens, ctx)
    C = tokens.shape[1]
    positions = start + jnp.arange(C, dtype=jnp.int32)
    if cfg.pos == "learned":
        x = x + jnp.take(params["pos_embed"], positions,
                         axis=0)[None].astype(x.dtype)
    elif cfg.pos == "sinusoidal":
        table = common.sinusoidal_pos(cache.k.shape[3], cfg.d_model)
        x = x + jnp.take(table, positions, axis=0)[None].astype(x.dtype)

    # pin the cache stacks to their planned layout at program ENTRY: GSPMD
    # infers each program's cache placement independently, and on a
    # data-sharded mesh the chunk program compiled its cache input
    # batch-REPLICATED while the decode programs compiled it batch-sharded —
    # one full-cache reshard per admission boundary on the donated buffer
    # (caught by the repro.analysis residency pass; invisible at data=1)
    def pin(a, *dims):
        return None if a is None else ctx.ann(a, None, *dims)

    kv = ("batch", "kv_heads", "kv_seq", "head_dim")
    cache = cache._replace(
        k=pin(cache.k, *kv), v=pin(cache.v, *kv),
        k_scale=pin(cache.k_scale, *kv[:3], None),
        v_scale=pin(cache.v_scale, *kv[:3], None),
        hot_k=pin(cache.hot_k, "batch", "kv_heads", None, "head_dim"),
        hot_v=pin(cache.hot_v, "batch", "kv_heads", None, "head_dim"))

    def block(lp, h, c, layer, attn_window=None):
        return block_prefill_chunk(lp, h, cfg, ctx, c, layer, slot, start,
                                   valid_len, attn_window)

    x, cache, picks = _layer_loop(params, x, cache, block, _windows(cfg))
    cache = cache._replace(length=jnp.maximum(cache.length,
                                              start + valid_len))
    x = common.apply_norm(cfg.norm, params["ln_f"], x, cfg.norm_eps)
    last = jax.lax.dynamic_slice_in_dim(x, valid_len - 1, 1, axis=1)
    logits = common.unembed_logits(unembed_table(params, cfg), last, ctx)
    return (cache, logits) if picks is None else (cache, logits, picks)


def make_cache(cfg: ModelConfig, batch: int, max_len: int,
               window: int = 0) -> KVCache:
    tiered = cfg.hot_window > 0
    return init_kv_cache(cfg.n_layers, batch, cfg.n_kv_heads, max_len,
                         cfg.head_dim, dtype=common.dtype_of(cfg),
                         quantized=(cfg.kv_dtype == "int8"), window=window,
                         hot_window=cfg.hot_window if tiered else 0,
                         cold_block=cfg.kv_cold_block if tiered else 0,
                         cold_dtype=cfg.kv_cold_dtype if tiered
                         else "bfloat16")
