"""The serving programs write KV in place.

``decode_step_slotted`` and ``prefill_chunk`` carry the (L,B,n_kv,S,hd) KV
stacks through the layer loop and write each layer's new tokens into them at
the layer's index. Before, the stacks went through the layer scan as xs/ys
and each row's append was a select over its whole layer slice, which made
the compiled programs rebuild and copy the stacks every micro-step.

- Exactness: over every cache variant the slotted path serves, the decode
  block and the chunk program give bit-identical tokens, cursors and cache
  leaves to the xs/ys layer loop, which is kept below verbatim as the
  reference (its KV write helpers included).
- Structure: in the lowered programs no scan takes or emits a KV stack as
  xs/ys and no select has an operand of a layer slice's shape, so a
  whole-cache copy cannot come back unnoticed.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.jaxpr_walk import iter_eqns
from repro.configs.registry import ASSIGNED
from repro.kv.cache import (batch_valid_mask, chunk_hot_image, cold_boundary,
                            layer_read_bucket, layer_read_shards,
                            layer_read_slot, layer_read_slot_cold,
                            layer_read_tiered, layer_read_tiered_shards,
                            quantize_cold)
from repro.models import NULL_CTX, build_model
from repro.models import common
from repro.models.attention import (chunk_attention, chunk_attention_tiered,
                                    decode_attention, decode_attention_split,
                                    qkv_project)
from repro.models.registry import make_decode_block
from repro.models.transformer import _mix_ffn, unembed_table
from repro.quant.int8 import quantize_kv

B, S, T, C = 4, 32, 4, 8


# ---------------------------------------------------------------------------
# Reference: the xs/ys layer loop and its KV write helpers, as they were
# ---------------------------------------------------------------------------

def ref_layer_append_slotted(k_l, v_l, k_scale_l, v_scale_l, k_new, v_new,
                             positions, window, active=None):
    size = k_l.shape[2]
    slots = jax.lax.rem(positions, size) if window else positions
    if active is None:
        active = jnp.ones(positions.shape, bool)

    def row(dst, new, slot, act):
        upd = jax.lax.dynamic_update_slice(
            dst, new[:, None, :].astype(dst.dtype), (0, slot, 0))
        return jnp.where(act, upd, dst)

    if k_scale_l is not None:
        kq, ks = quantize_kv(k_new)
        vq, vs = quantize_kv(v_new)
        return (jax.vmap(row)(k_l, kq, slots, active),
                jax.vmap(row)(v_l, vq, slots, active),
                jax.vmap(row)(k_scale_l, ks, slots, active),
                jax.vmap(row)(v_scale_l, vs, slots, active))
    return (jax.vmap(row)(k_l, k_new, slots, active),
            jax.vmap(row)(v_l, v_new, slots, active), None, None)


def ref_layer_append_tiered(k_l, v_l, k_scale_l, v_scale_l, hot_k_l, hot_v_l,
                            k_new, v_new, positions, cold_dtype, active=None):
    H = hot_k_l.shape[2]
    ring = jax.lax.rem(positions, H)
    if active is None:
        active = jnp.ones(positions.shape, bool)

    def row(dst, new, slot, act):
        upd = jax.lax.dynamic_update_slice(
            dst, new[:, None, :].astype(dst.dtype), (0, slot, 0))
        return jnp.where(act, upd, dst)

    kq, ks = quantize_cold(k_new, cold_dtype)
    vq, vs = quantize_cold(v_new, cold_dtype)
    k_l = jax.vmap(row)(k_l, kq, positions, active)
    v_l = jax.vmap(row)(v_l, vq, positions, active)
    if k_scale_l is not None:
        k_scale_l = jax.vmap(row)(k_scale_l, ks, positions, active)
        v_scale_l = jax.vmap(row)(v_scale_l, vs, positions, active)
    hot_k_l = jax.vmap(row)(hot_k_l, k_new, ring, active)
    hot_v_l = jax.vmap(row)(hot_v_l, v_new, ring, active)
    return k_l, v_l, k_scale_l, v_scale_l, hot_k_l, hot_v_l


def ref_layer_write_chunk(k_l, v_l, k_scale_l, v_scale_l, k_new, v_new, slot,
                          start, valid_len):
    C = k_new.shape[1]
    keep = (jnp.arange(C, dtype=jnp.int32) < valid_len)[None, :, None]

    def put(dst, new):
        if dst is None:
            return None
        cur = jax.lax.dynamic_slice(
            dst, (slot, 0, start, 0), (1,) + new.shape)
        new = jnp.where(keep, new.astype(dst.dtype), cur[0])
        return jax.lax.dynamic_update_slice(dst, new[None],
                                            (slot, 0, start, 0))

    if k_scale_l is not None:
        kq, ks = quantize_kv(k_new)
        vq, vs = quantize_kv(v_new)
        return (put(k_l, kq), put(v_l, vq),
                put(k_scale_l, ks), put(v_scale_l, vs))
    return put(k_l, k_new), put(v_l, v_new), None, None


def ref_layer_write_chunk_tiered(k_l, v_l, k_scale_l, v_scale_l, hot_k_l,
                                 hot_v_l, k_new, v_new, slot, start,
                                 valid_len, cold_dtype):
    C = k_new.shape[1]
    keep = (jnp.arange(C, dtype=jnp.int32) < valid_len)[None, :, None]

    def put(dst, new):
        if dst is None:
            return None
        cur = jax.lax.dynamic_slice(
            dst, (slot, 0, start, 0), (1,) + new.shape)
        new = jnp.where(keep, new.astype(dst.dtype), cur[0])
        return jax.lax.dynamic_update_slice(dst, new[None],
                                            (slot, 0, start, 0))

    kq, ks = quantize_cold(k_new, cold_dtype)
    vq, vs = quantize_cold(v_new, cold_dtype)
    k_l, v_l = put(k_l, kq), put(v_l, vq)
    k_scale_l, v_scale_l = put(k_scale_l, ks), put(v_scale_l, vs)

    H = hot_k_l.shape[2]
    s_idx = jnp.arange(H, dtype=jnp.int32)
    r = jax.lax.rem(s_idx - jax.lax.rem(start, H) + H, H)
    i_star = jnp.clip(r + H * ((valid_len - 1 - r) // H), 0, C - 1)
    keep_h = (r < valid_len)[None, :, None]

    def put_hot(dst, new):
        g = jnp.take(new, i_star, axis=1)
        cur = jax.lax.dynamic_slice(dst, (slot, 0, 0, 0), (1,) + g.shape)
        g = jnp.where(keep_h, g.astype(dst.dtype), cur[0])
        return jax.lax.dynamic_update_slice(dst, g[None], (slot, 0, 0, 0))

    return (k_l, v_l, k_scale_l, v_scale_l,
            put_hot(hot_k_l, k_new), put_hot(hot_v_l, v_new))


def ref_block_decode_slotted(p, x, cfg, ctx, kv_slices, positions, active,
                             window=0, kv_bucket=0, kv_shards=1):
    B = x.shape[0]
    tiered = len(kv_slices) == 6
    if tiered:
        k_l, v_l, ks_l, vs_l, hk_l, hv_l = kv_slices
    else:
        k_l, v_l, ks_l, vs_l = kv_slices
        hk_l = hv_l = None
    if window:
        kv_bucket = 0
        kv_shards = 1
    h = common.apply_norm(cfg.norm, p["ln1"], x, cfg.norm_eps)
    h = ctx.ann(h, "batch", "seq", "embed")
    q, k, v = qkv_project(p["attn"], h, cfg, ctx, positions[:, None])
    if tiered:
        k_l, v_l, ks_l, vs_l, hk_l, hv_l = ref_layer_append_tiered(
            k_l, v_l, ks_l, vs_l, hk_l, hv_l, k[:, 0], v[:, 0], positions,
            cfg.kv_cold_dtype, active)
        counts = positions + 1
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
        k_l, v_l, ks_l, vs_l = ref_layer_append_slotted(
            k_l, v_l, ks_l, vs_l, k[:, 0], v[:, 0], positions, window, active)
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
        o = decode_attention_split(q[:, 0], kc, vc, mask, ctx)
    else:
        kc = ctx.ann(kc, "batch", "kv_heads", "kv_seq", "head_dim")
        vc = ctx.ann(vc, "batch", "kv_heads", "kv_seq", "head_dim")
        mask = batch_valid_mask(kc.shape[2], window, positions)
        o = decode_attention(q[:, 0], kc, vc, mask, ctx)
    o = common.linear(p["attn"]["wo"], o.reshape(B, 1, -1))
    x = ctx.ann(x + o, "batch", "seq", "embed_shard")
    h = common.apply_norm(cfg.norm, p["ln2"], x, cfg.norm_eps)
    h = ctx.ann(h, "batch", "seq", "embed")
    f, _ = _mix_ffn(p, h, cfg, ctx, train=False)
    x = ctx.ann(x + f, "batch", "seq", "embed_shard")
    if tiered:
        return x, (k_l, v_l, ks_l, vs_l, hk_l, hv_l)
    return x, (k_l, v_l, ks_l, vs_l)


def ref_block_prefill_chunk(p, x, cfg, ctx, kv_slices, slot, start,
                            valid_len):
    _, C, _ = x.shape
    tiered = len(kv_slices) == 6
    if tiered:
        k_l, v_l, ks_l, vs_l, hk_l, hv_l = kv_slices
    else:
        k_l, v_l, ks_l, vs_l = kv_slices
    positions = start + jnp.arange(C, dtype=jnp.int32)[None]
    h = common.apply_norm(cfg.norm, p["ln1"], x, cfg.norm_eps)
    h = ctx.ann(h, "batch", "seq", "embed")
    q, k, v = qkv_project(p["attn"], h, cfg, ctx, positions)
    S = k_l.shape[2]
    k_ch = jnp.swapaxes(k[0], 0, 1)
    v_ch = jnp.swapaxes(v[0], 0, 1)
    mask = jnp.arange(S, dtype=jnp.int32)[None, :] \
        <= positions[0][:, None]
    if tiered:
        kh, vh = chunk_hot_image(hk_l, hv_l, k_ch, v_ch, slot, start,
                                 valid_len, S, dtype=x.dtype)
        k_l, v_l, ks_l, vs_l, hk_l, hv_l = ref_layer_write_chunk_tiered(
            k_l, v_l, ks_l, vs_l, hk_l, hv_l, k_ch, v_ch, slot, start,
            valid_len, cfg.kv_cold_dtype)
        kc, vc = layer_read_slot_cold(k_l, v_l, ks_l, vs_l, slot,
                                      cfg.kv_cold_dtype, dtype=x.dtype)
        kh = ctx.ann(kh, "batch", "kv_heads", "kv_seq", "head_dim")
        vh = ctx.ann(vh, "batch", "kv_heads", "kv_seq", "head_dim")
        kc = ctx.ann(kc, "batch", "kv_heads", "kv_seq", "head_dim")
        vc = ctx.ann(vc, "batch", "kv_heads", "kv_seq", "head_dim")
        hot_mask = (jnp.arange(S, dtype=jnp.int32)[None, :] >=
                    cold_boundary(positions[0] + 1, cfg.hot_window,
                                  cfg.kv_cold_block)[:, None])[None]
        o = chunk_attention_tiered(q, kh, vh, kc, vc, hot_mask, mask, ctx)
    else:
        k_l, v_l, ks_l, vs_l = ref_layer_write_chunk(
            k_l, v_l, ks_l, vs_l, k_ch, v_ch, slot, start, valid_len)
        kc, vc = layer_read_slot(k_l, v_l, ks_l, vs_l, slot, dtype=x.dtype)
        kc = ctx.ann(kc, "batch", "kv_heads", "kv_seq", "head_dim")
        vc = ctx.ann(vc, "batch", "kv_heads", "kv_seq", "head_dim")
        o = chunk_attention(q, kc, vc, mask, ctx)
    o = common.linear(p["attn"]["wo"], o.reshape(1, C, -1))
    x = ctx.ann(x + o, "batch", "seq", "embed_shard")
    h = common.apply_norm(cfg.norm, p["ln2"], x, cfg.norm_eps)
    h = ctx.ann(h, "batch", "seq", "embed")
    f, _ = _mix_ffn(p, h, cfg, ctx, train=False)
    x = ctx.ann(x + f, "batch", "seq", "embed_shard")
    if tiered:
        return x, (k_l, v_l, ks_l, vs_l, hk_l, hv_l)
    return x, (k_l, v_l, ks_l, vs_l)


def ref_decode_step_slotted(params, cache, tokens, positions, active, cfg,
                            ctx, kv_bucket=0, kv_shards=1):
    x = common.embed(params["embed"], tokens[:, None], ctx)
    if cfg.pos == "learned":
        x = x + jnp.take(params["pos_embed"], positions,
                         axis=0)[:, None].astype(x.dtype)
    scales = cache.k_scale is not None
    tiered = cache.is_tiered

    def body(h, xs):
        lp, k_l, v_l = xs[0], xs[1], xs[2]
        rest = list(xs[3:])
        ks_l, vs_l = (rest.pop(0), rest.pop(0)) if scales else (None, None)
        if tiered:
            hk_l, hv_l = rest
            slices = (k_l, v_l, ks_l, vs_l, hk_l, hv_l)
        else:
            slices = (k_l, v_l, ks_l, vs_l)
        h, slices = ref_block_decode_slotted(
            lp, h, cfg, ctx, slices, positions, active,
            window=cache.window, kv_bucket=kv_bucket, kv_shards=kv_shards)
        ys = tuple(s for s in slices if s is not None)
        return h, ys

    xs = (params["blocks"], cache.k, cache.v) + \
        ((cache.k_scale, cache.v_scale) if scales else ()) + \
        ((cache.hot_k, cache.hot_v) if tiered else ())
    x, ys = jax.lax.scan(body, x, xs, unroll=common.scan_unroll())
    ys = list(ys)
    k_new, v_new = ys.pop(0), ys.pop(0)
    ks_new, vs_new = (ys.pop(0), ys.pop(0)) if scales else (None, None)
    hk_new, hv_new = (ys.pop(0), ys.pop(0)) if tiered else (None, None)
    new_len = jnp.maximum(
        cache.length, jnp.max(jnp.where(active, positions, 0)) + 1)
    cache = cache._replace(k=k_new, v=v_new, k_scale=ks_new, v_scale=vs_new,
                           hot_k=hk_new, hot_v=hv_new, length=new_len)
    x = common.apply_norm(cfg.norm, params["ln_f"], x, cfg.norm_eps)
    logits = common.unembed_logits(unembed_table(params, cfg), x, ctx)
    return cache, logits


def ref_prefill_chunk(params, cache, tokens, slot, start, valid_len, cfg,
                      ctx):
    x = common.embed(params["embed"], tokens, ctx)
    C = tokens.shape[1]
    positions = start + jnp.arange(C, dtype=jnp.int32)
    if cfg.pos == "learned":
        x = x + jnp.take(params["pos_embed"], positions,
                         axis=0)[None].astype(x.dtype)
    elif cfg.pos == "sinusoidal":
        table = common.sinusoidal_pos(cache.k.shape[3], cfg.d_model)
        x = x + jnp.take(table, positions, axis=0)[None].astype(x.dtype)
    scales = cache.k_scale is not None
    tiered = cache.is_tiered

    def body(h, xs):
        lp, k_l, v_l = xs[0], xs[1], xs[2]
        rest = list(xs[3:])
        ks_l, vs_l = (rest.pop(0), rest.pop(0)) if scales else (None, None)
        if tiered:
            hk_l, hv_l = rest
            slices = (k_l, v_l, ks_l, vs_l, hk_l, hv_l)
        else:
            slices = (k_l, v_l, ks_l, vs_l)
        h, slices = ref_block_prefill_chunk(
            lp, h, cfg, ctx, slices, slot, start, valid_len)
        ys = tuple(s for s in slices if s is not None)
        return h, ys

    k_st = ctx.ann(cache.k, None, "batch", "kv_heads", "kv_seq", "head_dim")
    v_st = ctx.ann(cache.v, None, "batch", "kv_heads", "kv_seq", "head_dim")
    xs = (params["blocks"], k_st, v_st) + \
        ((ctx.ann(cache.k_scale, None, "batch", "kv_heads", "kv_seq", None),
          ctx.ann(cache.v_scale, None, "batch", "kv_heads", "kv_seq", None))
         if scales else ()) + \
        ((ctx.ann(cache.hot_k, None, "batch", "kv_heads", None, "head_dim"),
          ctx.ann(cache.hot_v, None, "batch", "kv_heads", None, "head_dim"))
         if tiered else ())
    x, ys = jax.lax.scan(body, x, xs, unroll=common.scan_unroll())
    ys = list(ys)
    k_new, v_new = ys.pop(0), ys.pop(0)
    ks_new, vs_new = (ys.pop(0), ys.pop(0)) if scales else (None, None)
    hk_new, hv_new = (ys.pop(0), ys.pop(0)) if tiered else (None, None)
    new_len = jnp.maximum(cache.length, start + valid_len)
    cache = cache._replace(k=k_new, v=v_new, k_scale=ks_new, v_scale=vs_new,
                           hot_k=hk_new, hot_v=hv_new, length=new_len)
    x = common.apply_norm(cfg.norm, params["ln_f"], x, cfg.norm_eps)
    last = jax.lax.dynamic_slice_in_dim(x, valid_len - 1, 1, axis=1)
    logits = common.unembed_logits(unembed_table(params, cfg), last, ctx)
    return cache, logits


# ---------------------------------------------------------------------------
# Cases
# ---------------------------------------------------------------------------

# variant → (config overrides, ring window, kv_shards)
VARIANTS = {
    "bf16": ({}, 0, 1),
    "int8": ({"kv_dtype": "int8"}, 0, 1),
    "tiered-int8": ({"hot_window": 4, "kv_cold_dtype": "int8",
                     "kv_cold_block": 4}, 0, 1),
    "tiered-int4": ({"hot_window": 4, "kv_cold_dtype": "int4",
                     "kv_cold_block": 4}, 0, 1),
    "windowed": ({}, 12, 1),
    # split-KV einsums take bf16 operands into an f32 accumulator, a dot
    # the CPU backend lacks: f32 activations keep the program compilable
    "split2": ({"dtype": "float32"}, 0, 2),
}


def _model(variant):
    over, window, shards = VARIANTS[variant]
    cfg = ASSIGNED["qwen2-0.5b"].reduced().replace(**over)
    api = build_model(cfg)
    return cfg, api, api.init(jax.random.key(0)), window, shards


def _filled_cache(cfg, window, seed):
    """A cache whose every leaf holds seeded nonzero bytes, so a write to
    the wrong place, or a lost byte, shows in the comparison."""
    from repro.models.transformer import make_cache
    cache = make_cache(cfg, B, S, window=window)
    leaves, tree = jax.tree_util.tree_flatten(cache)
    keys = jax.random.split(jax.random.key(seed), len(leaves))
    out = []
    for key, leaf in zip(keys, leaves):
        if leaf.ndim == 0:
            out.append(leaf)
        elif jnp.issubdtype(leaf.dtype, jnp.integer):
            out.append(jax.random.randint(key, leaf.shape, -127, 128,
                                          jnp.int32).astype(leaf.dtype))
        else:
            out.append(jax.random.uniform(key, leaf.shape, jnp.float32, 0.01,
                                          1.0).astype(leaf.dtype))
    return jax.tree_util.tree_unflatten(tree, out)


def _assert_same(got, want):
    g, w = jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)
    assert len(g) == len(w)
    for a, b in zip(g, w):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype and a.shape == b.shape
        assert a.tobytes() == b.tobytes()


def _block_args(cfg):
    rng = np.random.default_rng(7)
    tok = jnp.asarray(rng.integers(0, cfg.vocab_size, B), jnp.int32)
    pos = jnp.asarray([3, 17, 9, 26], jnp.int32)      # rows at own cursors
    act = jnp.asarray([True, False, True, True])        # row 1 retired
    rem = jnp.asarray([T, T, 2, T], jnp.int32)          # row 2 halts mid-block
    eos = jnp.full((B,), -1, jnp.int32)
    return tok, pos, act, rem, eos


@pytest.mark.parametrize("variant", list(VARIANTS))
def test_decode_block_matches_xs_ys_loop(variant):
    cfg, api, params, window, shards = _model(variant)
    cache = _filled_cache(cfg, window, seed=1)
    args = _block_args(cfg)
    extra = {"kv_shards": shards} if shards > 1 else {}

    def ref_slotted(p, c, t, pos, a, ctx, kv_bucket=0, kv_shards=1):
        return ref_decode_step_slotted(p, c, t, pos, a, cfg, ctx,
                                       kv_bucket=kv_bucket,
                                       kv_shards=kv_shards)

    ref_block = make_decode_block(ref_slotted)
    got = jax.jit(lambda p, c, *a: api.decode_block(
        p, c, *a, NULL_CTX, block_size=T, **extra))(params, cache, *args)
    want = jax.jit(lambda p, c, *a: ref_block(
        p, c, *a, NULL_CTX, block_size=T, **extra))(params, cache, *args)
    # tokens, emitted, last token, cursors, active, remaining — and the cache
    _assert_same(got[1:], want[1:])
    _assert_same(got[0], want[0])
    assert np.asarray(got[2]).sum() == 2 * T + 2      # rows 0 and 3, 2 of row 2


@pytest.mark.parametrize("variant", ["bf16", "int8", "tiered-int8",
                                     "tiered-int4"])
@pytest.mark.parametrize("start, valid", [(0, C), (16, 5)],
                         ids=["full", "padded-last"])
def test_chunk_program_matches_xs_ys_loop(variant, start, valid):
    cfg, api, params, _, _ = _model(variant)
    cache = _filled_cache(cfg, 0, seed=2)
    rng = np.random.default_rng(11)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, C)), jnp.int32)
    scal = [jnp.asarray(x, jnp.int32) for x in (2, start, valid)]
    got = jax.jit(lambda p, c, *a: api.prefill_chunk(p, c, *a, NULL_CTX))(
        params, cache, toks, *scal)
    want = jax.jit(lambda p, c, *a: ref_prefill_chunk(p, c, *a, cfg,
                                                      NULL_CTX))(
        params, cache, toks, *scal)
    _assert_same(got[1], want[1])
    _assert_same(got[0], want[0])


# ---------------------------------------------------------------------------
# Structure
# ---------------------------------------------------------------------------

def _kv_shapes(caches):
    stacks = {leaf.shape for leaf in jax.tree_util.tree_leaves(caches)
              if leaf.ndim == 5}
    return stacks, {s[1:] for s in stacks}


@pytest.mark.parametrize("program", ["decode_block", "prefill_chunk"])
def test_no_scan_carries_kv_as_xs_and_no_slice_select(program):
    cfg, api, params, _, _ = _model("bf16")
    caches = api.init_caches(B, S)
    stacks, slices = _kv_shapes(caches)
    if program == "decode_block":
        args = _block_args(cfg)
        jaxpr = jax.make_jaxpr(lambda p, c, *a: api.decode_block(
            p, c, *a, NULL_CTX, block_size=T))(params, caches, *args)
    else:
        scal = [jnp.asarray(x, jnp.int32) for x in (1, 8, C)]
        jaxpr = jax.make_jaxpr(lambda p, c, *a: api.prefill_chunk(
            p, c, *a, NULL_CTX))(params, caches,
                                 jnp.zeros((1, C), jnp.int32), *scal)
    n_scans = 0
    for site in iter_eqns(jaxpr):
        eqn = site.eqn
        if eqn.primitive.name == "scan":
            n_scans += 1
            skip = eqn.params["num_consts"] + eqn.params["num_carry"]
            xs = [v.aval.shape for v in eqn.invars[skip:]]
            ys = [v.aval.shape for v in
                  eqn.outvars[eqn.params["num_carry"]:]]
            assert not stacks & set(xs), f"KV stack as scan xs: {xs}"
            assert not stacks & set(ys), f"KV stack as scan ys: {ys}"
        if eqn.primitive.name == "select_n":
            shapes = {tuple(v.aval.shape) for v in eqn.invars}
            assert not slices & shapes, \
                f"select over a whole layer slice: {shapes}"
    assert n_scans >= 1
