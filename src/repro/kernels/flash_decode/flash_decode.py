"""Flash-style decode attention Pallas TPU kernel.

TPU adaptation of the paper's attention kernel (§4.2): "KV-cache blocks are
processed in a tiled fashion, computing attention scores and value aggregation
without materializing large intermediate matrices ... we rely on LLC streaming
for KV blocks while maintaining query vectors in private cache." Here:
- K/V stay in HBM; each row's tiles are copied HBM→VMEM by double-buffered
  DMA, touched exactly once;
- the (B, n_kv, G, hd) query block is VMEM-pinned for the whole call;
- online softmax (running max / normalizer) per (row, KV head), so no
  (H, S) score matrix is ever materialized.

Per-row live ranges: row b attends positions ``lo[b] <= pos < hi[b]``
(traced int32 (B,) operands, scalar-prefetched into SMEM: no recompile as
cursors advance). The kernel walks one work list of (row, tile) pairs
holding only the tiles that range touches (``live_tiles``), and copies
nothing else: no DMA is issued outside a row's range, and a row with
``hi <= lo`` costs no DMA and no compute (its output is 0). The list is one
loop, so the copy of the next tile (of this row or of the next live row)
overlaps the attention over the current one; the grid is a single step.

All KV heads of a row share one (n_kv, block_s, hd) tile, so GQA's G =
Hq // n_kv query heads of each KV head attend it from VMEM. K/V may be one
layer's (B, n_kv, S, hd) or the whole (L, B, n_kv, S, hd) stack with a
traced ``layer``: a serving program passes the stacks it carries, and the
kernel reads the layer's tiles in place (no slice of the stack is made).
Supports INT8 KV via per-position scales (the paper runs fully-INT8 KV) and
an optional (B, S) mask on top of the ranges (split-KV shards).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128


def live_tiles(lo, hi, block_s: int):
    """(first tile, tile count) of the positions ``[lo, hi)`` in tiles of
    ``block_s``: tiles ``first .. first + count - 1``, each holding at least
    one position of the range; count 0 where ``hi <= lo``. Works on traced
    scalars (the kernel) and on numpy arrays (the engine's ``kv_read``
    counter and the bounds verifier) alike."""
    first = lo // block_s
    last = (hi - 1) // block_s
    return first, (last - first + 1) * (hi > lo)


def _weigh(p, v, dims):
    """``p`` (G, bs) f32 against the V tile: in two bf16 passes where V is
    bf16 (p's high half, then the rest: exact products, about 16 bits of
    each weight), else in f32."""
    if v.dtype == jnp.float32:
        return jax.lax.dot_general(p, v, dims,
                                   preferred_element_type=jnp.float32)
    hi = p.astype(v.dtype)
    lo = (p - hi.astype(jnp.float32)).astype(v.dtype)
    return sum(jax.lax.dot_general(a, v, dims,
                                   preferred_element_type=jnp.float32)
               for a in (hi, lo))


def _kernel(layer_ref, lo_ref, hi_ref, q_ref, k_hbm, v_hbm, *rest,
            n_rows: int, block_s: int, scale: float, quantized: bool,
            masked: bool, kv_t: bool, partial_stats: bool):
    n_in = 2 * quantized + masked
    ins, (o_ref, m_ref, l_ref), scratch = (rest[:n_in], rest[n_in:n_in + 3],
                                           rest[n_in + 3:])
    bufs, (sem, row_s, tile_s) = scratch[:-3], scratch[-3:]
    srcs = (k_hbm, v_hbm) + tuple(ins)
    n_kv = q_ref.shape[1]
    layer = layer_ref[0]

    o_ref[...] = jnp.zeros_like(o_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)

    # the work list: every (row, tile) pair that a row's range touches
    def add_row(b, w):
        first, count = live_tiles(lo_ref[b], hi_ref[b], block_s)

        def put(i, _):
            row_s[w + i] = b
            tile_s[w + i] = first + i
        jax.lax.fori_loop(0, count, put, None)
        return w + count
    n_work = jax.lax.fori_loop(0, n_rows, add_row, jnp.int32(0))

    def copies(w, slot):
        b = row_s[w]
        at = pl.ds(pl.multiple_of(tile_s[w] * block_s, block_s), block_s)
        out = []
        for j, (src, buf) in enumerate(zip(srcs, bufs)):
            if j < 2 and not kv_t:               # (.., S, hd) K and V
                src = src.at[layer, b, :, at]
            elif j < 2 + 2 * quantized:          # (.., hd or 1, S)
                src = src.at[layer, b, :, :, at]
            else:                                # the (B, 1, S) mask
                src = src.at[b, :, at]
            out.append(pltpu.make_async_copy(src, buf.at[slot],
                                             sem.at[j, slot]))
        return out

    @pl.when(n_work > 0)
    def _first():
        for c in copies(0, 0):
            c.start()

    def step(w, _):
        slot = jax.lax.rem(w, 2)

        @pl.when(w + 1 < n_work)
        def _next():
            for c in copies(w + 1, 1 - slot):
                c.start()

        for c in copies(w, slot):
            c.wait()
        b = row_s[w]
        pos = tile_s[w] * block_s + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_s), 1)
        valid = (pos >= lo_ref[b]) & (pos < hi_ref[b])
        if masked:
            valid &= bufs[-1][slot] != 0                 # (1, block_s)
        # K contracts hd with q; V's positions contract with p
        k_dims = (((1,), (0,)), ((), ())) if kv_t else (((1,), (1,)), ((), ()))
        v_dims = (((1,), (1,)), ((), ())) if kv_t else (((1,), (0,)), ((), ()))
        for h in range(n_kv):
            q = q_ref[b, h]                              # (G, hd)
            k = bufs[0][slot, h]                         # (bs, hd)/(hd, bs)
            v = bufs[1][slot, h]
            if k.dtype != q.dtype or quantized:
                q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
            s = jax.lax.dot_general(q, k, k_dims,
                                    preferred_element_type=jnp.float32)
            s = s * scale
            if quantized:                                # (1, bs) scales
                s = s * bufs[2][slot, h]
            s = jnp.where(valid, s, NEG_INF)             # (G, bs)
            m_prev = m_ref[b, h]                         # (G, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_ref[b, h] = l_ref[b, h] * corr + jnp.sum(p, axis=1,
                                                       keepdims=True)
            if quantized:
                p = p * bufs[3][slot, h]
            o_ref[b, h] = o_ref[b, h] * corr + _weigh(p, v, v_dims)
            m_ref[b, h] = m_new

    jax.lax.fori_loop(0, n_work, step, None)

    # split-KV partial mode defers normalization to the cross-shard combine
    # (combine.py): the raw (o, m, l) triple IS the kernel's output
    if not partial_stats:
        o_ref[...] = o_ref[...] / jnp.maximum(l_ref[...], 1e-30)


@functools.partial(jax.jit,
                   static_argnames=("block_s", "scale", "interpret",
                                    "partial_stats"))
def flash_decode_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                        k_scale, v_scale, mask, *,
                        block_s: int = 512, scale: float = None,
                        interpret: bool = False,
                        kv_limit=None, partial_stats: bool = False,
                        lo=None, hi=None, layer=None):
    """q: (B,Hq,hd); k/v: (B,n_kv,S,hd), or the (L,B,n_kv,S,hd) stacks with
    ``layer`` (traced int32) naming the layer to read (int8 ⇒ scales of the
    same shape with a last dim of 1, else pass None); mask: (B,S) bool or
    None → (B,Hq,hd) f32.

    Row b attends the positions ``lo[b] <= pos < hi[b]`` where ``mask``
    (if given) holds: ``lo``/``hi`` are (B,) int32, TRACED (a fresh value
    every step, no recompilation), clipped to [0, S]; by default 0 and S.
    Only the tiles of ``block_s`` positions that a row's range touches are
    copied from HBM (``live_tiles``); a row with ``hi <= lo`` returns 0.

    ``kv_limit``: optional scalar/0-d/(1,1) int32, the same upper bound for
    every row (e.g. ``max(positions) + 1`` after the append): the per-row
    form with ``hi`` = ``kv_limit`` and ``lo`` = 0.

    ``partial_stats`` (static): split-KV mode — skip the final
    normalization and return the raw ``(o, m, l)`` flash statistics as
    ``((B,Hq,hd), (B,Hq), (B,Hq))`` f32 for a cross-shard
    ``combine_partial_stats`` merge. A row with nothing to attend (e.g. a
    ``kv_limit`` of 0) returns the exact merge identity ``(0, NEG_INF,
    0)``."""
    B, Hq, hd = q.shape
    if k.ndim == 4:                       # one layer: a stack of one
        k, v = k[None], v[None]
        k_scale = None if k_scale is None else k_scale[None]
        v_scale = None if v_scale is None else v_scale[None]
        layer = 0
    _, _, n_kv, S, _ = k.shape
    G = Hq // n_kv
    bs = min(block_s, S)
    assert S % bs == 0, (S, bs)
    sc = scale if scale is not None else 1.0 / math.sqrt(hd)
    quantized = k_scale is not None
    masked = mask is not None
    if kv_limit is not None:
        if lo is not None or hi is not None:
            raise ValueError("pass kv_limit or per-row lo/hi, not both")
        hi = jnp.broadcast_to(jnp.asarray(kv_limit, jnp.int32).reshape(()),
                              (B,))
    lo = jnp.zeros((B,), jnp.int32) if lo is None else lo
    hi = jnp.full((B,), S, jnp.int32) if hi is None else hi
    lo = jnp.clip(jnp.asarray(lo, jnp.int32), 0, S)
    hi = jnp.clip(jnp.asarray(hi, jnp.int32), 0, S)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    # XLA lays a stack whose head size is narrower than a lane row (128)
    # out with positions minor, as (.., hd, S): the kernel reads it through
    # that view, which is the same bytes, so no relayout copy is made (the
    # per-position scales alike, as (.., 1, S))
    kv_t = hd < LANES
    if kv_t:
        k, v = jnp.swapaxes(k, 3, 4), jnp.swapaxes(v, 3, 4)
    operands = [q.reshape(B, n_kv, G, hd), k, v]
    bufs = [pltpu.VMEM((2,) + k.shape[2:4] + (bs,) if kv_t
                       else (2, n_kv, bs, hd), k.dtype)] * 2
    if quantized:
        operands += [jnp.swapaxes(k_scale, 3, 4),
                     jnp.swapaxes(v_scale, 3, 4)]
        bufs += [pltpu.VMEM((2, n_kv, 1, bs), k_scale.dtype)] * 2
    if masked:
        # (B, 1, S): a row's tile is a (1, block_s) copy along the lanes
        operands.append(mask.astype(jnp.int32).reshape(B, 1, S))
        bufs.append(pltpu.VMEM((2, 1, bs), jnp.int32))
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(1,),
        in_specs=[vmem] + [hbm] * (len(operands) - 1),
        out_specs=[vmem] * 3,
        scratch_shapes=bufs + [
            pltpu.SemaphoreType.DMA((len(bufs), 2)),
            pltpu.SMEM((B * (S // bs),), jnp.int32),
            pltpu.SMEM((B * (S // bs),), jnp.int32)])
    o, m, l = pl.pallas_call(
        functools.partial(_kernel, n_rows=B, block_s=bs, scale=sc,
                          quantized=quantized, masked=masked, kv_t=kv_t,
                          partial_stats=partial_stats),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, n_kv, G, hd), jnp.float32),
            jax.ShapeDtypeStruct((B, n_kv, G, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, n_kv, G, 1), jnp.float32),
        ],
        interpret=interpret,
    )(layer, lo, hi, *operands)
    if partial_stats:
        return (o.reshape(B, Hq, hd), m.reshape(B, Hq), l.reshape(B, Hq))
    return o.reshape(B, Hq, hd)
