"""Common building blocks: initializers, norms, RoPE, linear (bf16/int8),
embedding, and the memory-efficient chunked cross-entropy loss.

Pure-functional: params are nested dicts of arrays (pytrees); every array
carries a parallel "logical axes" annotation tree used by the sharding rules.
"""
from __future__ import annotations

import math
import os
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def scan_unroll() -> bool:
    """Cost-probe mode: when REPRO_UNROLL_SCANS=1, every lax.scan fully
    unrolls so compiled.cost_analysis() counts true trip-scaled FLOPs/bytes
    (XLA cost analysis counts while bodies ONCE — see launch/dryrun.py's
    probe-extrapolation protocol)."""
    return os.environ.get("REPRO_UNROLL_SCANS") == "1"

from repro.models.sharding import ShardingCtx
from repro.quant.int8 import QuantizedTensor, int8_matmul, quantize_int8

Params = Dict[str, Any]


def dtype_of(cfg) -> jnp.dtype:
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# Init — fan-in scaled normal (truncation unnecessary for benchmarking fidelity)
# ---------------------------------------------------------------------------

def dense_init(key, shape, dtype, fan_in: Optional[int] = None):
    fan_in = fan_in if fan_in is not None else shape[0]
    std = 1.0 / math.sqrt(max(fan_in, 1))
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def make_linear(key, d_in: int, d_out: int, dtype, *, bias: bool = False,
                int8: bool = False) -> Params:
    w = dense_init(key, (d_in, d_out), dtype)
    p: Params = {"w": quantize_int8(w, axis=0) if int8 else w}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def linear(p: Params, x: jax.Array, out_dtype=None) -> jax.Array:
    """x: (..., d_in) @ w: (d_in, d_out). Supports int8 QuantizedTensor w."""
    w = p["w"]
    if isinstance(w, QuantizedTensor):
        y = int8_matmul(x, w, out_dtype=out_dtype or x.dtype)
    else:
        y = jnp.einsum("...k,kn->...n", x, w,
                       preferred_element_type=jnp.float32).astype(out_dtype or x.dtype)
    if "b" in p:
        y = y + p["b"].astype(y.dtype)
    return y


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def make_norm(kind: str, d: int, dtype) -> Params:
    p: Params = {"scale": jnp.ones((d,), dtype)}
    if kind == "layernorm":
        p["bias"] = jnp.zeros((d,), dtype)
    return p


def apply_norm(kind: str, p: Params, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        n = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
        return (n * p["scale"].astype(jnp.float32)).astype(x.dtype)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    n = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (n * p["scale"].astype(jnp.float32)
            + p["bias"].astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def yarn_freqs(head_dim: int, theta: float, yarn) -> Tuple[np.ndarray, float]:
    """YaRN's inverse frequencies (hd/2,) and its cos/sin scale, as the
    published ``rope_type: "yarn"`` computes them (``configs.base.
    YaRNConfig``): dimension pairs below the one that turns ``beta_fast``
    times over the original context keep their frequency, those past the
    one that turns ``beta_slow`` times are divided by ``factor``, and a
    linear ramp blends the two between (bounds floored and ceiled)."""
    pos = theta ** (np.arange(0, head_dim, 2, dtype=np.float64) / head_dim)

    def dim_of(turns):
        return head_dim * math.log(yarn.original_max_position_embeddings
                                   / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(dim_of(yarn.beta_fast)), 0)
    high = min(math.ceil(dim_of(yarn.beta_slow)), head_dim - 1)
    ramp = np.clip((np.arange(head_dim // 2) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    inv = (1.0 / pos) * (1.0 - ramp) + (1.0 / (yarn.factor * pos)) * ramp
    return inv.astype(np.float32), float(yarn.attention_factor)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               freqs: Optional[jax.Array] = None,
               scale: Optional[jax.Array] = None) -> jax.Array:
    """x: (..., seq, heads, head_dim); positions: (..., seq) int32.
    ``freqs`` (hd/2,) replaces the plain table of ``theta`` and ``scale``
    multiplies cos and sin (YaRN, or a layer's pick of its kind's table)."""
    hd = x.shape[-1]
    if freqs is None:
        freqs = rope_freqs(hd, theta)                    # (hd/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs   # (..., seq, hd/2)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    if scale is not None:
        cos, sin = cos * scale, sin * scale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_pos(seq: int, d: int) -> jax.Array:
    pos = jnp.arange(seq, dtype=jnp.float32)[:, None]
    dim = jnp.arange(0, d, 2, dtype=jnp.float32)[None, :]
    ang = pos / jnp.power(10000.0, dim / d)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# Activations
# ---------------------------------------------------------------------------

def gated_act(kind: str, up: jax.Array, gate: jax.Array) -> jax.Array:
    if kind == "swiglu":
        return jax.nn.silu(gate.astype(jnp.float32)).astype(up.dtype) * up
    if kind == "geglu":
        return jax.nn.gelu(gate.astype(jnp.float32)).astype(up.dtype) * up
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# Embedding + memory-efficient CE loss
# ---------------------------------------------------------------------------

def make_embedding(key, vocab: int, d: int, dtype) -> Params:
    return {"table": dense_init(key, (vocab, d), dtype, fan_in=d)}


def embed(p: Params, tokens: jax.Array, ctx: ShardingCtx) -> jax.Array:
    tab = ctx.ann(p["table"], "vocab", "embed")
    return ctx.ann(jnp.take(tab, tokens, axis=0), "batch", "seq", "embed")


def unembed_logits(p_table: jax.Array, x: jax.Array, ctx: ShardingCtx) -> jax.Array:
    """Full logits — ONLY for decode (seq==1); training uses chunked_ce_loss."""
    logits = jnp.einsum("...d,vd->...v", x.astype(jnp.float32),
                        p_table.astype(jnp.float32))
    return ctx.ann(logits, "batch", "seq", "vocab")


def ce_chunk(S: int, target: int = 512) -> int:
    """Largest divisor of S that is ≤ target (vision-token offsets make S
    non-powers-of-two, e.g. 3840)."""
    for c in range(min(target, S), 0, -1):
        if S % c == 0:
            return c
    return S


def chunked_ce_loss(table: jax.Array, x: jax.Array, labels: jax.Array,
                    ctx: ShardingCtx, chunk: int = 512) -> jax.Array:
    """Cross-entropy WITHOUT materializing (B,S,V) logits.

    Scans the sequence in chunks; per chunk computes (B,c,V) logits against the
    (vocab-sharded) table, reduces to per-token loss, and discards. The paper's
    Table-1 "+1 serving socket" (embedding/argmax stage) maps onto this
    vocab-parallel head. Peak per-chip logit footprint: B·chunk·V/tp floats.
    """
    B, S, D = x.shape
    n = S // chunk
    assert n * chunk == S, (S, chunk)
    xs = x.reshape(B, n, chunk, D).swapaxes(0, 1)           # (n,B,c,D)
    ls = labels.reshape(B, n, chunk).swapaxes(0, 1)         # (n,B,c)

    def body(tot, xc_lc):
        xc, lc = xc_lc
        logits = jnp.einsum("bcd,vd->bcv", xc.astype(jnp.float32),
                            table.astype(jnp.float32))
        logits = ctx.ann(logits, "batch", "seq", "vocab")
        m = jnp.max(logits, axis=-1)
        lse = m + jnp.log(jnp.sum(jnp.exp(logits - m[..., None]), axis=-1))
        gold = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        return tot + jnp.sum(lse - gold), None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (xs, ls), unroll=scan_unroll())
    return total / (B * S)


# ---------------------------------------------------------------------------
# Key-splitting helper for building stacked (scan) layer params
# ---------------------------------------------------------------------------

def stacked_init(key, n: int, init_fn):
    """vmap an init over n layers → leaves with leading layer dim."""
    return jax.vmap(init_fn)(jax.random.split(key, n))


def fold(key, *ints) -> jax.Array:
    for i in ints:
        key = jax.random.fold_in(key, i)
    return key
