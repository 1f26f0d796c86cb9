"""Plain float32 reference of a dense GQA decoder (Qwen2, InternLM2).

Written from the published descriptions, in ``jax.numpy`` at the highest
matmul precision, with no cache, no batching tricks and nothing imported
from the program under test. Each block: RMSNorm, q/k/v projections (with
biases where the configuration has them), rotary embedding on the
half-split head dimension (the published ``rotate_half``), causal grouped
attention with 1/sqrt(head_dim) scaling, output projection and residual;
RMSNorm, SwiGLU (silu(x W_gate) * x W_up) W_down and residual. A final
RMSNorm and the unembedding (the embedding table when tied).

Departures from the published models: InternLM2 stores q, k and v as one
interleaved ``wqkv`` matrix, which with random weights computes the same
function as three separate ones; its dynamic RoPE scaling acts only past
32,768 positions, beyond every context served here. Weights are random
(``bench/weights.py``, in the layout of the configuration's architecture
module), so the numbers say nothing of the trained models.

The forward pass runs one layer at a time: each layer's weights are made
from the seed inside the layer's own program and dropped after it, so
float32 weights are never held whole on the chip.
The queries attend in blocks of at most 512 so the scores fit too.

``quant="fp8"`` computes every matrix multiplication (and the embedding
lookup) as W8A8 in float8 e4m3: weights rounded per output channel,
activations per token, each scaled to the format's range. It is the
control of the correctness check: the reference put in the program's
place one precision step below the configuration's bf16. ``quant="int8"``
is the same with symmetric int8 rounding.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from bench import harness
from bench import weights as W

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 512


def _int8(x, axis):
    """Symmetric int8 rounding of x along ``axis`` (one scale per slice of
    the other axes), returned dequantized."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _fp8(x, axis):
    """float8 e4m3 rounding of x, scaled so each slice's largest magnitude
    lands on the format's largest value (448), returned dequantized."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


QUANT = {"int8": _int8, "fp8": _fp8}


def linear(x, w, quant: Optional[str]):
    """x (..., d_in) @ w (d_in, d_out) in float32."""
    if quant is not None:
        x, w = QUANT[quant](x, -1), QUANT[quant](w, 0)
    return jnp.einsum("...k,kn->...n", x, w, precision=HIGHEST)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, positions, theta):
    """x (B, S, H, hd); rotates the pairs (i, i + hd/2)."""
    hd = x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = positions[:, :, None, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v):
    """Causal grouped attention. q (B, S, Hq, hd); k, v (B, S, Hkv, hd)."""
    B, S, Hq, hd = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    c = min(Q_BLOCK, S)
    if S % c:
        raise ValueError(f"sequence {S} is not a multiple of {c}")
    keys = jnp.arange(S)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * c, c, 1)
        qb = qb.reshape(B, c, Hkv, G, hd)
        s = jnp.einsum("bqkgh,bskh->bkgqs", qb, k,
                       precision=HIGHEST) / math.sqrt(hd)
        rows = i * c + jnp.arange(c)
        s = jnp.where(keys[None, :] <= rows[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkgqs,bskh->bqkgh", p, v, precision=HIGHEST)
        return o.reshape(B, c, Hq * hd)

    out = jax.lax.map(block, jnp.arange(S // c))           # (S/c, B, c, D)
    return jnp.swapaxes(out, 0, 1).reshape(B, S, Hq * hd)


def block(cfg, w, h, positions, quant):
    B, S, _ = h.shape
    hq, hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    x = rms_norm(h, w["ln1"], eps)
    q, k, v = (linear(x, w[n], quant) for n in ("wq", "wk", "wv"))
    if cfg["qkv_bias"]:
        q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
    q = rope(q.reshape(B, S, hq, hd), positions, theta)
    k = rope(k.reshape(B, S, hkv, hd), positions, theta)
    v = v.reshape(B, S, hkv, hd)
    h = h + linear(attention(q, k, v), w["wo"], quant)
    x = rms_norm(h, w["ln2"], eps)
    gate = jax.nn.silu(linear(x, w["w_gate"], quant))
    return h + linear(gate * linear(x, w["w_up"], quant), w["w_down"], quant)


@partial(jax.jit, static_argnums=(0, 3))
def _embed(cfg_items, key, tokens, quant):
    cfg = dict(cfg_items)
    table = W.global_leaf(harness.architecture(cfg), cfg, key, "embed")
    if quant is not None:
        table = QUANT[quant](table, 1)
    return jnp.take(table, tokens, axis=0)


@partial(jax.jit, static_argnums=(0, 4), donate_argnums=(3,))
def _layer(cfg_items, key, index, h, quant):
    cfg = dict(cfg_items)
    positions = jnp.broadcast_to(jnp.arange(h.shape[1]), h.shape[:2])
    w = W.layer(harness.architecture(cfg), cfg, key, index)
    return block(cfg, w, h, positions, quant)


@partial(jax.jit, static_argnums=(0, 3))
def _head(cfg_items, key, h_at, quant):
    cfg = dict(cfg_items)
    arch = harness.architecture(cfg)
    x = rms_norm(h_at, W.global_leaf(arch, cfg, key, "ln_f"),
                 cfg["rms_norm_eps"])
    name = "embed" if cfg["tie_word_embeddings"] else "unembed"
    return linear(x, W.global_leaf(arch, cfg, key, name).T, quant)


def _items(cfg):
    """The configuration as a hashable static argument (sizes only)."""
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, bool))))


def logits(cfg, key, tokens, score_pos, quant: Optional[str] = None):
    """Logits (B, n, V) at positions ``score_pos`` (B, n) of the token
    rows ``tokens`` (B, S); the root weight key ``key`` of the run's seed.
    Positions past a row's true length attend only what comes before them,
    so right padding changes no scored position."""
    items = _items(cfg)
    with jax.default_matmul_precision("highest"):
        h = _embed(items, key, jnp.asarray(tokens), quant)
        for i in range(cfg["num_hidden_layers"]):
            h = _layer(items, key, jnp.uint32(i), h, quant)
        h_at = jnp.take_along_axis(h, jnp.asarray(score_pos)[..., None], 1)
        del h
        return _head(items, key, h_at, quant)
