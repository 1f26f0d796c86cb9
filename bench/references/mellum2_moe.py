"""Plain float32 reference of Mellum2 (JetBrains Mellum2-12B-A2.5B) as one
chip of expert parallelism.

Written from the published ``config.json`` and the layer equations its
keys name, in ``jax.numpy`` at the highest matmul precision, with no
cache, no batching tricks and nothing imported from the program under
test. Each block: RMSNorm; q/k/v projections without bias; rotary
embedding on the half-split head dimension (``rotate_half``), with the
plain table of ``rope_theta`` on sliding layers and YaRN on full ones
(frequencies blended between theta's and theta's over ``factor`` by a
linear ramp between the dimensions that turn ``beta_fast`` and
``beta_slow`` times over ``original_max_position_embeddings``, the ramp's
bounds floored and ceiled; cos and sin times ``attention_factor``); causal
grouped attention with 1/sqrt(head_dim) scaling, a sliding layer's query
at p attending positions p - sliding_window + 1 .. p; output projection
and residual; RMSNorm; the expert layer and residual. A final RMSNorm and
the untied unembedding.

The expert layer: float32 router logits over all the published experts,
softmax, the top ``num_experts_per_tok`` renormalised over themselves
(``norm_topk_prob``), and the weighted SwiGLU outputs of those picked
experts that this chip holds (``expert_parallel.held_offset`` and the
configuration's ``num_experts``); the others add nothing, as in the
program.

Departures from the published model: no multi-token-prediction head (the
config declares none); no q/k norm (the config names none);
``intermediate_size`` (7168) unused, since every ``mlp_layer_types`` entry
is sparse; the experts held on other chips left out, as this chip's share
of the deployment leaves them out. Weights are random (``bench/
weights.py``, in the layout of ``architectures/mellum2_moe.py``), so the
numbers say nothing of the trained model.

The forward pass runs one layer at a time, each layer's weights made from
the seed inside its own program, and the queries attend in blocks of at
most 512, as in ``dense_gqa``. ``quant="fp8"`` (the control of the
correctness check) and ``quant="int8"`` compute every matrix
multiplication, the router's and the experts' among them, and the
embedding lookup as W8A8, as ``dense_gqa``'s do.
"""
from __future__ import annotations

import json
import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import harness
from bench import weights as W

_dense = harness.load_module("references", "dense_gqa")
HIGHEST, Q_BLOCK, QUANT = _dense.HIGHEST, _dense.Q_BLOCK, _dense.QUANT
linear, rms_norm = _dense.linear, _dense.rms_norm


def rope_table(head_dim: int, params: dict):
    """(inverse frequencies (hd/2,), cos/sin scale) of a ``rope_parameters``
    section."""
    theta = float(params["rope_theta"])
    inv = 1.0 / theta ** (np.arange(0, head_dim, 2) / head_dim)
    if params["rope_type"] == "default":
        return inv.astype(np.float32), 1.0
    factor = params["factor"]

    def dim_of(turns):
        return head_dim * math.log(
            params["original_max_position_embeddings"] / (turns * 2 * math.pi)
        ) / (2 * math.log(theta))

    low = max(math.floor(dim_of(params["beta_fast"])), 0)
    high = min(math.ceil(dim_of(params["beta_slow"])), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(head_dim // 2) - low) / (high - low), 0, 1)
    extrapolate = 1 - ramp
    inv = inv / factor * (1 - extrapolate) + inv * extrapolate
    return inv.astype(np.float32), float(params["attention_factor"])


def rope(x, positions, inv, scale):
    """x (B, S, H, hd); rotates the pairs (i, i + hd/2)."""
    hd = x.shape[-1]
    ang = positions[:, :, None, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, window: int):
    """Causal grouped attention, over the last ``window`` positions where
    window > 0. q (B, S, Hq, hd); k, v (B, S, Hkv, hd)."""
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
        rows = (i * c + jnp.arange(c))[:, None]
        ok = keys[None, :] <= rows
        if window:
            ok &= keys[None, :] > rows - window
        p = jax.nn.softmax(jnp.where(ok, s, -jnp.inf), axis=-1)
        o = jnp.einsum("bkgqs,bskh->bqkgh", p, v, precision=HIGHEST)
        return o.reshape(B, c, Hq * hd)

    out = jax.lax.map(block, jnp.arange(S // c))
    return jnp.swapaxes(out, 0, 1).reshape(B, S, Hq * hd)


def experts(cfg, w, x, quant):
    """The held experts' part of the expert layer for x (..., D)."""
    logits = linear(x, w["router"], quant)
    probs = jax.nn.softmax(logits, axis=-1)
    top, idx = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    top = top / jnp.sum(top, -1, keepdims=True)
    offset = cfg["expert_parallel"]["held_offset"]
    out = jnp.zeros_like(x)
    for e in range(cfg["num_experts"]):
        weight = jnp.sum(jnp.where(idx == offset + e, top, 0.0), -1)
        gate = jax.nn.silu(linear(x, w["w_gate"][e], quant))
        y = linear(gate * linear(x, w["w_up"][e], quant), w["w_down"][e],
                   quant)
        out = out + weight[..., None] * y
    return out


def block(cfg, w, h, positions, window, quant):
    B, S, _ = h.shape
    hq, hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    kind = "sliding_attention" if window else "full_attention"
    inv, scale = rope_table(hd, cfg["rope_parameters"][kind])
    x = rms_norm(h, w["ln1"], eps)
    q, k, v = (linear(x, w[n], quant) for n in ("wq", "wk", "wv"))
    q = rope(q.reshape(B, S, hq, hd), positions, inv, scale)
    k = rope(k.reshape(B, S, hkv, hd), positions, inv, scale)
    v = v.reshape(B, S, hkv, hd)
    h = h + linear(attention(q, k, v, window), w["wo"], quant)
    return h + experts(cfg, w, rms_norm(h, w["ln2"], eps), quant)


@partial(jax.jit, static_argnums=(0, 3))
def _embed(cfg_json, key, tokens, quant):
    cfg = json.loads(cfg_json)
    table = W.global_leaf(harness.architecture(cfg), cfg, key, "embed")
    if quant is not None:
        table = QUANT[quant](table, 1)
    return jnp.take(table, tokens, axis=0)


@partial(jax.jit, static_argnums=(0, 4, 5), donate_argnums=(3,))
def _layer(cfg_json, key, index, h, window, quant):
    cfg = json.loads(cfg_json)
    positions = jnp.broadcast_to(jnp.arange(h.shape[1]), h.shape[:2])
    w = W.layer(harness.architecture(cfg), cfg, key, index)
    return block(cfg, w, h, positions, window, quant)


@partial(jax.jit, static_argnums=(0, 3))
def _head(cfg_json, key, h_at, quant):
    cfg = json.loads(cfg_json)
    arch = harness.architecture(cfg)
    x = rms_norm(h_at, W.global_leaf(arch, cfg, key, "ln_f"),
                 cfg["rms_norm_eps"])
    return linear(x, W.global_leaf(arch, cfg, key, "unembed").T, quant)


def logits(cfg, key, tokens, score_pos, quant: Optional[str] = None):
    """Logits (B, n, V) at positions ``score_pos`` (B, n) of the token
    rows ``tokens`` (B, S); the root weight key ``key`` of the run's seed.
    Positions past a row's true length attend only what comes before them,
    so right padding changes no scored position."""
    cfg_json = json.dumps(cfg, sort_keys=True)
    window = cfg["sliding_window"] if cfg["use_sliding_window"] else 0
    with jax.default_matmul_precision("highest"):
        h = _embed(cfg_json, key, jnp.asarray(tokens), quant)
        for i, kind in enumerate(cfg["layer_types"]):
            h = _layer(cfg_json, key, jnp.uint32(i), h,
                       window if kind == "sliding_attention" else 0, quant)
        h_at = jnp.take_along_axis(h, jnp.asarray(score_pos)[..., None], 1)
        del h
        return _head(cfg_json, key, h_at, quant)
