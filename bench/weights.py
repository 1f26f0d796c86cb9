"""Seeded random weights of a dense GQA decoder, made by the benchmark.

Every value is a small integer times a power of two, so it is exact in
bfloat16: the served bf16 weights and the reference's float32 weights are
the same numbers, with no rounding between them. Each leaf is drawn from
its own key (the seed's root key folded with the layer and the leaf), so
one layer can be made alone, in any program, with the same values as the
whole tree. Norm scales and biases are drawn too, so that the comparison
with the reference covers them.

Layout (``spec``): ``embed`` (V, D); per layer ``ln1``/``ln2`` (D,), ``wq``
(D, Hq*hd), ``wk``/``wv`` (D, Hkv*hd), ``bq``/``bk``/``bv`` when the
configuration has QKV biases, ``wo`` (Hq*hd, D), ``w_gate``/``w_up``
(D, F), ``w_down`` (F, D); ``ln_f`` (D,); ``unembed`` (V, D) when untied.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

LAYER_LEAVES = ("ln1", "wq", "bq", "wk", "bk", "wv", "bv", "wo", "ln2",
                "w_gate", "w_up", "w_down")
GLOBAL_LEAVES = ("embed", "ln_f", "unembed")
_GLOBAL_BASE = 1 << 20           # global leaves' fold-in ids, past any layer
# two bytes of random bits a, b give a - b in [-255, 255]: a triangular
# integer of this standard deviation, exact in bfloat16 (8 significant bits)
_TRI_STD = math.sqrt(2 * (256 ** 2 - 1) / 12)


def root_key(seed: int):
    """The key of a seed of any size (``jax.random.key`` keeps only the low
    32 bits of a larger Python int when 64-bit mode is off)."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.key(0)
    while True:
        key = jax.random.fold_in(key, jnp.uint32(seed & 0xFFFFFFFF))
        seed >>= 32
        if not seed:
            return key


def layer_shapes(cfg) -> Dict[str, Tuple[int, ...]]:
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    shapes = {"ln1": (d,), "wq": (d, q), "wk": (d, kv), "wv": (d, kv),
              "wo": (q, d), "ln2": (d,), "w_gate": (d, f), "w_up": (d, f),
              "w_down": (f, d)}
    if cfg["qkv_bias"]:
        shapes.update(bq=(q,), bk=(kv,), bv=(kv,))
    return shapes


def global_shapes(cfg) -> Dict[str, Tuple[int, ...]]:
    v, d = cfg["vocab_size"], cfg["hidden_size"]
    shapes = {"embed": (v, d), "ln_f": (d,)}
    if not cfg["tie_word_embeddings"]:
        shapes["unembed"] = (v, d)
    return shapes


def _leaf(key, name: str, shape) -> jax.Array:
    """float32 values, each exact in bfloat16."""
    bits = jax.random.bits(key, shape, jnp.uint32)
    a = (bits & 0xFF).astype(jnp.int32)
    b = ((bits >> 8) & 0xFF).astype(jnp.int32)
    if name.startswith("ln"):
        # 1 + k / 128 with k in [-31, 31]
        k = (a & 31) - (b & 31)
        return 1.0 + k.astype(jnp.float32) * 2.0 ** -7
    k = (a - b).astype(jnp.float32)
    if name.startswith("b"):
        return k * 2.0 ** -10                       # standard deviation 0.1
    fan_in = shape[-1] if name in ("embed", "unembed") else shape[0]
    # the power of two nearest to a 1/sqrt(fan_in) standard deviation
    return k * 2.0 ** -round(math.log2(_TRI_STD * math.sqrt(fan_in)))


def layer(cfg, key, index) -> Dict[str, jax.Array]:
    """One layer's leaves in float32; ``index`` may be traced."""
    lk = jax.random.fold_in(key, index)
    return {name: _leaf(jax.random.fold_in(lk, LAYER_LEAVES.index(name)),
                        name, shape)
            for name, shape in layer_shapes(cfg).items()}


def global_leaf(cfg, key, name: str) -> jax.Array:
    return _leaf(jax.random.fold_in(key, _GLOBAL_BASE
                                    + GLOBAL_LEAVES.index(name)),
                 name, global_shapes(cfg)[name])


def stacked(cfg, key, dtype) -> Dict[str, jax.Array]:
    """The whole tree: layer leaves stacked on a leading layer axis."""
    layers = jax.vmap(lambda i: layer(cfg, key, i))(
        jnp.arange(cfg["num_hidden_layers"], dtype=jnp.uint32))
    out = {k: v.astype(dtype) for k, v in layers.items()}
    for name in global_shapes(cfg):
        out[name] = global_leaf(cfg, key, name).astype(dtype)
    return out


# the serving program's parameter tree, path by path
_PROGRAM_PATHS = {
    ("embed", "table"): "embed", ("unembed", "table"): "unembed",
    ("ln_f", "scale"): "ln_f",
    ("blocks", "ln1", "scale"): "ln1", ("blocks", "ln2", "scale"): "ln2",
    ("blocks", "attn", "wq", "w"): "wq", ("blocks", "attn", "wq", "b"): "bq",
    ("blocks", "attn", "wk", "w"): "wk", ("blocks", "attn", "wk", "b"): "bk",
    ("blocks", "attn", "wv", "w"): "wv", ("blocks", "attn", "wv", "b"): "bv",
    ("blocks", "attn", "wo", "w"): "wo",
    ("blocks", "ffn", "w_gate", "w"): "w_gate",
    ("blocks", "ffn", "w_up", "w"): "w_up",
    ("blocks", "ffn", "w_down", "w"): "w_down",
}


def _path_names(path) -> Tuple[str, ...]:
    return tuple(getattr(p, "key", getattr(p, "name", str(p))) for p in path)


def to_program_tree(weights: Dict[str, jax.Array], program_shapes):
    """Place the leaves into the tree the program's ``init`` would make
    (``program_shapes``: its ``jax.eval_shape``). A leaf the benchmark
    does not know, or a shape or dtype that differs, is an error."""
    def fill(path, aval):
        names = _path_names(path)
        if names not in _PROGRAM_PATHS:
            raise ValueError(f"program parameter {'/'.join(names)} has no "
                             "benchmark weight")
        leaf = weights[_PROGRAM_PATHS[names]]
        if leaf.shape != aval.shape or leaf.dtype != aval.dtype:
            raise ValueError(f"{'/'.join(names)}: program wants {aval.shape} "
                             f"{aval.dtype}, benchmark has {leaf.shape} "
                             f"{leaf.dtype}")
        return leaf
    return jax.tree_util.tree_map_with_path(fill, program_shapes)
