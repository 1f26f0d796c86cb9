"""Seeded random weights, made by the benchmark, in the layout of a
configuration's architecture module (``bench/architectures/<name>.py``:
its leaves, their shapes and fan-ins, and the program path of each).

Every value is a small integer times a power of two, so it is exact in
bfloat16: the served bf16 weights and the reference's float32 weights are
the same numbers, with no rounding between them. Each leaf is drawn from
its own key (the seed's root key folded with the layer and the leaf's
index in the architecture's ``LAYER_LEAVES`` or ``GLOBAL_LEAVES``), so one
layer can be made alone, in any program, with the same values as the
whole tree. Norm scales and biases are drawn too, so that the comparison
with the reference covers them.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

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


def _leaf(key, shape, fan_in) -> jax.Array:
    """float32 values, each exact in bfloat16. ``fan_in`` is ``"norm"``,
    ``"bias"`` or a matrix's fan-in (the architecture's ``fan_in``)."""
    bits = jax.random.bits(key, shape, jnp.uint32)
    a = (bits & 0xFF).astype(jnp.int32)
    b = ((bits >> 8) & 0xFF).astype(jnp.int32)
    if fan_in == "norm":
        # 1 + k / 128 with k in [-31, 31]
        k = (a & 31) - (b & 31)
        return 1.0 + k.astype(jnp.float32) * 2.0 ** -7
    k = (a - b).astype(jnp.float32)
    if fan_in == "bias":
        return k * 2.0 ** -10                       # standard deviation 0.1
    # the power of two nearest to a 1/sqrt(fan_in) standard deviation
    return k * 2.0 ** -round(math.log2(_TRI_STD * math.sqrt(fan_in)))


def layer(arch, cfg, key, index) -> Dict[str, jax.Array]:
    """One layer's leaves in float32; ``index`` may be traced."""
    lk = jax.random.fold_in(key, index)
    return {name: _leaf(jax.random.fold_in(lk, arch.LAYER_LEAVES.index(name)),
                        shape, arch.fan_in(name, shape))
            for name, shape in arch.layer_shapes(cfg).items()}


def global_leaf(arch, cfg, key, name: str) -> jax.Array:
    shape = arch.global_shapes(cfg)[name]
    return _leaf(jax.random.fold_in(key, _GLOBAL_BASE
                                    + arch.GLOBAL_LEAVES.index(name)),
                 shape, arch.fan_in(name, shape))


def stacked(arch, cfg, key, dtype) -> Dict[str, jax.Array]:
    """The whole tree: layer leaves stacked on a leading layer axis."""
    layers = jax.vmap(lambda i: layer(arch, cfg, key, i))(
        jnp.arange(cfg["num_hidden_layers"], dtype=jnp.uint32))
    out = {k: v.astype(dtype) for k, v in layers.items()}
    for name in arch.global_shapes(cfg):
        out[name] = global_leaf(arch, cfg, key, name).astype(dtype)
    return out


def _path_names(path) -> Tuple[str, ...]:
    return tuple(getattr(p, "key", getattr(p, "name", str(p))) for p in path)


def to_program_tree(arch, weights: Dict[str, jax.Array], program_shapes):
    """Place the leaves into the tree the program's ``init`` would make
    (``program_shapes``: its ``jax.eval_shape``), by the architecture's
    ``PROGRAM_PATHS``. A program leaf the benchmark has no weight for, or a
    shape or dtype that differs, is an error."""
    def fill(path, aval):
        names = _path_names(path)
        name = arch.PROGRAM_PATHS.get(names)
        if name not in weights:
            raise ValueError(f"program parameter {'/'.join(names)} has no "
                             "benchmark weight")
        leaf = weights[name]
        if leaf.shape != aval.shape or leaf.dtype != aval.dtype:
            raise ValueError(f"{'/'.join(names)}: program wants {aval.shape} "
                             f"{aval.dtype}, benchmark has {leaf.shape} "
                             f"{leaf.dtype}")
        return leaf
    return jax.tree_util.tree_map_with_path(fill, program_shapes)
