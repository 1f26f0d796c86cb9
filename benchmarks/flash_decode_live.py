"""Time the flash-decode kernel's live-range read against the full-extent
einsum pair, at the benchmark cells' decode attention shapes, on one chip.

One decode micro-step's attention over every layer of a carried
(L, B, n_kv, S, hd) bf16 stack: the kernel reads each decoding row's
``[lo, hi)`` tiles of the stack in place, the jnp form
(``models/attention.py::decode_attention``) reads every row's full extent
of a layer slice. Cursors are drawn like the chat mix (lognormal prompts,
median 1020, sigma 0.8, clipped to [32, 3968], plus up to 128 decoded
tokens); ``--active`` rows of ``B`` decode, the rest have ``hi = 0``.

    python benchmarks/flash_decode_live.py [--block-s 256 512] [--reps 20]

Without ``--block-s`` each shape runs at the tile the serving decode picks
for it (``models/transformer.py::live_range_tile``). Prints one JSON line
per (shape, block size): milliseconds per micro-step
for both forms, their ratio, and the largest difference of the outputs.
Needs a TPU: the kernel does not fall back.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.flash_decode.flash_decode import flash_decode_pallas
from repro.kv.cache import KVCache
from repro.models import NULL_CTX
from repro.models.transformer import live_range_tile
from repro.models.attention import decode_attention

S = 4096
# (name, layers, slots, KV heads, query heads per KV head, head size,
#  decoding rows, layer windows: every layer full, or Mellum2's 3:1 pattern)
SHAPES = [
    ("qwen2-0.5b", 24, 32, 2, 7, 64, 4, None),
    ("internlm2-1.8b.code", 24, 8, 8, 2, 128, 1, None),
    ("internlm2-1.8b.chat", 24, 8, 8, 2, 128, 4, None),
    ("mellum2-12b-a2.5b-ep8", 28, 32, 4, 8, 128, 4, (1024, 1024, 1024, 0)),
]


def cursors(rng, B, active):
    p = np.clip(np.exp(rng.normal(np.log(1020), 0.8, B)), 32, 3968)
    p = (p + rng.integers(0, 128, B)).astype(np.int32)
    act = np.zeros(B, bool)
    act[rng.choice(B, active, replace=False)] = True
    return p, act


def run(shape, block_s, reps, seed):
    name, L, B, n_kv, G, hd, active, pattern = shape
    rng = np.random.default_rng(seed)
    p, act = cursors(rng, B, active)
    windows = jnp.asarray((pattern * (L // len(pattern))) if pattern
                          else (0,) * L, jnp.int32)
    kk, kv_, kq = jax.random.split(jax.random.key(seed), 3)
    k = jax.random.normal(kk, (L, B, n_kv, S, hd), jnp.bfloat16)
    v = jax.random.normal(kv_, (L, B, n_kv, S, hd), jnp.bfloat16)
    q = jax.random.normal(kq, (B, n_kv * G, hd), jnp.bfloat16)
    block_s = block_s or live_range_tile(KVCache(k, v, None, None, None))
    pos = jnp.asarray(p)
    hi = jnp.where(jnp.asarray(act), pos + 1, 0)

    def bounds(w):
        return jnp.where(w > 0, jnp.maximum(0, pos + 1 - w), 0)

    def kernel_step(q, k, v):
        def layer(i, q):
            o = flash_decode_pallas(q, k, v, None, None, None,
                                    block_s=block_s, lo=bounds(windows[i]),
                                    hi=hi, layer=i)
            return (q + 1e-3 * o).astype(q.dtype)
        return jax.lax.fori_loop(0, L, layer, q)

    def einsum_step(q, k, v):
        keys = jnp.arange(S, dtype=jnp.int32)

        def layer(i, q):
            mask = (keys[None] < pos[:, None] + 1) & (
                keys[None] >= bounds(windows[i])[:, None])
            o = decode_attention(q, k[i], v[i], mask, NULL_CTX)
            return (q + 1e-3 * o).astype(q.dtype)
        return jax.lax.fori_loop(0, L, layer, q)

    out = {"shape": name, "block_s": block_s, "slots": B,
           "decoding": int(act.sum()), "layers": L,
           "live_positions": int(sum(p[act] + 1))}
    for label, fn in (("kernel", kernel_step), ("einsum", einsum_step)):
        step = jax.jit(lambda q, k, v, fn=fn: jax.lax.fori_loop(
            0, reps, lambda _, q: fn(q, k, v), q))
        jax.block_until_ready(step(q, k, v))             # compile, warm
        t0 = time.perf_counter()
        jax.block_until_ready(step(q, k, v))
        out[f"{label}_ms"] = (time.perf_counter() - t0) * 1e3 / reps
    out["speedup"] = out["einsum_ms"] / out["kernel_ms"]
    # one layer's outputs, decoding rows only (others are discarded)
    lo0 = bounds(windows[0])
    got = flash_decode_pallas(q, k, v, None, None, None, block_s=block_s,
                              lo=lo0, hi=hi, layer=0)
    keys = jnp.arange(S, dtype=jnp.int32)
    mask = (keys[None] < pos[:, None] + 1) & (keys[None] >= lo0[:, None])
    want = decode_attention(q, k[0], v[0], mask, NULL_CTX)
    diff = jnp.abs(got - want.astype(jnp.float32))[jnp.asarray(act)]
    out["max_abs_diff"] = float(diff.max())
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--block-s", type=int, nargs="+", default=[0])
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=2147483905)
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"needs a TPU, found {dev.platform}")
    for shape in SHAPES:
        for bs in args.block_s:
            print(json.dumps(dict(run(shape, bs, args.reps, args.seed),
                                  device=dev.device_kind)), flush=True)


if __name__ == "__main__":
    main()
