#!/usr/bin/env python3
"""Bring-up smoke run of the serving engine on TPU chips.

    python chip_smoke.py                 # one chip
    python chip_smoke.py --four-chips    # W/A backend on four chips

One chip: serves 16 requests of qwen2-0.5b at its published widths
(24 layers, d_model 896, 14/2 heads, d_ff 4864, vocab 151936, bf16, random
weights drawn from --seed) through ``repro.launch.serve.serve``: continuous
batching over 8 slots, T=8 decode blocks, 64-token prefill chunks. It checks
that every request completed with its whole token budget, that nothing was
rejected, retried or timed out, and that every program compiled once. Then
it compares the bf16 prefill logits of two prompts with an f32 run of the
same weights at the highest matmul precision.

Four chips (``--four-chips``, and nothing else): serves the same requests
with the colocated backend on one chip and with the W/A backend on a (1, 4)
("data", "model") mesh at a_shards 1 and 4. In f32 the three token streams
must be identical; the bf16 runs must complete.

Times printed here are readings of a smoke run, not benchmark results. The
last line of standard output is one JSON object naming the device. When JAX
finds no TPU, or any check fails, the script exits non-zero without it.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.registry import get_config  # noqa: E402
from repro.launch.serve import (enable_compile_cache, make_requests,  # noqa: E402
                                serve)
from repro.models import NULL_CTX, build_model  # noqa: E402
from repro.models.sharding import ShardingCtx, sub_operator  # noqa: E402
from repro.runtime.serving import ServingEngine  # noqa: E402

ARCH = "qwen2-0.5b"
N_REQUESTS, SLOTS, PROMPT_LEN, MAX_NEW, ARRIVAL_EVERY = 16, 8, 128, 32, 2
ENGINE = dict(mode="continuous", block_size=8, prefill_chunk=64,
              kv_bucket_chunk=0)
# bf16 prefill logits against the f32 reference of the same weights:
# max |bf16 - f32| over max |f32|. bf16 keeps 8 significant bits (a
# rounding of 2^-9 relative per op); 24 layers of rounded activations and
# residual adds compound that, so the bound sits at 5e-2 (about 25 such
# roundings) — well above bf16 noise, well below a wrong layer, whose
# error is of the order of the logits themselves.
LOGITS_REL_TOL = 5e-2


def fail(msg: str):
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


def require_tpu(count: int):
    devs = jax.devices()
    d = devs[0]
    print(f"smoke: device platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)}")
    if d.platform != "tpu":
        fail(f"JAX found platform {d.platform!r}, not a TPU")
    if len(devs) < count:
        fail(f"needs {count} TPU chips, JAX found {len(devs)}")
    return devs


def mem(d, key: str) -> int:
    return d.memory_stats()[key]


def param_bytes(api) -> int:
    shapes = jax.eval_shape(api.init, jax.random.key(0))
    return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(shapes))


def check_served(stats, label: str):
    """Every request completed with its whole budget, nothing shed or
    retried, every program compiled exactly once."""
    problems = []
    if stats["completed"] != N_REQUESTS:
        problems.append(f"completed {stats['completed']}/{N_REQUESTS}")
    for k in ("rejections", "retries", "watchdog_timeouts",
              "deadline_misses"):
        if stats[k]:
            problems.append(f"{k}={stats[k]}")
    for name, rec in stats["runtime"].items():
        if rec["compiles"] != 1:
            problems.append(f"{name} compiled {rec['compiles']} times")
    short = [m["rid"] for m in stats["per_request"]
             if m["tokens"] != MAX_NEW]
    if short:
        problems.append(f"requests {short} did not generate {MAX_NEW} tokens")
    if problems:
        fail(f"{label}: " + "; ".join(problems))


def report_serve(stats, label: str):
    compile_s = {k: round(v["compile_s"], 3)
                 for k, v in stats["runtime"].items()}
    print(f"smoke[{label}]: compile seconds per program {compile_s}")
    print(f"smoke[{label}]: ttft mean {stats['ttft_mean_ms']:.1f} ms, "
          f"p99 {stats['ttft_p99_ms']:.1f} ms; tpot mean "
          f"{stats['tpot_mean_ms']:.2f} ms, p50 {stats['tpot_p50_ms']:.2f} "
          f"ms, p99 {stats['tpot_p99_ms']:.2f} ms "
          f"({stats['decode_tokens']} decode tokens, "
          f"{stats['macro_steps']} macro-steps)")


def serve_phase(seed: int):
    t0 = time.perf_counter()
    stats = serve(ARCH, N_REQUESTS, SLOTS, PROMPT_LEN, MAX_NEW,
                  reduced=False, seed=seed, arrival_every=ARRIVAL_EVERY,
                  backend="colocated", **ENGINE)
    wall = time.perf_counter() - t0
    check_served(stats, "serve")
    print(f"smoke[serve]: wall {wall:.1f} s for {N_REQUESTS} requests "
          "(weight init and compiles included)")
    report_serve(stats, "serve")


def logits_phase(cfg, seed: int):
    """bf16 prefill logits of two of the served prompts against the same
    weights upcast to f32 at the highest matmul precision."""
    api = build_model(cfg)
    params = api.init(jax.random.key(seed))       # serve()'s weights
    reqs = make_requests(cfg, 2, PROMPT_LEN, MAX_NEW, seed)
    toks = jnp.asarray(np.stack([r.prompt for r in reqs]))
    _, got = jax.jit(lambda p, t: api.prefill(p, {"tokens": t}, NULL_CTX))(
        params, toks)
    api32 = build_model(cfg.replace(dtype="float32"))
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        _, want = jax.jit(lambda p, t: api32.prefill(p, {"tokens": t},
                                                     NULL_CTX))(p32, toks)
    got = np.asarray(got.astype(jnp.float32))
    want = np.asarray(want)
    if got.shape != (2, 1, cfg.vocab_size) or got.shape != want.shape:
        fail(f"logits shape {got.shape} vs reference {want.shape}")
    if not (np.isfinite(got).all() and np.isfinite(want).all()):
        fail("non-finite logits")
    err = float(np.abs(got - want).max())
    rel = err / float(np.abs(want).max())
    agree = int((got.argmax(-1) == want.argmax(-1)).sum())
    print(f"smoke[logits]: max abs err {err:.6g}, max rel err {rel:.6g} "
          f"(tolerance {LOGITS_REL_TOL:g}), argmax agrees on {agree}/2 "
          f"prompts, max |f32 logit| {float(np.abs(want).max()):.6g}")
    if rel > LOGITS_REL_TOL:
        fail(f"logits rel err {rel:.6g} > {LOGITS_REL_TOL:g}")


def one_chip(seed: int):
    devs = require_tpu(1)
    print(f"smoke: compile cache at {enable_compile_cache()}")
    cfg = get_config(ARCH)
    print(f"smoke: {ARCH} params {param_bytes(build_model(cfg))} bytes "
          f"({cfg.dtype})")
    serve_phase(seed)
    print(f"smoke[serve]: peak bytes in use "
          f"{mem(devs[0], 'peak_bytes_in_use')}")
    logits_phase(cfg, seed)
    print(f"smoke: peak bytes in use {mem(devs[0], 'peak_bytes_in_use')}")


def weight_bytes_per_device(params):
    out = {}
    for leaf in jax.tree.leaves(params):
        for sh in leaf.addressable_shards:
            out[sh.device.id] = out.get(sh.device.id, 0) + sh.data.nbytes
    return dict(sorted(out.items()))


def four_chips(seed: int):
    devs = require_tpu(4)[:4]
    print(f"smoke: compile cache at {enable_compile_cache()}")
    mesh = jax.sharding.Mesh(np.array(devs).reshape(1, 4), ("data", "model"))
    variants = (("colocated", NULL_CTX, {}),
                ("wa_a1", ShardingCtx(mesh, sub_operator()),
                 dict(backend="wa", a_shards=1)),
                ("wa_a4", ShardingCtx(mesh, sub_operator()),
                 dict(backend="wa", a_shards=4)))
    for dtype in ("float32", "bfloat16"):
        cfg = get_config(ARCH).replace(dtype=dtype)
        api = build_model(cfg)
        # one compiled init instead of an eager compile per weight op
        params = jax.jit(api.init)(jax.random.key(seed))
        # f32 at the highest precision: the identity check compares the
        # programs' arithmetic, not the TPU's bf16 passes of an f32 matmul
        prec = jax.default_matmul_precision("highest") \
            if dtype == "float32" else contextlib.nullcontext()
        streams = {}
        for label, ctx, kw in variants:
            reqs = make_requests(cfg, N_REQUESTS, PROMPT_LEN, MAX_NEW, seed,
                                 ARRIVAL_EVERY)
            eng = ServingEngine(api, ctx, SLOTS, PROMPT_LEN, **ENGINE, **kw)
            t0 = time.perf_counter()
            with prec:
                stats = eng.run(params, reqs)
            wall = time.perf_counter() - t0
            name = f"{dtype}/{label}"
            check_served(stats, name)
            streams[label] = [r.generated for r in reqs]
            print(f"smoke[{name}]: wall {wall:.1f} s (compiles included)")
            report_serve(stats, name)
            print(f"smoke[{name}]: weight bytes per device "
                  f"{weight_bytes_per_device(eng.params)}")
            print(f"smoke[{name}]: bytes in use per device "
                  f"{[mem(d, 'bytes_in_use') for d in devs]}")
            if "wa" in stats:
                wa = stats["wa"]
                print(f"smoke[{name}]: wa routing bytes total "
                      f"{wa['routing_total_bytes']}, per token "
                      f"{wa['routing_bytes_per_token']}")
                if len(weight_bytes_per_device(eng.params)) < 2:
                    fail(f"{name}: weights sit on one device")
            del eng
        same = {k: v == streams["colocated"] for k, v in streams.items()}
        print(f"smoke[{dtype}]: token streams equal to colocated: {same}")
        if dtype == "float32" and not all(same.values()):
            fail(f"f32 token streams differ across backends: {same}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and prompts")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip W/A check")
    args = ap.parse_args(argv)
    if args.four_chips:
        four_chips(args.seed)
    else:
        one_chip(args.seed)
    devs = jax.devices()
    print(json.dumps({"ok": True,
                      "device": {"platform": devs[0].platform,
                                 "kind": devs[0].device_kind,
                                 "count": len(devs)}}))


if __name__ == "__main__":
    main()
