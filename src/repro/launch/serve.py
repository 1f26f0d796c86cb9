"""Serving driver: continuous-batching decode with the static AOT runtime.

    PYTHONPATH=src python -m repro.launch.serve --arch internlm2-1.8b \
        --requests 8 --batch 4 --prompt-len 32 --max-new 16 --reduced \
        --arrival-every 4

``--no-reduced`` serves the architecture's published widths (on a chip:
``chip_smoke.py`` at the repo root is that run for ``qwen2-0.5b``).

Reports the paper's metrics (TPOT mean/p50/p99, throughput) plus the
scheduler-side metrics the continuous engine adds (per-request TTFT, queue
delay, overlapped admissions) from real measured steps on this host (reduced
configs) — the measurement side of the Table 2 methodology;
benchmarks/table2_end_to_end.py compares these against the analytical model.

``--mode drain`` runs the legacy drain-then-refill baseline for A/B
comparison (late arrivals starve until the whole batch empties — DESIGN.md §7).
"""
from __future__ import annotations

import argparse
import os
from pathlib import Path

import numpy as np

from repro.configs.registry import get_config
from repro.models.registry import build_model
from repro.models.sharding import ShardingCtx, operator_centric, sub_operator
from repro.runtime.serving import Request, ServingEngine

REPO_ROOT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache before the first compile
    and return its directory. ``JAX_COMPILATION_CACHE_DIR``, when set, is
    read by JAX itself and nothing is set here; otherwise the cache lives at
    the fixed ``<repo root>/.jax_cache`` (the path is part of each entry's
    key, so it must not move between runs)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = str(REPO_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def make_requests(cfg, n_requests: int, prompt_len: int, max_new: int,
                  seed: int = 0, arrival_every: int = 0):
    """Synthetic workload; ``arrival_every`` > 0 staggers arrivals so request
    i becomes visible at decode step i*arrival_every (mid-serve admission)."""
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size, prompt_len,
                                        dtype=np.int32),
                    max_new_tokens=max_new,
                    arrival_step=i * arrival_every)
            for i in range(n_requests)]


def serve(arch: str, n_requests: int, batch_slots: int, prompt_len: int,
          max_new: int, *, reduced: bool = True, seed: int = 0,
          executor: str = "sub_operator", mode: str = "auto",
          arrival_every: int = 0, block_size: int = 1,
          kv_bucket_chunk: int = 0, prefill_chunk: int = 0,
          backend: str = "colocated", a_shards: int = 1, overlap: int = 1,
          preemptible: bool = False, max_queue: int = 0,
          hot_window: int = 0, kv_cold_dtype: str = "int8",
          kv_cold_block: int = 16, kv_budget_bytes: int = 0):
    enable_compile_cache()
    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()
    if hot_window:
        # tiered KV cache: hot ring at the resident dtype, cold prefix
        # quantized in fixed blocks (build-time statics — DESIGN.md §7)
        cfg = cfg.replace(hot_window=hot_window,
                          kv_cold_dtype=kv_cold_dtype,
                          kv_cold_block=kv_cold_block)
    if mode == "drain" and prefill_chunk:
        print("note: --prefill-chunk ignored (drain mode has no chunk lane)")
        prefill_chunk = 0
    api = build_model(cfg)
    ctx = ShardingCtx(None, sub_operator() if executor == "sub_operator"
                      else operator_centric())
    import jax
    params = api.init(jax.random.key(seed))
    reqs = make_requests(cfg, n_requests, prompt_len, max_new, seed,
                         arrival_every)
    eng = ServingEngine(api, ctx, batch_slots, prompt_len, mode=mode,
                        block_size=block_size,
                        kv_bucket_chunk=kv_bucket_chunk,
                        prefill_chunk=prefill_chunk, backend=backend,
                        a_shards=a_shards, overlap=overlap,
                        preemptible=preemptible, max_queue=max_queue,
                        kv_budget_bytes=kv_budget_bytes)
    stats = eng.run(params, reqs)
    return stats


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="tiny widths of the same architecture (default); "
                         "--no-reduced serves the published widths")
    ap.add_argument("--mode", default="auto",
                    choices=("auto", "continuous", "drain"))
    ap.add_argument("--arrival-every", type=int, default=0,
                    help="stagger: request i arrives at step i*N (0 = all "
                         "at start)")
    ap.add_argument("--block-size", type=int, default=1,
                    help="decode micro-steps per host sync (macro-step "
                         "decode; 1 = per-token engine)")
    ap.add_argument("--kv-bucket-chunk", type=int, default=0,
                    help="KV bucket granularity for length-aware decode "
                         "(block mode; 0 = full extent)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunked-prefill lane: admit prompts as fixed "
                         "(1,C) chunks, one per block boundary, with "
                         "length-true cursors (0 = monolithic admission)")
    ap.add_argument("--backend", default="colocated",
                    choices=("colocated", "wa"),
                    help="executor backend: colocated, or the weight-"
                         "attention disaggregated path (routing compiled "
                         "into every step program; DESIGN.md §3)")
    ap.add_argument("--a-shards", type=int, default=1,
                    help="split-KV flash decode width: shard each slot's "
                         "KV walk into N equal sequence shards recombined "
                         "by the partial-softmax LSE merge (token-exact; "
                         "the KV extent must divide by N; under --backend "
                         "wa on a mesh the shards ride the A-domain model "
                         "axis)")
    ap.add_argument("--overlap", type=int, default=1,
                    help="sub-operator micro-batch pipelining depth for "
                         "the W/A boundary (backend wa only; 1, 2 or 4 — "
                         "batch must divide evenly): W runs QKV/FFN for "
                         "one micro-batch while A attends another, "
                         "token-exact at every depth (DESIGN.md §3)")
    ap.add_argument("--preemptible", action="store_true",
                    help="compile the token-exact KV swap pair and allow "
                         "priority/pressure preemption at block boundaries "
                         "(DESIGN.md §7)")
    ap.add_argument("--max-queue", type=int, default=0,
                    help="bounded-queue backpressure: shed lowest-priority "
                         "queued work beyond N as structured rejections "
                         "(0 = unbounded)")
    ap.add_argument("--hot-window", type=int, default=0,
                    help="tiered KV cache: keep the most recent N tokens "
                         "per slot at the cache-resident dtype and demote "
                         "older tokens to the quantized cold tier in "
                         "fixed blocks, inside the compiled programs "
                         "(0 = flat cache)")
    ap.add_argument("--kv-cold-dtype", default="int8",
                    choices=("bfloat16", "int8", "int4"),
                    help="cold-tier storage dtype (int4 packs two lanes "
                         "per byte with per-block scales)")
    ap.add_argument("--kv-cold-block", type=int, default=16,
                    help="demotion granularity: cold-boundary advances in "
                         "blocks of N tokens (build-time static)")
    ap.add_argument("--kv-budget-bytes", type=int, default=0,
                    help="tiered-KV arbiter byte budget: preempt victims "
                         "(with --preemptible) or hold admissions while "
                         "occupancy-priced live KV bytes exceed N "
                         "(0 = unbounded)")
    args = ap.parse_args(argv)
    stats = serve(args.arch, args.requests, args.batch, args.prompt_len,
                  args.max_new, mode=args.mode,
                  arrival_every=args.arrival_every,
                  block_size=args.block_size,
                  kv_bucket_chunk=args.kv_bucket_chunk,
                  prefill_chunk=args.prefill_chunk,
                  backend=args.backend, a_shards=args.a_shards,
                  overlap=args.overlap, preemptible=args.preemptible,
                  max_queue=args.max_queue, hot_window=args.hot_window,
                  kv_cold_dtype=args.kv_cold_dtype,
                  kv_cold_block=args.kv_cold_block,
                  kv_budget_bytes=args.kv_budget_bytes)
    per_req = stats.pop("per_request")
    rt = stats.pop("runtime")
    rejected = stats.pop("rejected")
    tiered = stats.pop("tiered", None)
    spans = stats.pop("spans")
    boundary = stats.pop("boundary")
    kv = stats.pop("kv", None)
    print("serve stats:", stats)
    # where a boundary's time goes (runtime/spans.py): nested spans
    # (swap_* in policies/admit, prefill in admit) count in both rows
    print("phases (serve:<phase> spans; dispatch and boundary in memory "
          "only):")
    for name, sp in spans.items():
        print(f"  {name:16s} n={sp['n']:6d} p50={sp['p50_ms']:9.3f}ms "
              f"p90={sp['p90_ms']:9.3f}ms total={sp['total_ms']:10.2f}ms")
    print(f"  host per decode boundary: n={boundary['n']} "
          f"p50={boundary['host_p50_ms']:.3f}ms "
          f"p90={boundary['host_p90_ms']:.3f}ms")
    if per_req:
        split = {k: float(np.median([m[k] for m in per_req]))
                 for k in ("queue_delay_ms", "lane_wait_ms", "prefill_ms",
                           "ttft_ms")}
        print(f"ttft split (p50): queue={split['queue_delay_ms']:.1f}ms "
              f"lane_wait={split['lane_wait_ms']:.1f}ms "
              f"prefill={split['prefill_ms']:.1f}ms "
              f"(ttft p50 {split['ttft_ms']:.1f}ms)")
    if kv:
        print(f"kv: in use {100 * kv['in_use_share_mean']:.1f}% of "
              f"{kv['reserved_tokens']} reserved positions (mean over "
              f"decode dispatches); chunk lane depth mean "
              f"{kv['lane_depth_mean']:.2f}")
    if tiered:
        # host-side placement arbiter view (KVArbiter): tier occupancy,
        # in-program demotions counted off cursor watermarks, byte savings
        print(f"tiered kv:  hot_window={tiered['hot_window']} "
              f"cold={tiered['cold_dtype']}/block{tiered['cold_block']} "
              f"demotions={tiered['demotions']} "
              f"kv_bytes_per_slot={tiered['kv_bytes_per_slot']} "
              f"peak_kv_bytes={tiered['peak_kv_bytes']} "
              f"cold_bytes_saved={tiered['cold_bytes_saved']}")
        for s in tiered["per_slot"]:
            print(f"  slot {s['slot']}: {s['tokens']} tokens "
                  f"({s['hot_tokens']} hot / {s['cold_tokens']} cold, "
                  f"{s['kv_bytes']} B)")
        print(f"  arbiter: {tiered['recommendation']}")
    if "wa" in stats:
        # per-domain stall accounting of the W/A schedule (DESIGN.md §3):
        # overlap efficiency = busy ticks / total over both domains
        wa = stats["wa"]
        print(f"wa overlap: depth={wa['overlap']} "
              f"efficiency={wa['overlap_efficiency']:.3f} "
              f"(W busy {wa['w_busy_ticks']}/{wa['schedule_ticks']}, "
              f"A busy {wa['a_busy_ticks']}/{wa['schedule_ticks']} ticks); "
              f"per macro-step W-idle {wa['w_idle_ms_per_macro_step']:.2f} "
              f"ms / A-idle {wa['a_idle_ms_per_macro_step']:.2f} ms; "
              f"micro-batch occupancy {wa['micro_batch_occupancy']:.2f}")
    # pressure / robustness counters (DESIGN.md §7): every submitted
    # request is terminally accounted completed / rejected / deadline-missed
    print(f"pressure: preemptions={stats['preemptions']} "
          f"restores={stats['restores']} rejections={stats['rejections']} "
          f"deadline_misses={stats['deadline_misses']} "
          f"retries={stats['retries']} "
          f"watchdog_timeouts={stats['watchdog_timeouts']} "
          f"quarantined={stats['quarantined_slots']} "
          f"swap_time_ms={stats['swap_time_ms']:.2f}")
    for e in rejected:
        print(f"  shed rid={e['rid']:3d} [{e['status']}] "
              f"priority={e['priority']} reason={e['reason']}")
    print("per-request:")
    for m in per_req:
        print(f"  rid={m['rid']:3d} admit@{m['admit_step']:4d} "
              f"queue={m['queue_delay_ms']:8.1f}ms "
              f"ttft={m['ttft_ms']:8.1f}ms tpot={m['tpot_ms']:6.2f}ms "
              f"preempts={m['preemptions']}")
    print("runtime:", {k: {kk: round(vv, 3) if isinstance(vv, float) else vv
                           for kk, vv in v.items()} for k, v in rt.items()})


if __name__ == "__main__":
    main()
