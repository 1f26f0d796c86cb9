#!/usr/bin/env python3
"""Readings that set a cell's correctness limit, on the chip, in one process.

    python3 bench/control.py --workload qwen2-0.5b.chat --seeds 1 2 3

For each seed: the served weights of that seed, a short closed-loop window
at the cell's own load (the cell's concurrency, twice over, so the mix's
longest requests finish), the same sample of completed requests a run
compares, and two readings on it: the widest logit gap of the program's
served tokens against the float32 reference (the lower reading), and the
widest gap of the tokens the control puts first (the upper reading): the
reference as W8A8 in float8 e4m3 (and, with ``--quant``, other precisions
for comparison).
The benchmark's own runs never run the control. Prints one line per seed
and a JSON summary as the last line.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

# the control; ``--quant fp8 int8`` reads int8 W8A8 beside it
CONTROLS = ("fp8",)


def readings(cfg, mix, reference, seed, api, n_requests,
             quants=CONTROLS):
    """(program gap, {control precision: gap}, tokens compared) of one
    seed."""
    from bench import check, harness, traffic_gen
    from bench import weights as W
    params = harness.make_weights(harness.architecture(cfg), cfg, api, seed)
    engine = harness.make_engine(cfg, mix, api)
    reqs = harness.requests(traffic_gen.draw(
        mix, n_requests, seed, traffic_gen.MEASURED, cfg["vocab_size"]))
    engine.run(params, reqs, max_steps=harness.NO_STEP_LIMIT)
    done = [r for r in reqs if r.status == "completed"
            and len(r.generated) == r.max_new_tokens]
    if len(done) != len(reqs):
        raise RuntimeError(f"seed {seed}: {len(reqs) - len(done)} requests "
                           "did not complete")
    del engine, params
    gc.collect()
    picked = [done[i] for i in check.pick(
        done, mix["check_requests"],
        traffic_gen.rng_for(seed, traffic_gen.SAMPLE))]
    sample = check.build(picked, traffic_gen.kv_extent(
        mix, cfg["max_new_tokens"]))
    key = W.root_key(seed)
    ref = reference.logits(cfg, key, sample.tokens, sample.score_pos)
    program = check.widest_gap(ref, sample.served, sample.mask)
    control = {}
    for quant in quants:
        ctl = reference.logits(cfg, key, sample.tokens, sample.score_pos,
                               quant=quant)
        control[quant] = check.control_gap(ref, ctl, sample.mask)
        del ctl
    return program, control, sample.n_tokens


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--quant", nargs="+", default=list(CONTROLS),
                    choices=("fp8", "int8"))
    args = ap.parse_args(argv)
    from bench import harness
    from bench.run import enable_cache, require_chips
    bm = harness.benchmark()
    wl, cfg, mix = harness.cell(bm, args.workload)
    require_chips(wl["chips"])
    enable_cache()
    from repro.models import build_model
    api = build_model(harness.architecture(cfg).program_config(cfg))
    reference = harness.load_module("references", cfg["reference"])
    rows = []
    for seed in args.seeds:
        program, control, n = readings(cfg, mix, reference, seed, api,
                                       2 * mix["concurrency"], args.quant)
        rows.append({"seed": seed, "program": program, "control": control,
                     "tokens": n})
        print(f"control {args.workload} seed {seed}: program gap {program} "
              f"control gaps {control} over {n} tokens", flush=True)
    print(json.dumps({
        "workload": args.workload, "readings": rows,
        "program_max": max(r["program"] for r in rows),
        "control_min": {q: min(r["control"][q] for r in rows)
                        for q in args.quant}}))


if __name__ == "__main__":
    main()
