#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload qwen2-0.5b.chat --seed 7 --seconds 30 --trace 0

The cell (``BENCHMARK.json``: ``workloads``) names a configuration and a
traffic mix. ``--trace 0`` prints the cell's end-to-end metrics; ``--trace
1`` serves the same backlog with a profiled slice of the window and prints
its per-layer metrics, the device's busy and window seconds, and a
breakdown. Every run checks the served tokens against the plain reference
and prints each compared number beside its limit, on standard error and
under ``checks`` at the end of the result line, the last line of standard
output. Without a TPU, or with fewer chips than the cell asks for, it
exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def require_chips(n: int):
    """The devices of the run; exits non-zero unless JAX finds n TPUs."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"bench: JAX found platform {devs[0].platform!r}, not a "
                 "TPU; refusing to measure")
    if len(devs) < n:
        sys.exit(f"bench: the cell needs {n} TPU chips, JAX found "
                 f"{len(devs)}")
    return devs[:n]


def enable_cache():
    """JAX's persistent compilation cache at the program's fixed path,
    keeping every program, however quick to compile, so that only a
    checkout's first run compiles."""
    import jax
    from repro.launch.serve import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def main(argv=None):
    args = parse(argv)
    from bench import harness
    bm = harness.benchmark()
    wl, cfg, mix = harness.cell(bm, args.workload)
    devices = require_chips(wl["chips"])
    harness.note(f"{len(devices)} chip(s) found "
                 f"{time.monotonic() - T_PROCESS:.3f} s after start")
    peaks = harness.peaks_for(devices[0].device_kind)
    enable_cache()
    result = harness.measure(
        cfg, mix, harness.load_json("cells", args.workload),
        harness.load_module("references", cfg["reference"]), args.seed,
        args.seconds, bool(args.trace),
        harness.end_to_end_for(bm, args.workload),
        harness.per_layer_for(bm, args.workload), T_PROCESS, devices, peaks)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
