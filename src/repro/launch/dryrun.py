import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile EVERY (arch × shape × mesh) cell and
record memory_analysis / cost_analysis / collective schedule.

The two lines above MUST run before any other import (jax locks the device
count on first init); they are intentionally placed before the module
docstring's siblings. Do NOT replicate this flag elsewhere — tests and
benches must see 1 device.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch internlm2-1.8b \
        --shape decode_32k --mesh single --executor sub_operator
    PYTHONPATH=src python -m repro.launch.dryrun --all --out artifacts/dryrun
"""
import argparse
import json
import time
import traceback

import jax
import numpy as np

from repro.configs.registry import ASSIGNED, get_config
from repro.configs.shapes import ALL_SHAPES, SHAPES, applicable
from repro.core.execution import make_step
from repro.launch.hlo_analysis import parse_collectives, ring_traffic_bytes
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import compute_terms

ARTIFACTS = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                         "artifacts", "dryrun")


# ---------------------------------------------------------------------------
# Cost probes.
#
# XLA's cost_analysis() counts while-loop bodies ONCE (trip counts are not
# multiplied) and reports PER-DEVICE numbers (verified empirically — see
# EXPERIMENTS.md §Dry-run methodology). The full-config compile is therefore
# used for memory_analysis + the per-layer collective schedule, while exact
# FLOPs/bytes come from two depth-reduced FULLY-UNROLLED probe compiles
# (REPRO_UNROLL_SCANS=1) and linear extrapolation — exact for uniform stacks:
#     cost(L) = a + b·L  ⇒  cost_real = c_lo + (c_hi−c_lo)·(u_real−u_lo)/(u_hi−u_lo)
# ---------------------------------------------------------------------------

def probe_configs(cfg, mult: int = 1):
    """→ (cfg_lo, cfg_hi, u_lo, u_hi, u_real): layer-unit probe pair.
    ``mult``: minimum layer multiple (= n_stages under PP)."""
    import dataclasses
    if mult > 1:
        return (cfg.replace(n_layers=mult), cfg.replace(n_layers=2 * mult),
                mult, 2 * mult, cfg.n_layers)
    if cfg.family == "hybrid":
        pat = len(cfg.rglru.block_pattern)          # 3
        tail = cfg.n_layers % pat                   # 2 for 38
        lo = cfg.replace(n_layers=pat + tail)
        hi = cfg.replace(n_layers=2 * pat + tail)
        return lo, hi, 1, 2, (cfg.n_layers - tail) // pat
    if cfg.family == "audio":
        enc = cfg.encoder
        lo = cfg.replace(n_layers=1,
                         encoder=dataclasses.replace(enc, n_layers=1))
        hi = cfg.replace(n_layers=2,
                         encoder=dataclasses.replace(enc, n_layers=2))
        # units move enc+dec together; exact because both stacks are 24L
        return lo, hi, 1, 2, cfg.n_layers
    return cfg.replace(n_layers=1), cfg.replace(n_layers=2), 1, 2, cfg.n_layers


def _probe_cost(cfg, shape, multi_pod, executor, pod_strategy):
    """Compile the two unrolled probes; return extrapolated (flops, bytes,
    collective operand bytes, ring bytes, by_axes)."""
    mult = 2 if (pod_strategy == "pp" and multi_pod) else 1
    lo_cfg, hi_cfg, u_lo, u_hi, u_real = probe_configs(cfg, mult)
    os.environ["REPRO_UNROLL_SCANS"] = "1"
    try:
        vals = []
        for c in (lo_cfg, hi_cfg):
            mesh = make_production_mesh(multi_pod=multi_pod)
            with mesh:
                bundle = make_step(c, SHAPES[shape.name], mesh,
                                   executor=executor,
                                   pod_strategy=pod_strategy)
                lowered = bundle.lower()
                compiled = lowered.compile()
                cost = compiled.cost_analysis()
                coll = parse_collectives(compiled.as_text(),
                                         mesh.devices.shape, mesh.axis_names)
            vals.append({
                "flops": float(cost.get("flops", 0.0)),
                "bytes": float(cost.get("bytes accessed", 0.0)),
                "coll": coll.total_operand_bytes,
                "ring": ring_traffic_bytes(coll),
                "by_axes": coll.bytes_by_axes(),
            })
    finally:
        os.environ.pop("REPRO_UNROLL_SCANS", None)

    def extrap(lo, hi):
        return lo + (hi - lo) * (u_real - u_lo) / (u_hi - u_lo)

    by_axes = {}
    for k in set(vals[0]["by_axes"]) | set(vals[1]["by_axes"]):
        by_axes[k] = extrap(vals[0]["by_axes"].get(k, 0.0),
                            vals[1]["by_axes"].get(k, 0.0))
    return {
        "flops": extrap(vals[0]["flops"], vals[1]["flops"]),
        "bytes": extrap(vals[0]["bytes"], vals[1]["bytes"]),
        "coll": extrap(vals[0]["coll"], vals[1]["coll"]),
        "ring": extrap(vals[0]["ring"], vals[1]["ring"]),
        "by_axes": by_axes,
        "probe_units": [u_lo, u_hi, u_real],
    }


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             executor: str = "sub_operator", pod_strategy: str = "dp",
             verbose: bool = True) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh_name = "2x16x16" if multi_pod else "16x16"
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "executor": executor, "pod_strategy": pod_strategy}
    ok, why = applicable(cfg, shape)
    if not ok:
        rec.update(status="skip", reason=why)
        return rec

    t0 = time.monotonic()
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        with mesh:
            bundle = make_step(cfg, shape, mesh, executor=executor,
                               pod_strategy=pod_strategy)
            lowered = bundle.lower()
            compiled = lowered.compile()
            mem = compiled.memory_analysis()
            hlo = compiled.as_text()
        chips = int(np.prod(mesh.devices.shape))
        coll = parse_collectives(hlo, mesh.devices.shape, mesh.axis_names)

        # exact trip-scaled cost from unrolled probes (per-device numbers)
        probe = _probe_cost(cfg, shape, multi_pod, executor, pod_strategy)
        flops = probe["flops"] * chips        # per-device → whole-step totals
        byts = probe["bytes"] * chips
        coll_bytes = probe["coll"] * chips
        xpod = sum(v for k, v in probe["by_axes"].items() if "pod" in k) * chips
        terms = compute_terms(
            cfg, shape, mesh_name=mesh_name, executor=executor, chips=chips,
            hlo_flops=flops, hlo_bytes=byts,
            collective_bytes=coll_bytes, cross_pod_bytes=xpod)
        rec.update(
            status="ok",
            compile_s=round(time.monotonic() - t0, 1),
            chips=chips,
            memory={
                "argument_bytes": mem.argument_size_in_bytes,
                "output_bytes": mem.output_size_in_bytes,
                "temp_bytes": mem.temp_size_in_bytes,
                "alias_bytes": mem.alias_size_in_bytes,
                "peak_per_device_gb": round(
                    (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                     + mem.output_size_in_bytes - mem.alias_size_in_bytes)
                    / 1e9, 3),
            },
            cost={"flops": flops, "bytes": byts,
                  "probe_units": probe["probe_units"]},
            collectives={
                "schedule_ops": coll.count(),          # per-body schedule
                "schedule_by_kind": coll.bytes_by_kind(),
                "operand_bytes": coll_bytes,           # trip-scaled, all chips
                "ring_traffic_bytes": probe["ring"] * chips,
                "by_axes": {k: v * chips for k, v in probe["by_axes"].items()},
            },
            roofline=terms.to_dict(),
        )
        if verbose:
            print(f"[ok {rec['compile_s']:>6}s] {arch} × {shape_name} × "
                  f"{mesh_name} × {executor}/{pod_strategy}: "
                  f"flops={flops:.3e} bytes={byts:.3e} "
                  f"coll={coll.total_operand_bytes:.3e} "
                  f"dom={terms.dominant} frac={terms.roofline_frac:.3f}")
            print(f"    memory_analysis: {rec['memory']}")
    except Exception as e:  # noqa: BLE001 — record the failure, keep sweeping
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-2000:],
                   compile_s=round(time.monotonic() - t0, 1))
        if verbose:
            print(f"[ERR {rec['compile_s']:>5}s] {arch} × {shape_name} × "
                  f"{mesh_name}: {rec['error']}")
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--executor", default="sub_operator")
    ap.add_argument("--pod-strategy", default="dp", choices=["dp", "pp"])
    ap.add_argument("--all", action="store_true",
                    help="sweep all assigned archs × shapes")
    ap.add_argument("--out", default=None, help="append JSONL records here")
    args = ap.parse_args(argv)

    archs = list(ASSIGNED) if (args.all or args.arch is None) else [args.arch]
    shapes = [s.name for s in ALL_SHAPES] if (args.all or args.shape is None) \
        else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    records = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                rec = run_cell(arch, shape, multi_pod=mp,
                               executor=args.executor,
                               pod_strategy=args.pod_strategy)
                records.append(rec)
                if args.out:
                    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
                    with open(args.out, "a") as f:
                        f.write(json.dumps(rec) + "\n")
    n_ok = sum(r["status"] == "ok" for r in records)
    n_skip = sum(r["status"] == "skip" for r in records)
    n_err = sum(r["status"] == "error" for r in records)
    print(f"\ndry-run: {n_ok} ok, {n_skip} skip (documented), {n_err} error")
    return 1 if n_err else 0


if __name__ == "__main__":
    raise SystemExit(main())
