"""One benchmark run: set-up, the measured window, the per-layer readings and
the correctness check, for one cell of ``BENCHMARK.json``.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file found by name (``find``): ``configs/<name>.json``,
``architectures/<name>.py`` (what a configuration's architecture means to
the program, its weights and its needed work; see ``architectures/
dense_gqa.py``), ``traffic/<name>.json``, ``metrics/<name>.py``,
``references/<name>.py``, ``cells/<workload>.json`` (the cell's limits) and
``devices.json`` (peaks by device kind). A new cell, mix, configuration,
architecture or metric is a new file.

The entry the window drives is ``ServingEngine.run`` of the program under
test: colocated backend, continuous scheduler, decode blocks of
``BLOCK_SIZE`` tokens, one full-extent decode program, the chunked prefill
lane, bf16, greedy decoding, no stop token (every request produces exactly
its drawn output length). The loop is closed: the backlog is served through
exactly ``concurrency`` slots, so a request enters when one completes.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Tuple

import numpy as np

from bench import check, e2e, trace_reduce, traffic_gen
from bench import weights as W

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent
BLOCK_SIZE = 8
NO_STEP_LIMIT = 1 << 40          # run() must never cut the backlog short
TRACE_AFTER, TRACE_FOR_MAX = 0.2, 3.0   # traced slice: start (share), length


# -- discovery ------------------------------------------------------------

def find(kind: str, name: str, root: Path = BENCH) -> Path:
    suffix = ".py" if kind in ("metrics", "references",
                               "architectures") else ".json"
    path = root / kind / f"{name}{suffix}"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file for {name!r} at {path}")
    return path


def load_json(kind: str, name: str, root: Path = BENCH) -> dict:
    return json.loads(find(kind, name, root).read_text())


def load_module(kind: str, name: str, root: Path = BENCH):
    path = find(kind, name, root)
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def architecture(cfg: dict, root: Path = BENCH):
    """The module of a configuration's ``"architecture"``."""
    return load_module("architectures", cfg["architecture"], root)


def peaks_for(device_kind: str, root: Path = BENCH) -> dict:
    table = json.loads((root / "devices.json").read_text())
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in the peak "
                       "table devices.json")
    return table[device_kind]


def benchmark(root: Path = REPO) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


# -- the program under test -------------------------------------------------

def make_weights(arch, cfg: dict, api, seed: int):
    """The served weights, made on the device by one compiled call."""
    import jax
    shapes = jax.eval_shape(api.init, jax.random.key(0))
    dtype = jax.numpy.dtype(cfg["torch_dtype"])
    make = jax.jit(lambda key: W.to_program_tree(
        arch, W.stacked(arch, cfg, key, dtype), shapes))
    return jax.block_until_ready(make(W.root_key(seed)))


def make_engine(cfg: dict, mix: dict, api, injector=None):
    from repro.models import NULL_CTX
    from repro.runtime.serving import ServingEngine
    return ServingEngine(
        api, NULL_CTX, mix["concurrency"], mix["prompt_tokens"]["max"],
        mode="continuous", backend="colocated",
        max_new_cap=cfg["max_new_tokens"], block_size=BLOCK_SIZE,
        kv_bucket_chunk=0, prefill_chunk=mix["prefill_chunk"],
        fault_injector=injector)


def backlog(limits: dict, mix: dict, seconds: float) -> int:
    """Requests of one window: the cell's ``requests_per_s`` (its rate
    on the chip when the cell was made) times the seconds, at least one
    per client. The same for every seed and every run."""
    return max(mix["concurrency"],
               int(round(limits["requests_per_s"] * seconds)))


def requests(draws, rid0: int = 0):
    from repro.runtime.serving import Request
    return [Request(rid=rid0 + i, prompt=d.prompt,
                    max_new_tokens=d.max_new_tokens, eos_id=-1)
            for i, d in enumerate(draws)]


def compiles(engine) -> Dict[str, int]:
    return {k: v["compiles"] for k, v in engine.rt.stats().items()}


class Collections:
    """Times Python's garbage collections while the context is open."""

    def __init__(self):
        self.count, self.total, self.longest = 0, 0.0, 0.0
        self._t0 = 0.0

    def _callback(self, phase, _info):
        if phase == "start":
            self._t0 = time.monotonic()
        else:
            dt = time.monotonic() - self._t0
            self.count += 1
            self.total += dt
            self.longest = max(self.longest, dt)

    def __enter__(self):
        gc.callbacks.append(self._callback)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._callback)


class Lowerings:
    """Counts the programs JAX lowers while the context is open, whatever
    calls them (a compile, or a load from the persistent cache)."""
    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        self.count = 0

    def _listen(self, name, _secs, **_kw):
        if name == self.EVENT:
            self.count += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._listen)


def program_modules(engine) -> Dict[str, str]:
    """Program name -> the HLO module name its executions carry in a device
    trace (``jit_<traced function>``). Read from the runtime's table of
    compiled steps, which has no public accessor: where it is gone the run
    fails here rather than leave the trace readers without programs."""
    return {s.name: f"jit_{s.fn.__name__}" for s in engine.rt._cache.values()}


# -- the traced slice -------------------------------------------------------

class Recorder:
    """Dispatch hook of the measured run (``--trace 1`` only): starts the
    profiler a share into the window and stops it a few seconds later,
    each time at a decode-block dispatch (when the previous program has
    been synced), marks every dispatch in between in the trace, and counts
    the work each decode block needs from the slots' cursors and budgets
    as the block is dispatched, one micro-step at a time, by the
    architecture's ``micro_step_need``. Starting and stopping the profiler
    stalls the engine for as long as the profiler takes to collect the
    slice (up to about a minute); ``stall_s`` is that time, which the
    per-layer readers leave out of the window."""

    def __init__(self, cfg: dict, arch, seconds: float, trace_dir: str):
        self.cfg, self.arch, self.dir = cfg, arch, trace_dir
        self.offset = TRACE_AFTER * seconds
        self.length = min(TRACE_FOR_MAX, 0.3 * seconds)
        self.engine = None
        self.armed = False
        self.state = "idle"
        self.t_first: Optional[float] = None
        self.stall_s = 0.0
        self.need = {"blocks": 0, "bytes": 0, "flops": 0}
        # a fault of the count, raised after the window: an exception in
        # the dispatch hook would reach the engine's retry logic instead
        self.error: Optional[str] = None

    def on_dispatch(self, name: str):
        if not self.armed:
            return
        import jax
        now = time.monotonic()
        if self.t_first is None:
            self.t_first = now
        block = name.endswith("decode_block")
        if block and self.state == "idle" and \
                now >= self.t_first + self.offset:
            # device and runtime events only: Python's own tracer would
            # record every call of the engine's host loop and slow it
            jax.profiler.start_trace(self.dir,
                                     profiler_options=_profile_options())
            with jax.profiler.TraceAnnotation(trace_reduce.BEGIN):
                pass
            self.state = "on"
            self.stall_s += time.monotonic() - now
        elif block and self.state == "on" and \
                now >= self.t_first + self.offset + self.length:
            self.stop()
            self.stall_s += time.monotonic() - now
        if self.state != "on":
            return
        with jax.profiler.TraceAnnotation(f"dispatch:{name}"):
            pass
        if block:
            self._count_block()

    def _count_block(self):
        # the slots' cursors, read from the engine's scheduler, which has no
        # public accessor
        try:
            sched = self.engine._sched
            pos, rem = sched.positions, sched.remaining
            live = np.flatnonzero(sched.decode_active())
        except AttributeError as e:
            self.error = self.error or f"slot cursors unreadable: {e}"
            return
        # row i produces min(BLOCK_SIZE, remaining) tokens, the t-th of them
        # at micro-step t, attending its cursor + t + 1 positions
        n = {int(i): int(min(BLOCK_SIZE, rem[i])) for i in live}
        for t in range(max(n.values(), default=0)):
            nbytes, flops = self.arch.micro_step_need(
                self.cfg, [int(pos[i]) + t + 1 for i in n if n[i] > t])
            self.need["bytes"] += nbytes
            self.need["flops"] += flops
        self.need["blocks"] += 1

    def stop(self):
        if self.state == "on":
            import jax
            with jax.profiler.TraceAnnotation(trace_reduce.END):
                pass
            jax.profiler.stop_trace()
            self.state = "done"


@dataclass
class Window:
    """What the per-layer metric readers read."""
    cfg: dict
    arch: object                            # the configuration's module
    peaks: dict
    slots: int
    block_size: int
    stats: dict
    window_s: float
    completed: List[Tuple[int, int]]        # (prompt tokens, generated)
    modules: Dict[str, str] = field(default_factory=dict)
    trace: Optional[dict] = None
    decode_need: Optional[dict] = None

    def program_time(self, program: str) -> Tuple[int, float]:
        """(executions, device seconds) of a program in the trace; (0, 0.0)
        without a trace. A program the engine does not have, or one with no
        execution in the trace, is an error: a reader of a cell that lists
        it expects to find it."""
        if self.trace is None:
            return 0, 0.0
        if program not in self.modules:
            raise LookupError(f"the engine has no program {program!r} "
                              f"(it has {sorted(self.modules)})")
        times = trace_reduce.module_times(self.trace)
        if self.modules[program] not in times:
            raise LookupError(f"no execution of {program!r} (HLO module "
                              f"{self.modules[program]!r}) in the trace")
        return times[self.modules[program]]

    @property
    def trace_busy_s(self) -> float:
        return trace_reduce.busy_s(self.trace) if self.trace else 0.0

    @property
    def trace_window_s(self) -> float:
        return trace_reduce.window_s(self.trace) if self.trace else 0.0


def _profile_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def note(msg: str):
    """A line of the run's diagnostics, on standard error."""
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def _trace_file(trace_dir: str) -> Optional[str]:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    return str(found[-1]) if found else None


# -- one run ----------------------------------------------------------------

def measure(cfg: dict, mix: dict, limits: dict, reference, seed: int,
            seconds: float, traced: bool, end_to_end: List[dict],
            per_layer: List[dict], t_process: float, devices,
            peaks: dict) -> dict:
    """Set up, serve the measured backlog, read the cell's metrics (its
    ``end_to_end`` entries of ``BENCHMARK.json``, or with ``traced`` its
    ``per_layer`` ones), check the output. Returns the result line (without
    printing it)."""
    from repro.models import build_model

    dev = devices[0]
    arch = architecture(cfg)
    api = build_model(arch.program_config(cfg))
    params = make_weights(arch, cfg, api, seed)
    note(f"weights made {time.monotonic() - t_process:.3f} s after start")
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if traced else None
    rec = Recorder(cfg, arch, seconds, trace_dir) if traced else None
    engine = make_engine(cfg, mix, api, injector=rec)
    if rec is not None:
        rec.engine = engine
    vocab, conc = cfg["vocab_size"], mix["concurrency"]

    # warm-up: a short closed loop on other draws of the seed, which
    # compiles (or loads) every program the window runs
    warm = requests(traffic_gen.draw(mix, mix["warmup_requests"], seed,
                                     traffic_gen.WARMUP, vocab))
    t0 = time.monotonic()
    engine.run(params, warm, max_steps=NO_STEP_LIMIT)
    note(f"warm-up of {len(warm)} requests took "
         f"{time.monotonic() - t0:.3f} s")
    # the backlog: a fixed number of requests for a window of ``seconds``
    n = backlog(limits, mix, seconds)
    reqs = requests(traffic_gen.draw(mix, n, seed, traffic_gen.MEASURED,
                                     vocab))
    before = compiles(engine)
    if rec is not None:
        rec.armed = True
    # the heap that set-up left (modules, JAX's caches, the programs) is
    # set aside, so a full collection in the window scans only what the
    # window allocates
    gc.collect()
    gc.freeze()
    with Lowerings() as lowered, Collections() as collected:
        stats = engine.run(params, reqs, max_steps=NO_STEP_LIMIT)
    gc.unfreeze()
    if rec is not None:
        rec.stop()
    after = compiles(engine)

    done = [r for r in reqs if r.status == "completed"
            and len(r.generated) == r.max_new_tokens]
    t_start = min(r.t_enqueue for r in reqs)
    t_end = max(r.t_done for r in reqs)
    setup_s = t_start - t_process
    window_s = t_end - t_start
    note(f"set-up {setup_s:.3f} s, window {window_s:.3f} s, {n} requests, "
         f"{time.monotonic() - t_end:.3f} s from the last completion to "
         "here")
    stamps = np.sort([t for r in done for t in (r.t_first_token, r.t_done)])
    if len(stamps):
        gaps = np.diff(np.concatenate([[t_start], stamps]))
        i = int(np.argmax(gaps))
        note(f"longest time without a first token or a completion: "
             f"{gaps[i]:.3f} s, ending at +{stamps[i] - t_start:.3f} s")
    note(f"{collected.count} garbage collections in the window, longest "
         f"{collected.longest:.3f} s, {collected.total:.3f} s in all")
    served = [e2e.Served(r.t_admitted, r.t_first_token, r.t_done,
                         len(r.generated)) for r in done]
    mem_peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")

    result_metrics: Dict[str, dict] = {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": mem_peak}
    breakdown = None
    if traced:
        t0 = time.monotonic()
        path = _trace_file(trace_dir)
        if path is None or rec.error is not None:
            raise RuntimeError(rec.error or "the profiler wrote no trace "
                               f"(started: {rec.state != 'idle'})")
        trace = trace_reduce.flatten(path)
        note(f"trace of {len(trace['device'])} device and "
             f"{len(trace['host'])} host events read in "
             f"{time.monotonic() - t0:.3f} s")
        shutil.rmtree(trace_dir, ignore_errors=True)
        note(f"the profiler stalled the window for {rec.stall_s:.3f} s")
        w = Window(cfg=cfg, arch=arch, peaks=peaks, slots=conc,
                   block_size=BLOCK_SIZE, stats=stats,
                   window_s=window_s - rec.stall_s,
                   completed=[(len(r.prompt), len(r.generated))
                              for r in done],
                   modules=program_modules(engine), trace=trace,
                   decode_need=rec.need)
        for m in per_layer:
            value = load_module("metrics", m["name"]).read(w)
            if value is not None:
                result_metrics[m["name"]] = {"value": value,
                                             "unit": m["unit"]}
        device["busy_s"] = w.trace_busy_s
        device["window_s"] = w.trace_window_s
        breakdown = {"device_ops": trace_reduce.top_ops(trace),
                     "idle_gaps": trace_reduce.idle_gaps(trace)}
    else:
        values = dict(e2e.metrics(served, conc, t_start, t_end),
                      setup_s=setup_s)
        for m in end_to_end:
            result_metrics[m["name"]] = {"value": values[m["name"]],
                                         "unit": m["unit"]}

    # correctness: free the program's state, then the reference on a
    # sample of the completed requests drawn from the seed
    sample_reqs = [done[i] for i in check.pick(
        done, mix["check_requests"],
        traffic_gen.rng_for(seed, traffic_gen.SAMPLE))] if done else []
    new_compiles = lowered.count + sum(after.get(k, 0) - before.get(k, 0)
                                       for k in after)
    del engine, params, stats
    if rec is not None:
        rec.engine = None
    gc.collect()
    gap = math.inf
    if sample_reqs:
        sample = check.build(sample_reqs, traffic_gen.kv_extent(
            mix, cfg["max_new_tokens"]))
        t0 = time.monotonic()
        ref = reference.logits(cfg, W.root_key(seed), sample.tokens,
                               sample.score_pos)
        gap = check.widest_gap(ref, sample.served, sample.mask)
        del ref
        note(f"reference over {sample.n_tokens} served tokens of "
             f"{len(sample_reqs)} requests in {time.monotonic() - t0:.3f} s")
    checks = {
        "widest_logit_gap": [gap if math.isfinite(gap) else str(gap),
                             limits["widest_logit_gap"]],
        "failed_requests": [n - len(done), 0],
        "window_compiles": [new_compiles, 0],
    }
    correct = bool(gap <= limits["widest_logit_gap"]) and len(done) == n \
        and new_compiles == 0
    for name, (value, limit) in checks.items():
        print(f"check {name} {value} limit {limit}", file=sys.stderr)
    out = {"correct": correct, "attempted": n, "failed": n - len(done),
           "metrics": result_metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in checks.items()}
    return out


def cell(bm: dict, workload: str):
    """(workload entry, configuration dict, traffic mix) of a cell."""
    wl = next((w for w in bm["workloads"] if w["name"] == workload), None)
    if wl is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    entry = next(c for c in bm["configs"] if c["name"] == wl["config"])
    cfg = json.loads((REPO / entry["file"]).read_text())
    return wl, cfg, load_json("traffic", wl["traffic"])


def per_layer_for(bm: dict, workload: str) -> List[dict]:
    return [m for m in bm["per_layer"]
            if workload in m.get("workloads", [workload])]


def end_to_end_for(bm: dict, workload: str) -> List[dict]:
    return [m for m in bm["end_to_end"]
            if workload in m.get("workloads", [workload])]
