"""Reduction of a profiler trace to the numbers the per-layer metrics read.

A trace is first flattened (``flatten``) into a small neutral form:

    {"window": [t0_ns, t1_ns],
     "device": [[chip, line, name, start_ns, duration_ns], ...],
     "host":   [[name, start_ns, duration_ns], ...]}

``window`` is the traced interval: from the benchmark's own host marker
``bench:trace_begin`` to ``bench:trace_end``, stretched to hold every
device event the profiler recorded. ``device`` holds
the events of the chips' "XLA Modules" line (one event per execution of a
compiled program, named ``<module>(<id>)``) and "XLA Ops" line (one per
operation). ``host`` holds every host-thread event, the benchmark's
``dispatch:<program>`` markers among them. Everything below works on that
form, so it is tested on a small trace recorded on the chip and kept in
``bench/testdata``.
"""
from __future__ import annotations

import bisect
import re
from typing import Dict, List, Optional, Tuple

BEGIN, END = "bench:trace_begin", "bench:trace_end"
MODULES, OPS = "XLA Modules", "XLA Ops"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


def flatten(xspace_path: str) -> dict:
    """Read an ``.xplane.pb`` file into the neutral form."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(xspace_path)
    device, host, marks = [], [], {}
    for plane in data.planes:
        chip = _DEVICE_PLANE.match(plane.name)
        if chip:
            for line in plane.lines:
                if line.name in (MODULES, OPS):
                    device.extend([int(chip.group(1)), line.name, e.name,
                                   e.start_ns, e.duration_ns]
                                  for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    host.append([e.name, e.start_ns, e.duration_ns])
                    if e.name in (BEGIN, END):
                        marks[e.name] = e.start_ns
    if BEGIN not in marks or END not in marks:
        raise ValueError("trace holds no bench:trace_begin/end markers")
    # the profiler runs only between the markers, but the chip's clock is
    # mapped onto the host's with an offset of a millisecond or two: the
    # window stretches to hold every device event, so none is dropped
    t0 = min([marks[BEGIN]] + [e[3] for e in device])
    t1 = max([marks[END]] + [e[3] + e[4] for e in device])
    return {"window": [t0, t1], "device": device, "host": host}


def module_name(event_name: str) -> str:
    """``jit_block_step(123)`` -> ``jit_block_step``."""
    return event_name.split("(", 1)[0]


def _in_window(tr: dict, line: str):
    t0, t1 = tr["window"]
    for chip, ln, name, start, dur in tr["device"]:
        if ln == line and start >= t0 and start + dur <= t1:
            yield chip, name, start, dur


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge (start, end) intervals."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def chips(tr: dict) -> List[int]:
    return sorted({ev[0] for ev in tr["device"]})


def busy_intervals(tr: dict, chip: int) -> List[Tuple[float, float]]:
    """Union of the intervals in which a program or an operation ran on
    ``chip``."""
    return union([(s, s + d) for line in (MODULES, OPS)
                  for c, _, s, d in _in_window(tr, line) if c == chip])


def window_s(tr: dict) -> float:
    return (tr["window"][1] - tr["window"][0]) * 1e-9


def busy_s(tr: dict) -> float:
    """Device busy seconds, averaged over the chips that ran anything."""
    used = chips(tr)
    if not used:
        return 0.0
    total = sum(e - s for c in used for s, e in busy_intervals(tr, c))
    return total * 1e-9 / len(used)


def module_times(tr: dict) -> Dict[str, Tuple[int, float]]:
    """Per compiled program: (executions, device seconds), summed over
    chips."""
    out: Dict[str, Tuple[int, float]] = {}
    for _, name, _, dur in _in_window(tr, MODULES):
        n, t = out.get(module_name(name), (0, 0.0))
        out[module_name(name)] = (n + 1, t + dur * 1e-9)
    return out


def op_name(event_name: str) -> str:
    """``%while.23 = (s32[], ...) while(...)`` -> ``while.23``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def top_ops(tr: dict, k: int = 10) -> List[list]:
    """The operations that took most device time, each named by the
    program it ran in and its HLO name: [["<module>/<op>", seconds], ...]."""
    runs: Dict[int, List[Tuple[float, float, str]]] = {}
    for chip, name, start, dur in _in_window(tr, MODULES):
        runs.setdefault(chip, []).append((start, start + dur,
                                          module_name(name)))
    starts = {c: [r[0] for r in sorted(v)] for c, v in runs.items()}
    runs = {c: sorted(v) for c, v in runs.items()}
    tot: Dict[str, float] = {}
    for chip, name, start, dur in _in_window(tr, OPS):
        label = op_name(name)
        i = bisect.bisect_right(starts.get(chip, []), start) - 1
        if i >= 0 and start < runs[chip][i][1]:
            label = f"{runs[chip][i][2]}/{label}"
        tot[label] = tot.get(label, 0.0) + dur * 1e-9
    return [[n, t] for n, t in sorted(tot.items(), key=lambda x: -x[1])[:k]]


def idle_gaps(tr: dict, k: int = 10, chip: Optional[int] = None
              ) -> List[list]:
    """The longest idle gaps of one chip (the first used, by default) in
    the window, each named by the host events that overlap it, longest
    overlap first: [["<host events>", seconds], ...]."""
    used = chips(tr)
    if not used:
        return []
    t0, t1 = tr["window"]
    busy = busy_intervals(tr, used[0] if chip is None else chip)
    edges = [t0] + [x for iv in busy for x in iv] + [t1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:k]:
        overlap: Dict[str, float] = {}
        for name, hs, hd in tr["host"]:
            if name in (BEGIN, END):
                continue
            o = min(e, hs + hd) - max(s, hs)
            if o > 0 or s <= hs < e:
                overlap[name] = overlap.get(name, 0.0) + max(o, 0.0)
        names = sorted(overlap, key=lambda n: -overlap[n])[:3]
        label = ("host: " + "; ".join(names)) if names else "host: no event"
        out.append([f"at +{(s - t0) * 1e-9:.6f}s {label}", (e - s) * 1e-9])
    return out
