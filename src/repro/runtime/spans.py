"""Host spans and counters of the serving loop: one table per run.

``SpanTable.span(phase, **meta)`` times one phase of a block boundary on the
host's monotonic clock. By default the phase is also a
``jax.profiler.TraceAnnotation`` named ``serve:<phase>``, with ``meta`` (a
request id, a slot) as its stats, so that a profiler session records it on
the host plane, on the device trace's clock; without a session that costs
under a microsecond. ``emit=False`` keeps a span in memory only, for spans
that enclose others: a trace reader that names an idle gap by the host
events overlapping it would otherwise name every gap after the enclosing
span. A span records its duration only when its body returns normally: a
dispatch that raised took no step of the serve.

``sample(name, value)`` records one reading of a counter. A decode boundary
runs from ``open_boundary()`` to ``close_boundary()``; its host time is its
wall time less the phases in which the host waits on the device
(``WAITS``: the chunk and decode syncs, and a monolithic or drain prefill,
whose one span holds its dispatch and its sync).
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional

import numpy as np
from jax.profiler import TraceAnnotation

PREFIX = "serve:"
WAITS = ("chunk_wait", "decode_wait", "prefill")


class Span:
    """One timed phase; ``t0`` is its start, ``seconds`` its duration once
    it has exited normally (None before, or after an exception)."""
    __slots__ = ("table", "phase", "annotation", "t0", "seconds")

    def __init__(self, table: "SpanTable", phase: str,
                 annotation: Optional[TraceAnnotation]):
        self.table, self.phase, self.annotation = table, phase, annotation
        self.t0 = 0.0
        self.seconds: Optional[float] = None

    def __enter__(self) -> "Span":
        if self.annotation is not None:
            self.annotation.__enter__()
        self.t0 = time.monotonic()
        return self

    def __exit__(self, exc_type, exc, tb):
        t1 = time.monotonic()
        if exc_type is None:
            self.seconds = t1 - self.t0
            self.table.add(self.phase, self.seconds)
        if self.annotation is not None:
            self.annotation.__exit__(exc_type, exc, tb)
        return False


class SpanTable:
    """Durations per phase, counter readings and per-boundary host time of
    one run."""

    def __init__(self):
        self.durations: Dict[str, List[float]] = {}
        self.totals: Dict[str, float] = {}
        self.samples: Dict[str, List[float]] = {}
        self.boundary_host: List[float] = []
        self._boundary: Optional[tuple] = None

    # -- spans ----------------------------------------------------------
    def span(self, phase: str, emit: bool = True, **meta) -> Span:
        return Span(self, phase,
                    TraceAnnotation(PREFIX + phase, **meta) if emit else None)

    def add(self, phase: str, seconds: float):
        self.durations.setdefault(phase, []).append(seconds)
        self.totals[phase] = self.totals.get(phase, 0.0) + seconds

    def count(self, phase: str) -> int:
        return len(self.durations.get(phase, ()))

    def total(self, *phases: str) -> float:
        """Seconds summed over the phases."""
        return sum(self.totals.get(p, 0.0) for p in phases)

    def seconds(self, phase: str) -> List[float]:
        return self.durations.get(phase, [])

    # -- counters -------------------------------------------------------
    def sample(self, name: str, value: float):
        self.samples.setdefault(name, []).append(value)

    def mean(self, name: str) -> Optional[float]:
        vals = self.samples.get(name)
        return float(np.mean(vals)) if vals else None

    # -- decode boundaries ------------------------------------------------
    def open_boundary(self):
        self._boundary = (time.monotonic(), self.total(*WAITS))

    def close_boundary(self):
        """Record the boundary opened last (in memory only): its wall time
        under ``boundary`` and its host time, the wall time less the
        device waits inside it."""
        t0, waited = self._boundary
        wall = time.monotonic() - t0
        self.add("boundary", wall)
        self.boundary_host.append(wall - (self.total(*WAITS) - waited))
        self._boundary = None

    # -- summaries ------------------------------------------------------
    def summary(self) -> Dict[str, Dict[str, float]]:
        """Per phase: n, total, p50, p90 and max in milliseconds."""
        out = {}
        for phase, secs in self.durations.items():
            ms = np.asarray(secs) * 1e3
            out[phase] = {"n": len(secs),
                          "total_ms": self.totals[phase] * 1e3,
                          "p50_ms": float(np.percentile(ms, 50)),
                          "p90_ms": float(np.percentile(ms, 90)),
                          "max_ms": float(ms.max())}
        return out

    def boundary_summary(self) -> Dict[str, float]:
        """Decode boundaries: n and the p50 and p90 of host time."""
        host = np.asarray(self.boundary_host or [0.0]) * 1e3
        return {"n": len(self.boundary_host),
                "host_p50_ms": float(np.percentile(host, 50)),
                "host_p90_ms": float(np.percentile(host, 90))}
