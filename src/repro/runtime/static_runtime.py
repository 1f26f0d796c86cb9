"""Static AOT runtime — the TPU analogue of the paper's pinned thread pool
(§4.3).

The paper replaces OpenMP's dynamic scheduling with threads pinned once at
init, deterministic shard→core maps, and state-transition execution loops.
The JAX analogue of each piece:

  pinned threads / fixed shard→core map  → shardings fixed at compile time,
                                            AOT ``.lower().compile()``
  no per-task queue or dynamic dispatch  → compiled executable cached by
                                            (step-name, shape signature);
                                            dispatch = one cached call, ZERO
                                            retracing on the critical path
  cache warmup / first-touch placement   → explicit warmup() that materializes
                                            params/caches with their final
                                            shardings before serving starts

Fig 10's "thread pool vs OpenMP" ablation maps to: cached AOT dispatch vs
re-tracing dispatch — benchmarks/fig10_runtime.py measures both on CPU.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax


class DispatchError(RuntimeError):
    """A program dispatch failed before the compiled call ran (transient
    driver hiccup, injected fault). Raised by dispatch interceptors BEFORE
    ``Compiled.__call__`` touches its operands, so donated buffers are
    still valid and the caller may retry the dispatch verbatim. The serving
    engine's retry/quarantine path (DESIGN.md §7) catches exactly this
    type — anything else is a real bug and propagates."""


@dataclass
class CompiledStep:
    name: str
    compiled: Any                    # jax.stages.Compiled
    lowered: Any                     # jax.stages.Lowered (kept for analysis)
    compile_s: float
    calls: int = 0
    # retained for static analysis (repro.analysis): the traced callable and
    # its abstract signature let the verifier re-derive the jaxpr of the
    # EXACT program that serves — no shadow re-implementation to drift
    fn: Optional[Callable] = None
    abstract_args: Optional[Tuple] = None
    donate_argnums: Tuple[int, ...] = ()
    static_argnums: Tuple[int, ...] = ()
    # build-time statics baked into this program that do NOT show in the
    # name or signature (e.g. the WA backend's sub-operator overlap depth)
    # — surfaced through StaticRuntime.stats() so a serve log can say
    # WHICH variant of a program it dispatched
    meta: Optional[Dict[str, Any]] = None
    # dispatch interceptor (fault injection / tracing). Runs BEFORE the
    # compiled call: raising DispatchError here models a dispatch that
    # never reached the device — donated operands stay valid, the dispatch
    # is retryable. Installed fleet-wide via StaticRuntime.set_interceptor.
    interceptor: Optional[Callable[[str], None]] = None

    def __call__(self, *args):
        if self.interceptor is not None:
            self.interceptor(self.name)
        self.calls += 1
        return self.compiled(*args)

    def cost_analysis(self):
        return self.compiled.cost_analysis()

    def memory_analysis(self):
        return self.compiled.memory_analysis()

    def jaxpr(self):
        """ClosedJaxpr of the step as traced at compile time (for the
        static verifier's jaxpr-level passes)."""
        if self.fn is None or self.abstract_args is None:
            raise ValueError(f"step {self.name!r} kept no trace inputs")
        if self.static_argnums:
            raise ValueError(f"step {self.name!r} has static argnums; "
                             "jaxpr() supports fully-traced steps only")
        return jax.make_jaxpr(self.fn)(*self.abstract_args)


class StaticRuntime:
    """AOT compile cache keyed on (name, mesh, abstract arg signature)."""

    def __init__(self, mesh=None):
        self.mesh = mesh
        self._cache: Dict[Tuple, CompiledStep] = {}
        self._interceptor: Optional[Callable[[str], None]] = None

    def set_interceptor(self, fn: Optional[Callable[[str], None]]):
        """Install (or clear, with None) a dispatch interceptor on every
        compiled step — existing and future. The hook runs at the top of
        each dispatch with the program name; raising ``DispatchError``
        models a failed dispatch (operands untouched, retry-safe), sleeping
        models a stalled one. This is the single injection point the chaos
        harness (``repro.runtime.faults``) uses."""
        self._interceptor = fn
        for step in self._cache.values():
            step.interceptor = fn

    # ------------------------------------------------------------------
    @staticmethod
    def _sig(args) -> Tuple:
        # weak_type participates in the signature: a weakly-typed scalar
        # (e.g. a bare python int leaking into an operand slot) traces to a
        # DIFFERENT program than the committed-dtype one and silently
        # recompiles on the serving path.  The compile-once auditor
        # (repro.analysis.compile_once) flags any weak-typed leaf.
        leaves = jax.tree_util.tree_leaves(args)
        return tuple((getattr(x, "shape", None), str(getattr(x, "dtype", "")),
                      bool(getattr(x, "weak_type", False)))
                     for x in leaves)

    def compile_step(self, name: str, fn: Callable, abstract_args: Tuple,
                     in_shardings=None, out_shardings=None,
                     donate_argnums: Tuple[int, ...] = (),
                     static_argnums: Tuple[int, ...] = (),
                     meta: Optional[Dict[str, Any]] = None) -> CompiledStep:
        key = (name, id(self.mesh), self._sig(abstract_args))
        if key in self._cache:
            return self._cache[key]
        t0 = time.monotonic()
        jitted = jax.jit(fn,
                         in_shardings=in_shardings,
                         out_shardings=out_shardings,
                         donate_argnums=donate_argnums,
                         static_argnums=static_argnums)
        lowered = jitted.lower(*abstract_args)
        compiled = lowered.compile()
        step = CompiledStep(name, compiled, lowered,
                            compile_s=time.monotonic() - t0,
                            fn=fn, abstract_args=abstract_args,
                            donate_argnums=tuple(donate_argnums),
                            static_argnums=tuple(static_argnums),
                            meta=dict(meta) if meta else None,
                            interceptor=self._interceptor)
        self._cache[key] = step
        return step

    def get(self, name: str, abstract_args) -> Optional[CompiledStep]:
        return self._cache.get((name, id(self.mesh), self._sig(abstract_args)))

    # ------------------------------------------------------------------
    def warmup(self, step: CompiledStep, *args):
        """First-touch analogue: run once so buffers land with their final
        shardings/layouts before the latency-critical loop starts."""
        out = step(*args)
        jax.block_until_ready(out)
        return out

    def stats(self) -> Dict[str, Dict]:
        """Per-step-name compile/call accounting. ``compiles`` counts distinct
        (mesh, signature) variants — a steady-state serving loop must show
        compiles == 1 per step with only ``calls`` growing (zero retracing
        across admissions; the §4.3 pinned-pool invariant)."""
        out: Dict[str, Dict] = {}
        for (name, *_), s in self._cache.items():
            rec = out.setdefault(name,
                                 {"compiles": 0, "compile_s": 0.0, "calls": 0})
            rec["compiles"] += 1
            rec["compile_s"] += s.compile_s
            rec["calls"] += s.calls
            if s.meta:
                rec.update(s.meta)
        return out
