"""Recursive jaxpr traversal with trip-count multipliers.

Serving programs nest: jit wrappers, the T-micro-step ``lax.scan`` of a
decode block, vmapped cache writes, cond branches. Every verifier pass
that counts or sizes eqns (routed hops, callbacks, DUS writes) must see
through that nesting AND weight body eqns by how often they run — a hop
inside a ``scan(length=T)`` moves T× the bytes of the same hop at top
level.

``while`` bodies have no static trip count; they are traversed with an
``unbounded`` flag so passes can refuse to reason about them rather than
under-count silently (no serving program uses while today).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Iterator, List, Optional, Tuple

from jax.extend import core as jax_core


@dataclass(frozen=True)
class EqnSite:
    eqn: Any               # jax.extend.core.JaxprEqn
    trips: int             # product of enclosing static scan lengths
    unbounded: bool        # inside a while body (trips is a lower bound)


def _subjaxprs(params) -> List[jax_core.Jaxpr]:
    """All jaxprs stashed in an eqn's params (closed or open, incl. inside
    tuples/lists — cond branches, custom_vjp pairs, pallas kernels)."""
    out: List[jax_core.Jaxpr] = []

    def visit(v):
        if isinstance(v, jax_core.ClosedJaxpr):
            out.append(v.jaxpr)
        elif isinstance(v, jax_core.Jaxpr):
            out.append(v)
        elif isinstance(v, (tuple, list)):
            for x in v:
                visit(x)

    for v in params.values():
        visit(v)
    return out


def iter_eqns(jaxpr, trips: int = 1, unbounded: bool = False) \
        -> Iterator[EqnSite]:
    """Yield every eqn in ``jaxpr`` and its subjaxprs as an EqnSite."""
    if isinstance(jaxpr, jax_core.ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        yield EqnSite(eqn, trips, unbounded)
        name = eqn.primitive.name
        sub_trips, sub_unbounded = trips, unbounded
        if name == "scan":
            sub_trips = trips * int(eqn.params.get("length", 1))
        elif name == "while":
            sub_unbounded = True
        for sub in _subjaxprs(eqn.params):
            yield from iter_eqns(sub, sub_trips, sub_unbounded)


def named_jit_sites(jaxpr, names) -> List[Tuple[str, EqnSite]]:
    """(name, site) for every nested jit eqn whose name is in ``names`` — the
    anchor used by routing_check to find the tagged W↔A hop markers."""
    names = set(names)
    out = []
    for site in iter_eqns(jaxpr):
        if site.eqn.primitive.name == "jit" \
                and site.eqn.params.get("name") in names:
            out.append((site.eqn.params["name"], site))
    return out


def primitive_sites(jaxpr, prim_names) -> List[EqnSite]:
    prim_names = set(prim_names)
    return [s for s in iter_eqns(jaxpr)
            if s.eqn.primitive.name in prim_names]


def literal_value(v) -> Optional[int]:
    """Int value of a jaxpr literal operand, None if traced."""
    if isinstance(v, jax_core.Literal):
        try:
            return int(v.val)
        except (TypeError, ValueError):
            return None
    return None


def aval_bytes(aval) -> int:
    import numpy as np
    return int(np.prod(aval.shape, dtype=np.int64)) * aval.dtype.itemsize


__all__ = ["EqnSite", "iter_eqns", "named_jit_sites", "primitive_sites",
           "literal_value", "aval_bytes"]
