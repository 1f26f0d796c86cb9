"""Routing-bytes cross-check (pass 4).

Invariant ("only embeddings move", §3.1): each WA serving program routes
exactly ``2 × n_layers`` W↔A hops per micro-step — 3 W→A (q,k,v) and
1 A→W (attention output) per layer — and the analytic meter
``WABackend.expected_routing`` / ``core.wa.routing_bytes`` claims precisely
those bytes. This pass recomputes the hop traffic FROM THE PROGRAM: it
walks the jaxpr for the tagged hop markers (``wa_hop_to_a`` /
``wa_hop_to_w`` jit eqns, scan-trip-weighted) and fails on any drift —
a dropped hop (a layer silently bypassing the A domain), an extra hop, or
a meter constant that no longer matches what the compiled program moves.

Sub-operator overlap (``overlap`` = D > 1) scales the hop COUNT of the
slotted decode programs, not the bytes: the pipelined layer loop routes
each micro-batch separately, so a decode micro-step carries ``D × 3L``
W→A and ``D × L`` A→W hops of ``rows / D`` rows each. Chunk/admission
programs are batch-1 and never pipeline (D = 1 for them regardless of the
knob).

The bytes identity: per micro-step the A→W hops carry
``L × rows × n_heads × head_dim × el`` bytes IN TOTAL across micro-batches
(depth-invariant) while the analytic meter claims
``2 × L × rows × d_model × el``, so

    2 × d_model × Σ(A→W hop bytes)  ==  (n_heads × head_dim) × analytic

holds exactly in integers for every current program at every overlap
depth — checked per program with no tolerance.
"""
from __future__ import annotations

import numpy as np

from repro.analysis.findings import Report
from repro.analysis.jaxpr_walk import named_jit_sites
from repro.analysis.programs import Cell
from repro.core.wa import WA_HOP_TO_A, WA_HOP_TO_W, routing_bytes

PASS = "routing_check"


def _hop_stats(jaxpr):
    """{tag: (weighted_count, weighted_bytes, dtypes)} over tagged hops."""
    stats = {WA_HOP_TO_A: [0, 0, set()], WA_HOP_TO_W: [0, 0, set()]}
    for tag, site in named_jit_sites(jaxpr, stats):
        aval = site.eqn.invars[0].aval
        nbytes = int(np.prod(aval.shape, dtype=np.int64))\
            * aval.dtype.itemsize
        stats[tag][0] += site.trips
        stats[tag][1] += site.trips * nbytes
        stats[tag][2].add(str(aval.dtype))
        if site.unbounded:
            return None
    return {k: (c, b, d) for k, (c, b, d) in stats.items()}


def check_routing(cell: Cell, report: Report):
    if cell.spec.backend != "wa":
        return
    backend = cell.backend
    cfg = cell.cfg
    mesh_on = cell.mesh is not None
    for rec in cell.records:
        if not rec.name.startswith("serve_wa_")\
                or rec.kind in ("reset", "swap_out", "swap_in"):
            # reset and the preemption swap pair are cache-only programs:
            # zero W↔A hops by construction, no routing model to check
            continue
        try:
            rows, trips = backend.expected_routing(rec.name)
        except KeyError as e:
            report.error(PASS, rec.name, "routing model", str(e))
            continue
        if not mesh_on:
            # mesh=None no-ops every constraint — nothing to cross-check
            report.info(PASS, rec.name, "hops",
                        "no mesh: hops are no-ops, cross-check skipped")
            continue
        try:
            jaxpr = rec.step.jaxpr()
        except (ValueError, TypeError) as e:
            report.error(PASS, rec.name, "jaxpr",
                         f"could not retrace for hop audit: {e}")
            continue
        stats = _hop_stats(jaxpr)
        if stats is None:
            report.error(PASS, rec.name, "while",
                         "hops inside an unbounded while loop — static "
                         "byte accounting impossible")
            continue
        to_a_n, _to_a_b, _ = stats[WA_HOP_TO_A]
        to_w_n, to_w_b, to_w_dt = stats[WA_HOP_TO_W]
        L = cfg.n_layers
        # overlap depth D multiplies the hop COUNT of the slotted decode
        # programs (one routed chain per micro-batch); chunk/admission
        # programs are batch-1 and stay sequential at any depth
        depth = backend.overlap if rec.kind in ("decode", "block") else 1
        if to_a_n != 3 * L * trips * depth or to_w_n != L * trips * depth:
            report.error(
                PASS, rec.name, "hop count",
                f"expected 3·L·T·D={3 * L * trips * depth} W→A and "
                f"L·T·D={L * trips * depth} A→W routed hops (L={L} "
                f"layers, T={trips} micro-steps, overlap D={depth}) "
                f"but the compiled program routes {to_a_n} W→A / {to_w_n} "
                "A→W — a W↔A boundary was dropped or duplicated in "
                "core/wa.py's layer loop")
            continue
        # the meter's bytes-per-element must match the traced activations
        el = backend._el
        traced_el = {np.dtype(d).itemsize for d in to_w_dt} or {el}
        if traced_el != {el}:
            report.error(
                PASS, rec.name, "element size",
                f"meter assumes {el} B/element but the routed activations "
                f"trace as {sorted(to_w_dt)} — stats()['wa'] under/over-"
                "counts every dispatch")
            continue
        analytic = trips * routing_bytes(cfg, rows, el)
        lhs = 2 * cfg.d_model * to_w_b
        rhs = cfg.n_heads * cfg.head_dim * analytic
        if lhs != rhs:
            report.error(
                PASS, rec.name, "hop bytes",
                f"analytic meter claims {analytic} routed B/dispatch "
                f"(rows={rows}, trips={trips}) but the compiled A→W hops "
                f"move {to_w_b} B — 2·d_model·hops = {lhs} != "
                f"heads·head_dim·analytic = {rhs}; the meter in "
                "runtime/serving.py drifted from the program")
        else:
            report.info(PASS, rec.name, "hops",
                        f"{to_a_n}+{to_w_n} hops, analytic "
                        f"{analytic} B/dispatch confirmed")
