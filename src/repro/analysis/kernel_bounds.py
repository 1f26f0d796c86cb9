"""Kernel bounds checker (pass 5).

Two invariants at the bottom of the stack:

  K1  flash-decode coverage + live bounds: the Pallas grid must tile the
      FULL extent of every blocked operand (an under-covering grid
      silently drops tail KV — attention quietly forgets the newest
      positions), and the traced bounds (a ``kv_limit`` operand, or the
      per-row ``lo``/``hi`` the kernel prefetches into SMEM) must actually
      be READ by the kernel body (dead bounds mean the walk is no longer
      length-aware). Checked by evaluating each BlockSpec index map over
      every grid point and unioning the covered index ranges. K/V that
      stay in HBM are copied tile by tile along each row's work list
      (``live_tiles``): that map is evaluated over ragged ranges at the
      serving tile — every fetched tile lies in [0, S), holds a position
      of the row's range, and the tiles cover the range. No TPU needed,
      tracing is enough.

  K2  chunk-write slot isolation: the chunked-prefill lane writes each
      (1, n_kv, C, hd) chunk with ``dynamic_update_slice`` at a TRACED
      slot offset. Its update extent along the slot axis must be 1 — an
      extent > 1 with a traced start could alias a neighbouring slot's
      live KV at runtime and no runtime check would ever fire (DUS clamps,
      it does not trap). Stack-level writes (extent == slots) are safe
      only at a LITERAL 0 offset.

The serving programs on CPU dispatch the jnp reference kernel, so K1 runs
against the kernel library directly at every (bucket, shard) shape the
cell's engine would serve — same shapes, same dtypes, no hardware.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import jax
import numpy as np
from jax.extend import core as jax_core

from repro.analysis.findings import Report
from repro.analysis.jaxpr_walk import iter_eqns, literal_value
from repro.analysis.programs import Cell, ProgramRecord
from repro.kv.cache import KVCache

PASS = "kernel_bounds"

_MAX_GRID_POINTS = 65536


# ---------------------------------------------------------------------------
# K1: pallas grid coverage + kv_limit liveness
# ---------------------------------------------------------------------------

def _eval_index_map(bm, idx: Tuple[int, ...]) -> Optional[Tuple[int, ...]]:
    im = getattr(bm, "index_map_jaxpr", None)
    if im is None:
        return None
    try:
        out = jax_core.jaxpr_as_fun(im)(*[np.int32(i) for i in idx])
        return tuple(int(x) for x in out)
    except Exception:
        return None


def check_pallas_sites(jaxpr, program: str, report: Report,
                       expect_limit: bool = False) -> int:
    """Audit every pallas_call in ``jaxpr``; returns how many were seen."""
    seen = 0
    for site in iter_eqns(jaxpr):
        eqn = site.eqn
        if eqn.primitive.name != "pallas_call":
            continue
        seen += 1
        gm = eqn.params.get("grid_mapping")
        if gm is None:
            report.warning(PASS, program, "pallas_call",
                           "no grid_mapping param — cannot audit bounds")
            continue
        grid = tuple(int(g) for g in gm.grid)
        npts = int(np.prod(grid, dtype=np.int64)) if grid else 1
        if npts > _MAX_GRID_POINTS:
            report.warning(PASS, program, "pallas_call",
                           f"grid {grid} too large to enumerate "
                           f"({npts} points) — coverage unchecked")
            continue
        n_in = getattr(gm, "num_inputs", None)
        mappings = list(gm.block_mappings)
        in_avals = [v.aval for v in eqn.invars]
        if n_in is None:
            n_in = min(len(mappings), len(in_avals))
        pts = [()] if not grid else list(np.ndindex(*grid))
        for op_i in range(min(n_in, len(mappings), len(in_avals))):
            _check_coverage(program, report, op_i, in_avals[op_i],
                            mappings[op_i], pts)
        if expect_limit:
            _check_limit_live(program, report, eqn, in_avals[:n_in])
    return seen


def _check_coverage(program: str, report: Report, op_i: int, aval,
                    bm, pts: List[Tuple[int, ...]]):
    # block dims are pl.Blocked(n) or pl.Squeezed() (block_size None): a
    # squeezed dim tiles by 1
    bshape = tuple(int(getattr(b, "block_size", None) or 1)
                   for b in getattr(bm, "block_shape", ()))
    if len(bshape) != len(aval.shape) or not pts:
        return
    starts = set()
    for p in pts:
        s = _eval_index_map(bm, p)
        if s is None:
            return                      # exotic index map — skip, don't lie
        starts.add(s)
    for d, (extent, blk) in enumerate(zip(aval.shape, bshape)):
        covered = set()
        for s in starts:
            lo = s[d] * blk
            covered.update(range(lo, min(lo + blk, extent)))
        if len(covered) != extent:
            missing = sorted(set(range(extent)) - covered)
            report.error(
                PASS, program,
                f"pallas operand {op_i} ({aval.shape}:{aval.dtype}) dim {d}",
                f"grid tiles cover only {len(covered)}/{extent} positions "
                f"(first missing: {missing[:4]}) — the kernel silently "
                "drops the uncovered KV tail; grid/block_s do not tile "
                "the extent")


def _check_limit_live(program: str, report: Report, eqn, in_avals):
    """The bounds must be consumed by the kernel: its scalar-prefetch
    operands (per-row ``lo``/``hi``), else a (1,1) int32 kv_limit."""
    n_index = getattr(eqn.params.get("grid_mapping"), "num_index_operands",
                      0)
    lim_idx = list(range(n_index)) or [
        i for i, a in enumerate(in_avals)
        if tuple(a.shape) == (1, 1) and a.dtype == np.int32]
    if not lim_idx:
        report.error(
            PASS, program, "kv_limit",
            "flash-decode pallas_call has NO (1,1) int32 kv_limit "
            "operand — tile early-out is impossible and every dispatch "
            "walks the full padded extent")
        return
    kjaxpr = eqn.params.get("jaxpr")
    if kjaxpr is None:
        return
    kj = kjaxpr.jaxpr if isinstance(kjaxpr, jax_core.ClosedJaxpr) else kjaxpr
    for i in lim_idx:
        if i >= len(kj.invars):
            continue
        ref = kj.invars[i]
        used = any(ref in site.eqn.invars for site in iter_eqns(kj))
        if not used:
            report.error(
                PASS, program, f"kv_limit (operand {i})",
                "kv_limit ref is never read inside the kernel body — the "
                "early-out is dead code and padded tiles all execute")


# ---------------------------------------------------------------------------
# K1 driver: trace the kernel library at the cell's serving shapes
# ---------------------------------------------------------------------------

def _flash_shapes(cell: Cell) -> List[Tuple[str, int]]:
    """(label, kv extent) pairs the cell's engine would hand the kernel:
    each KV bucket, and each per-shard extent under split-KV."""
    backend = cell.backend
    caches = cell.caches_aval
    if not isinstance(caches, KVCache):
        return []
    S_full = caches.k.shape[3]
    out = []
    buckets = [b for b in (backend.buckets or ()) if b > 0] or [S_full]
    for b in buckets:
        sh = cell.spec.a_shards
        if sh > 1:
            out.append((f"bucket {b} / {sh} shards", b // sh))
        else:
            out.append((f"bucket {b}", b))
    return out


def check_live_tile_map(program: str, S: int, block_s: int, windows,
                        report: Report, tiles=None) -> int:
    """Evaluate the kernel's per-row tile map (``live_tiles``, or
    ``tiles``) over ragged ranges ``[lo, hi)`` of an ``S``-position extent
    in tiles of ``block_s``: the empty range, one position, ends on and
    beside tile boundaries, the whole extent, and each window's lower
    bound. Every fetched tile must lie in [0, S // block_s) and hold a
    position of the range, and the tiles must cover it. Returns the ranges
    checked."""
    if tiles is None:
        from repro.kernels.flash_decode.flash_decode import live_tiles
        tiles = live_tiles
    n_tiles = S // block_s
    ends = sorted({0, 1, 2, block_s - 1, block_s, block_s + 1, S // 2,
                   S - block_s, S - block_s + 1, S - 1, S} & set(
                       range(S + 1)))
    ranges = [(0, hi) for hi in ends]
    for w in windows:
        if w:
            ranges += [(max(0, hi - w), hi) for hi in ends]
    ranges.append((S // 2, S // 4))                  # hi <= lo: nothing
    for lo, hi in ranges:
        first, count = (int(x) for x in tiles(np.int64(lo), np.int64(hi),
                                              block_s))
        visited = range(first, first + count)
        bad = [t for t in visited if not 0 <= t < n_tiles
               or t * block_s >= hi or (t + 1) * block_s <= lo]
        covered = {p for t in visited for p in range(t * block_s,
                                                     (t + 1) * block_s)}
        if bad or not covered >= set(range(lo, hi)):
            report.error(
                PASS, program, f"live tiles [{lo}, {hi}) block_s={block_s}",
                f"the row's work list visits tiles {list(visited)[:6]} of "
                f"{n_tiles}: "
                + (f"tile {bad[0]} lies outside [0, {n_tiles}) or outside "
                   "the range" if bad else "positions of the range are "
                   "never fetched"))
            break
    return len(ranges)


def check_kernel_library(cell: Cell, report: Report):
    from repro.kernels.flash_decode.flash_decode import flash_decode_pallas
    from repro.models.transformer import live_range_tile
    caches = cell.caches_aval
    if not isinstance(caches, KVCache):
        report.info(PASS, "<kernel>", cell.spec.label,
                    "attention-free family: no flash-decode kernel")
        return
    tile = live_range_tile(caches)
    if tile:
        check_live_tile_map(f"flash_decode[extent {caches.k.shape[3]}]",
                            caches.k.shape[3], tile,
                            getattr(cell.cfg, "layer_windows", ()), report)
    _L, B, n_kv, _S, hd = caches.k.shape
    Hq = cell.cfg.n_heads
    quant = caches.k_scale is not None
    kv_dtype = caches.k.dtype
    if caches.is_tiered:
        # the tiered read dequantizes the cold prefix and merges it with
        # the hot ring BEFORE attention — the kernel sees the compute-dtype
        # image at the full head_dim (int4's packed hd/2 and the cold
        # scales never reach it)
        hd = caches.hot_k.shape[4]
        kv_dtype = caches.hot_k.dtype
        quant = False
    for label, S in _flash_shapes(cell):
        for bs in {S, max(S // 2, 1)}:
            if S % bs:
                continue

            def trace(q, k, v, ks, vs, mask, lim, _bs=bs):
                return flash_decode_pallas(q, k, v, ks, vs, mask,
                                           block_s=_bs, kv_limit=lim)

            q = jax.ShapeDtypeStruct((B, Hq, hd), np.float32)
            kv = jax.ShapeDtypeStruct((B, n_kv, S, hd), kv_dtype)
            sc = jax.ShapeDtypeStruct((B, n_kv, S, 1), np.float32)\
                if quant else None
            mask = jax.ShapeDtypeStruct((B, S), np.bool_)
            lim = jax.ShapeDtypeStruct((1, 1), np.int32)
            try:
                jaxpr = jax.make_jaxpr(trace)(q, kv, kv, sc, sc, mask, lim)
            except Exception as e:
                report.error(PASS, f"flash_decode[{label}]", f"block_s={bs}",
                             "kernel fails to trace at serving shape "
                             f"(B={B}, n_kv={n_kv}, S={S}, hd={hd}): {e}")
                continue
            n = check_pallas_sites(jaxpr, f"flash_decode[{label}]", report,
                                   expect_limit=True)
            if n == 0:
                report.error(PASS, f"flash_decode[{label}]", "pallas_call",
                             "no pallas_call traced — the kernel path "
                             "silently fell back")


# ---------------------------------------------------------------------------
# K2: chunk-write slot isolation
# ---------------------------------------------------------------------------

def check_chunk_writes(cell: Cell, rec: ProgramRecord, report: Report):
    caches = cell.caches_aval
    if not isinstance(caches, KVCache):
        return
    # tiered colocated monolithic admission compiles a chunk BODY under the
    # "serve_admit" name (kind "admit") — its traced-offset DUS writes get
    # the same slot-isolation audit as the chunked lane
    tiered_admit = rec.kind == "admit" and caches.is_tiered
    if rec.kind != "chunk" and not tiered_admit:
        return
    try:
        jaxpr = rec.step.jaxpr()
    except (ValueError, TypeError) as e:
        report.warning(PASS, rec.name, "jaxpr",
                       f"could not retrace for chunk-write audit: {e}")
        return
    stacks = {leaf.shape for leaf in jax.tree_util.tree_leaves(caches)
              if getattr(leaf, "ndim", 0) == 5}          # (L, B, n_kv, S, *)

    def slot_dim(shape):
        """Slot axis of a per-layer slice (B, n_kv, S, *) or a stack
        (L, B, n_kv, S, *) — whole, or one batch shard's inside a
        shard_map — else None."""
        for full in stacks:
            if len(shape) == 5 and shape[0] == full[0] \
                    and shape[2:] == full[2:] and full[1] % shape[1] == 0:
                return 1
            if len(shape) == 4 and shape[1:] == full[2:] \
                    and full[1] % shape[0] == 0:
                return 0
        return None

    B = cell.spec.slots
    n_checked = 0
    for site in iter_eqns(jaxpr):
        eqn = site.eqn
        if eqn.primitive.name != "dynamic_update_slice":
            continue
        dst, upd, *starts = eqn.invars
        dshape = tuple(dst.aval.shape)
        dim = slot_dim(dshape)
        if dim is None:
            continue
        n_checked += 1
        extent = upd.aval.shape[dim]
        start = literal_value(starts[dim])
        if extent == 1:
            continue
        if extent == dshape[dim] and start == 0:
            continue                                     # full-width literal
        report.error(
            PASS, rec.name,
            f"dynamic_update_slice dst {dshape} slot dim {dim}",
            f"chunk write updates {extent} slots at "
            f"{'a TRACED offset' if start is None else f'offset {start}'} "
            "— a masked chunk/shard write may alias a neighbouring "
            f"slot's live KV (slot-extent must be 1, got {extent} of "
            f"{B} slots)")
    if n_checked == 0:
        report.warning(PASS, rec.name, "dynamic_update_slice",
                       "no cache-shaped DUS writes found in the chunk "
                       "program — the slot-isolation audit matched nothing "
                       "(cache write idiom changed?)")


def check_kernel_bounds(cell: Cell, report: Report):
    # serving programs (CPU programs carry no pallas_call; audit anyway —
    # on TPU builds the same pass sees the real kernels in-program)
    for rec in cell.records:
        try:
            jaxpr = rec.step.jaxpr()
        except (ValueError, TypeError):
            continue
        check_pallas_sites(jaxpr, rec.name, report)
        check_chunk_writes(cell, rec, report)
    check_kernel_library(cell, report)
