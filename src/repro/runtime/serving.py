"""Continuous-batching decode serving engine under STATIC shapes.

The paper's prototype serves a fixed decode batch and defers continuous
batching to future work (§7.2). This engine closes that gap without leaving
the cache-resident/static-shape regime the paper's runtime depends on:

- the decode batch is a fixed set of SLOTS (static shapes → AOT compile once),
- a queued request is admitted into any free slot *mid-serve* — no drain, no
  retrace,
- every row carries its own cursor (``positions``) and an ``active`` mask is
  threaded through decode (``ModelAPI.decode_slotted``) so retired slots
  neither write KV nor pollute the argmax,
- **macro-step decode** (``block_size`` = T > 1): decode runs as
  ``ModelAPI.decode_block`` — T greedy micro-steps inside ONE AOT-compiled
  ``lax.scan``, with per-slot on-device halting. The host syncs ONCE per T
  tokens and admission waits for block boundaries — the step-axis analogue
  of the paper's sub-operator dependency relaxation (§5),
- **chunked-prefill lane** (``prefill_chunk`` = C > 0): admission prefill is
  no longer one monolithic full-width program that stalls the whole decode
  batch. Each block boundary runs AT MOST ONE fixed-(1,C) chunk
  (``ModelAPI.prefill_chunk``) for the admitting slot, writing KV at the
  slot's offset, then the decode block for live slots — in-flight TPOT pays
  one chunk per boundary instead of a full-prompt stall. Prompt lengths are
  TRUE lengths end to end: the cursor starts at the real length (short
  prompts land in small KV buckets from step 0) and arbitrary lengths are
  covered by the chunk loop — nothing is ever silently truncated,
- **length-aware KV walking**: in block mode each macro-step runs the block
  program compiled for the smallest KV *bucket* (chunk multiple) covering
  every live cursor + T (``kv_bucket_chunk``),
- all step programs are AOT-compiled through ``StaticRuntime`` — ``stats()``
  must show compiles == 1 per program with only ``calls`` growing across
  admissions (the §4.3 pinned-pool invariant).

The engine is split into a host-side **SlotScheduler** (slot occupancy,
arrival pump, cursors/halt operands, chunk-lane bookkeeping — decisions
only) and a device-side **ExecutorBackend** (the compiled step programs and
the slot caches — execution only); ``ServingEngine`` is the boundary loop
that connects them. The backend is PLUGGABLE (``backend=``): the colocated
backend runs the single-domain programs, the WA backend
(``backend="wa"``) runs the same feature set — macro-step blocks, KV
buckets, chunked prefill, slot admission — through the weight–attention
disaggregated layer loop of ``core/wa.py`` with the W→A→W routing inside
the compiled programs (sharding-constrained, ``device_put``-free). The
scheduler is backend-agnostic: no scheduling decision moves. The previous
drain-then-refill loop is kept as ``mode="drain"`` — the baseline the
continuous scheduler is measured against, and the fallback for model
families without slotted support.

Per-request accounting: queue delay (enqueue→admit), lane wait (admit→first
prefill dispatch), prefill (first prefill dispatch→first token), TTFT (their
sum, spanning chunk boundaries under chunked admission), TPOT, and max
inter-token gap (the decode-stall a prefill inflicts on in-flight requests).
Engine-level: every time comes from one span table per run
(``runtime/spans.py``: a ``serve:<phase>`` span per boundary phase, counters
at each decode dispatch) — decode-token throughput over decode wall-time
only (prefill AND chunk-prefill wall-time are excluded from both sides),
host time per decode boundary, KV positions in use against reserved, host
syncs per decode token, and per-macro-step token counts.

**Serving under pressure** (DESIGN.md §7, failure model): requests carry a
``priority`` lane and TTFT/TPOT deadline fields; admission drains the queue
in priority order, a bounded queue (``max_queue``) sheds lowest-priority
work as STRUCTURED rejections, and expired-TTFT queued requests are shed as
deadline misses. With ``preemptible=True`` the engine may, at a block
boundary (the only preemption point), swap a victim slot's true-length KV
out to a host-side buffer (``serve_[wa_]swap_out`` — stored bytes verbatim,
int8 scales included) and later restore it via the masked full-width write
(``serve_[wa_]swap_in``); cursors already carry true lengths, so a restored
sequence is byte-identical to an uninterrupted one and the swap pair joins
the compile-once program set. Every program dispatch runs through a
hardened wrapper: bounded retry-with-backoff on ``DispatchError`` (raised
BEFORE the compiled call touches donated operands — retry-safe), a watchdog
counter for dispatches exceeding ``watchdog_s``, and a poisoned-slot
quarantine path that demotes a persistently failing request to a structured
rejection instead of a hung engine. Every request ends terminally accounted:
completed, rejected, or deadline_missed.
"""
from __future__ import annotations

import time
import warnings
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.pipeline import wa_schedule_occupancy
from repro.core.wa import WADisaggregated, micro_batch_slices, routing_bytes
from repro.kv.cache import (KVCache, cold_boundary, export_slot_kv,
                            import_slot_kv)
from repro.models.attention import bucket_for, kv_buckets
from repro.models.common import dtype_of
from repro.models.param_specs import cache_specs, param_specs
from repro.models.registry import DECODE_SLACK, ModelAPI
from repro.models.sharding import ShardingCtx
from repro.runtime.spans import SpanTable
from repro.runtime.static_runtime import DispatchError, StaticRuntime


class RequestRejected(ValueError):
    """Enqueue-time rejection of an unrepresentable request. Carries the
    request id, the offending length and the per-mode limit as FIELDS (not
    just prose) so a fleet log line is actionable: which request, which
    length, which knob to raise."""

    def __init__(self, rid: int, reason: str, *, length=None, limit=None,
                 limit_name: str = ""):
        self.rid, self.reason = rid, reason
        self.length, self.limit, self.limit_name = length, limit, limit_name
        super().__init__(f"request {rid}: {reason}")


class DispatchFailure(RuntimeError):
    """A program dispatch kept raising ``DispatchError`` past the bounded
    retry budget. The boundary loop demotes this to a structured rejection
    of the responsible request (+ slot quarantine where the slot's cache
    bytes are suspect) — never a hung engine."""

    def __init__(self, name: str, attempts: int, cause: Exception):
        self.name, self.attempts, self.cause = name, attempts, cause
        super().__init__(f"dispatch of {name!r} failed after {attempts} "
                         f"attempt(s): {cause}")


@dataclass
class SwapState:
    """Host-side image of a preempted slot: the full-extent STORED bytes
    (``export_slot_kv`` tuple — int8 values + scales verbatim, dense K/V
    verbatim) plus the cursor triple that makes restore token-exact. The
    true KV length travels here, not in the buffer — exactly the chunk
    lane's cursors-are-validity contract."""
    saved: Tuple                     # (k, v, k_scale, v_scale) host arrays
    kv_len: int                      # TRUE length: positions cursor at swap
    last_tok: int                    # last emitted token (its KV not yet written)
    remaining: int                   # decode budget left


def _pin_cache_tree(caches, ctx: ShardingCtx):
    """Constrain every cache leaf to its planned layout (``cache_specs``).

    Cache-only programs (slot write, slot reset) contain no matmuls and no
    annotations of their own, so GSPMD sees nothing to anchor on and pins
    the whole program — including the DONATED cache buffer — to a single
    device, forcing a full-cache reshard every time dispatch alternates
    with the model-step programs. Pinning entry and exit keeps every
    program in a cell on one agreed cache placement."""
    if ctx.mesh is None or ctx.mesh.empty:
        return caches
    return jax.tree.map(
        lambda x, s: jax.lax.with_sharding_constraint(
            x, jax.sharding.NamedSharding(ctx.mesh, s)),
        caches, cache_specs(caches, ctx))


@dataclass
class Request:
    rid: int
    prompt: np.ndarray                  # (L,) int32 — TRUE length, no padding
    max_new_tokens: int
    arrival_step: int = 0               # decode step at which it reaches the queue
    eos_id: int = -1                    # stop id (< 0 → budget-only halting)
    generated: List[int] = field(default_factory=list)
    t_enqueue: float = 0.0
    t_admitted: float = 0.0
    t_first_chunk: float = 0.0          # start of its first prefill dispatch
    t_first_token: float = 0.0
    t_done: float = 0.0
    admit_step: int = -1                # decode step at which it got a slot
    t_last_emit: float = 0.0            # last token-emission sync (gap stats)
    max_gap: float = 0.0                # max inter-token gap (decode stall)
    priority: int = 0                   # higher wins admission AND survives
                                        # preemption/shedding longer
    ttft_deadline_ms: float = 0.0       # 0 → none; queued past it → shed
    tpot_deadline_ms: float = 0.0       # SLO target (recorded, never sheds)
    status: str = "pending"             # pending/queued/active → terminal:
                                        # completed|rejected|deadline_missed
    reject_reason: Optional[str] = None
    preemptions: int = 0                # times swapped out of a slot
    swap: Optional[SwapState] = None    # host KV image while preempted
    kv_base: int = 0                    # cursor base at start_decode (true
                                        # prompt length; padded width when
                                        # admitted monolithically)

    @property
    def done(self) -> bool:
        if self.eos_id >= 0 and self.generated\
                and self.generated[-1] == self.eos_id:
            return True
        return len(self.generated) >= self.max_new_tokens

    def note_emit(self, now: float):
        """Token(s) for this request became host-visible at ``now``; the max
        gap between consecutive emissions is the decode-stall metric (a
        monolithic prefill of another request shows up here)."""
        if self.t_last_emit > 0.0:
            self.max_gap = max(self.max_gap, now - self.t_last_emit)
        self.t_last_emit = now

    def metrics(self) -> Dict[str, Any]:
        """Per-request latencies in ms. TTFT splits into queue delay
        (enqueue→admission), lane wait (admission→first prefill dispatch,
        the wait for the chunk lane) and prefill (first prefill
        dispatch→first token): the three sum to ``ttft_ms``."""
        n = len(self.generated)
        ttft = max(0.0, self.t_first_token - self.t_enqueue) * 1e3
        tpot = ((self.t_done - self.t_first_token) / (n - 1) * 1e3
                if n > 1 else 0.0)
        return {
            "rid": self.rid,
            "tokens": n,
            "prompt_tokens": int(len(self.prompt)),
            "arrival_step": self.arrival_step,
            "admit_step": self.admit_step,
            "queue_delay_ms": max(0.0, self.t_admitted - self.t_enqueue) * 1e3,
            "lane_wait_ms": max(0.0, self.t_first_chunk - self.t_admitted)
            * 1e3,
            "prefill_ms": max(0.0, self.t_first_token - self.t_first_chunk)
            * 1e3,
            "ttft_ms": ttft,
            "tpot_ms": tpot,
            "max_gap_ms": self.max_gap * 1e3,
            "priority": self.priority,
            "status": self.status,
            "preemptions": self.preemptions,
            # deadline attainment (completed requests; goodput-under-
            # deadline in the pressure benchmark sums these)
            "ttft_deadline_met": bool(self.ttft_deadline_ms <= 0
                                      or ttft <= self.ttft_deadline_ms),
            "tpot_deadline_met": bool(self.tpot_deadline_ms <= 0
                                      or tpot <= self.tpot_deadline_ms),
        }


def pad_row(prompt: np.ndarray, width: int) -> np.ndarray:
    """Zero-pad a prompt (or prompt slice) up to a static width. PAD ONLY:
    callers must have rejected anything longer (the silent-truncation fix
    deleted every truncating path)."""
    assert len(prompt) <= width, (len(prompt), width)
    row = np.zeros((width,), np.int32)
    row[:len(prompt)] = prompt
    return row


# ---------------------------------------------------------------------------
# SlotScheduler — the HOST half of the scheduler/executor split
# ---------------------------------------------------------------------------

class SlotScheduler:
    """Slot occupancy, arrival pump, per-slot cursors/halt operands and the
    chunked-prefill lane bookkeeping. Pure host state: it decides WHAT runs
    at each block boundary and never touches a device array — the
    ExecutorBackend owns every compiled call, and because no decision
    lives there, every backend serves through this ONE scheduler
    (DESIGN.md §7)."""

    FREE, PREFILL, DECODE = "free", "prefill", "decode"

    def __init__(self, n_slots: int, requests: List[Request],
                 queue: List[Request]):
        self.n = n_slots
        self.pending = sorted(requests, key=lambda r: r.arrival_step)
        self.queue = queue                       # engine-owned (submit target)
        self.req: List[Optional[Request]] = [None] * n_slots
        self.phase = [self.FREE] * n_slots
        self.filled = [0] * n_slots              # prompt tokens written so far
        self.prefill_fifo: List[int] = []        # slots awaiting chunk work
        self.positions = np.zeros((n_slots,), np.int32)
        self.last_tok = np.zeros((n_slots,), np.int32)
        self.remaining = np.zeros((n_slots,), np.int32)
        self.eos = np.full((n_slots,), -1, np.int32)
        self.quarantined: set = set()            # poisoned slots, never reused

    # -- queue / occupancy ------------------------------------------------
    def work_remaining(self) -> bool:
        return bool(self.pending or self.queue
                    or any(p != self.FREE for p in self.phase))

    def pump(self, step: int):
        """Arrival simulation: requests whose arrival_step has come move to
        the queue (already validated by run()). Stamped here UNLESS the
        request was submit()ted before run() — its enqueue time is the
        submit, and queue_delay/TTFT must keep counting from there."""
        while self.pending and self.pending[0].arrival_step <= step:
            r = self.pending.pop(0)
            if not r.t_enqueue:
                r.t_enqueue = time.monotonic()
            r.status = "queued"
            self.queue.append(r)

    def occupied(self) -> bool:
        return any(p != self.FREE for p in self.phase)

    def decode_active(self) -> np.ndarray:
        return np.array([p == self.DECODE for p in self.phase])

    def micro_batch_view(self, depth: int, active=None):
        """Per-micro-batch (slot indices, active-mask rows) under overlap
        depth ``depth`` — routed through ``core.wa.micro_batch_slices``,
        the SAME helper the pipelined layer loop slices its rows with, so
        the scheduler's occupancy view and the backend's micro-batch split
        share one source of truth and cannot drift."""
        act = self.decode_active() if active is None else np.asarray(active)
        return [(list(range(sl.start, sl.stop)), act[sl])
                for sl in micro_batch_slices(self.n, depth)]

    # -- priority queue / quarantine --------------------------------------
    def usable_free(self) -> Optional[int]:
        """Lowest-index FREE slot that is not quarantined, or None."""
        for i in range(self.n):
            if self.phase[i] == self.FREE and i not in self.quarantined:
                return i
        return None

    def usable_capacity(self) -> int:
        return self.n - len(self.quarantined)

    def pop_queue(self) -> Optional[Request]:
        """Highest-priority queued request; FIFO within a priority class.
        A preempted request keeps its ORIGINAL enqueue stamp, so it
        re-admits ahead of later same-priority arrivals (its wait already
        counted once)."""
        if not self.queue:
            return None
        j = min(range(len(self.queue)),
                key=lambda j: (-self.queue[j].priority,
                               self.queue[j].t_enqueue, self.queue[j].rid))
        return self.queue.pop(j)

    def top_priority(self) -> Optional[int]:
        return max((r.priority for r in self.queue), default=None)

    def decode_slots(self) -> List[int]:
        return [i for i in range(self.n) if self.phase[i] == self.DECODE]

    # -- chunk lane -------------------------------------------------------
    def begin_prefill(self, slot: int, r: Request, step: int):
        """Admit a fresh request into a free slot (PREFILL phase); its
        chunks run one per boundary from the admission FIFO."""
        r.t_admitted = time.monotonic()
        r.t_first_chunk = 0.0
        r.admit_step = step
        r.status = "active"
        self.req[slot] = r
        self.phase[slot] = self.PREFILL
        self.filled[slot] = 0
        self.prefill_fifo.append(slot)

    def next_chunk(self, chunk: int, kv_extent: Optional[int]
                   ) -> Optional[Tuple[int, Request, int, int]]:
        """Head of the prefill FIFO → (slot, request, start, n_valid) for
        the next fixed-shape chunk, or None when no slot is prefilling.

        The fixed (1,C) window must FIT the cache: ``dynamic_update_slice``
        clamps an out-of-bounds start instead of erroring, which would land
        the final chunk's K/V at the wrong positions. When
        ``start + C > kv_extent`` the window shifts LEFT over
        already-written positions — recomputing a prefix position's K/V is
        bit-identical (same tokens, same attended prefix), so the overlap
        is a no-op and the window still ends at the prompt's true length."""
        if not self.prefill_fifo:
            return None
        i = self.prefill_fifo[0]
        r = self.req[i]
        start = self.filled[i]
        if kv_extent is not None and start + chunk > kv_extent:
            start = kv_extent - chunk
        return i, r, start, min(chunk, len(r.prompt) - start)

    def chunk_done(self, slot: int, start: int, n_valid: int) -> bool:
        """Advance the slot's prompt cursor; True when the prompt is fully
        written (the chunk that just ran was the final one)."""
        self.filled[slot] = start + n_valid
        if self.filled[slot] >= len(self.req[slot].prompt):
            self.prefill_fifo.pop(0)
            return True
        return False

    # -- phase transitions ------------------------------------------------
    def start_decode(self, slot: int, cursor: int, first_tok: int):
        r = self.req[slot]
        r.kv_base = cursor
        self.phase[slot] = self.DECODE
        self.positions[slot] = cursor
        self.last_tok[slot] = first_tok
        self.remaining[slot] = r.max_new_tokens - 1
        self.eos[slot] = r.eos_id

    def preempt(self, slot: int) -> Request:
        """Release a DECODE slot whose KV the caller has already swapped
        out; the request goes back to the queue carrying its SwapState."""
        assert self.phase[slot] == self.DECODE, (slot, self.phase[slot])
        r = self.req[slot]
        self.req[slot] = None
        self.phase[slot] = self.FREE
        r.status = "queued"
        self.queue.append(r)
        return r

    def resume_decode(self, slot: int, r: Request, state: SwapState):
        """Re-enter DECODE directly from a restored swap image: cursors
        resume exactly where the preemption cut them — the prefill phase is
        skipped, the next decode step appends ``last_tok``'s KV at
        ``kv_len`` just as an uninterrupted serve would have."""
        r.status = "active"
        self.req[slot] = r
        self.phase[slot] = self.DECODE
        self.positions[slot] = state.kv_len
        self.last_tok[slot] = state.last_tok
        self.remaining[slot] = state.remaining
        self.eos[slot] = r.eos_id

    def retire(self, slot: int):
        self.req[slot] = None
        self.phase[slot] = self.FREE
        if slot in self.prefill_fifo:
            self.prefill_fifo.remove(slot)

    # -- invariants --------------------------------------------------------
    def invariant_violations(self) -> List[str]:
        """Occupancy/cursor consistency at a block boundary (the chaos
        harness runs this every boundary via ``strict_invariants``):
        FREE ⟺ no request, quarantined ⇒ FREE, no rid in two slots, the
        prefill FIFO holds exactly PREFILL slots, and every DECODE slot's
        cursor triple matches its request's emission count."""
        bad: List[str] = []
        seen: Dict[int, int] = {}
        for i in range(self.n):
            r, ph = self.req[i], self.phase[i]
            if ph == self.FREE and r is not None:
                bad.append(f"slot {i}: FREE but holds rid {r.rid}")
            if ph != self.FREE and r is None:
                bad.append(f"slot {i}: {ph} with no request")
            if ph != self.FREE and i in self.quarantined:
                bad.append(f"slot {i}: quarantined but {ph}")
            if r is not None:
                if r.rid in seen:
                    bad.append(f"rid {r.rid} in slots {seen[r.rid]} and {i}")
                seen[r.rid] = i
            if ph == self.DECODE:
                want_pos = r.kv_base + len(r.generated) - 1
                if int(self.positions[i]) != want_pos:
                    bad.append(
                        f"slot {i} rid {r.rid}: cursor {self.positions[i]} "
                        f"!= kv_base {r.kv_base} + emitted "
                        f"{len(r.generated)} - 1")
                if int(self.remaining[i]) != r.max_new_tokens\
                        - len(r.generated):
                    bad.append(
                        f"slot {i} rid {r.rid}: remaining "
                        f"{self.remaining[i]} != budget "
                        f"{r.max_new_tokens} - emitted {len(r.generated)}")
                if int(self.remaining[i]) < 0:
                    bad.append(f"slot {i} rid {r.rid}: negative remaining")
        if len(set(self.prefill_fifo)) != len(self.prefill_fifo):
            bad.append(f"duplicate slots in prefill FIFO {self.prefill_fifo}")
        for i in self.prefill_fifo:
            if self.phase[i] != self.PREFILL:
                bad.append(f"slot {i} in prefill FIFO but {self.phase[i]}")
        return bad


# ---------------------------------------------------------------------------
# ExecutorBackend — the DEVICE half of the scheduler/executor split
# ---------------------------------------------------------------------------

class ExecutorBackend:
    """Owns the slot caches and every AOT-compiled step program (compiled
    once through ``StaticRuntime`` — the §4.3 zero-retracing invariant).
    ``ServingEngine(backend=...)`` picks the implementation; the
    ``SlotScheduler`` is backend-agnostic and the boundary loop only ever
    calls this contract:

      fresh()                       fresh slot caches for a run (programs
                                    persist — compiles == 1 across runs)
      admit_full(params,row,slot)   monolithic admission → first-token array
      run_chunk(params,row,slot,start,valid)   one fixed-(1,C) prefill chunk
      decode_step(params,tok,pos,act)          one slotted step (T == 1)
      decode_block(params,bucket,…)  one T-micro-step block (per-bucket
                                     program; ``buckets`` fixed at build)
      reset(slot) / has_reset        debug slot zeroing
      drain_prefill / drain_decode   drain-mode batch programs (colocated
                                     backend only)

    Each backend × mode compiles exactly the programs it dispatches:

      colocated  chunked admission     serve_prefill_chunk
      colocated  monolithic admission  serve_prefill1 + serve_admit
      colocated  T == 1                serve_decode
      colocated  T > 1                 serve_decode_block[_s{N}] per bucket
      colocated  drain                 serve_prefill_batch + serve_decode_drain
      wa         chunked admission     serve_wa_prefill_chunk
      wa         monolithic admission  serve_wa_admit (full-width chunk)
      wa         T == 1                serve_wa_decode
      wa         T > 1                 serve_wa_decode_block[_s{N}] per bucket
      either     debug_reset_slots     serve_reset
      either     preemptible           serve_[wa_]swap_out + serve_[wa_]swap_in

    The scheduler never sees a jax array; the executor never makes a
    scheduling decision."""

    name = "colocated"
    program_prefix = "serve_"

    def __init__(self, api: ModelAPI, ctx: ShardingCtx, rt: StaticRuntime,
                 params, caches_aval, *, mode: str, slots: int,
                 prompt_len: int, max_new_cap: int, block_size: int,
                 kv_bucket_chunk: int, prefill_chunk: int,
                 debug_reset_slots: bool, a_shards: int = 1,
                 overlap: int = 1, preemptible: bool = False):
        self.api, self.ctx, self.rt = api, ctx, rt
        self.slots, self.prompt_len = slots, prompt_len
        self.max_new_cap = max_new_cap
        self.block_size = block_size
        self.prefill_chunk = prefill_chunk
        self.a_shards = a_shards
        # sub-operator overlap depth (micro-batch software pipelining of
        # the W/A boundary — WA backend only; the engine validated it)
        self.overlap = overlap
        self.preemptible = preemptible
        self.caches = None
        self.buckets: Tuple[int, ...] = ()
        self._decode_blocks: Dict[int, Callable] = {}
        self._reset = None
        self._swap_out_p = self._swap_in_p = None
        if mode == "continuous":
            self._build_continuous(params, caches_aval, kv_bucket_chunk,
                                   prefill_chunk, debug_reset_slots)
            if preemptible:
                self._build_swap(caches_aval)
        else:
            self._build_drain(params)

    # -- shared build pieces ----------------------------------------------
    def _bucket_set(self, caches_aval, kv_bucket_chunk) -> Tuple[int, ...]:
        """Static KV bucket set for the block programs. Bucketing applies
        only to prefix-ordered KV caches; recurrent states (and ring
        buffers) get the single full program."""
        bucketable = isinstance(caches_aval, KVCache)\
            and not caches_aval.window
        s_max = caches_aval.k.shape[3] if bucketable else 0
        # a_shards > 1 → every bucket must split into equal shard blocks
        # (kv_buckets rounds the chunk up; the engine validated s_max)
        return kv_buckets(s_max, kv_bucket_chunk, self.a_shards)\
            if bucketable and kv_bucket_chunk > 0 else (0,)

    @property
    def cache_ctx(self) -> ShardingCtx:
        """Sharding ctx that owns the slot caches (A domain under WA)."""
        return self.ctx

    def _build_reset(self, caches_aval, debug_reset_slots):
        if debug_reset_slots and self.api.reset_slot is not None:
            scalar = jnp.zeros((), jnp.int32)
            cctx = self.cache_ctx
            self._reset = self.rt.compile_step(
                "serve_reset",
                lambda c, slot: _pin_cache_tree(
                    self.api.reset_slot(_pin_cache_tree(c, cctx), slot),
                    cctx),
                (caches_aval, scalar), donate_argnums=(0,))

    # -- preemption swap pair ---------------------------------------------
    def _swap_export_fn(self, caches, slot):
        """Traced body of ``{prefix}swap_out`` (backends may override to
        route through their own cache-domain pins)."""
        return export_slot_kv(_pin_cache_tree(caches, self.cache_ctx), slot)

    def _swap_import_fn(self, caches, saved, slot, valid_len):
        """Traced body of ``{prefix}swap_in`` — masked true-length restore
        (the chunk lane's keep-past-valid write at full width)."""
        cctx = self.cache_ctx
        caches = import_slot_kv(_pin_cache_tree(caches, cctx), saved, slot,
                                valid_len)
        return _pin_cache_tree(caches, cctx)

    def _build_swap(self, caches_aval):
        """Compile the token-exact preemption pair (engine validated the
        family: prefix-ordered non-windowed KV cache). ``swap_out`` is
        READ-ONLY — no donation, it returns only the slot slices, so a
        failed/retried dispatch can never corrupt the resident cache;
        ``swap_in`` donates the caches like every steady-state program.
        Slot index and true length are traced scalars — one compiled pair
        serves every slot at every length (compiles == 1)."""
        scalar = jnp.zeros((), jnp.int32)
        saved_aval = jax.eval_shape(self._swap_export_fn, caches_aval,
                                    scalar)
        self._swap_out_p = self.rt.compile_step(
            f"{self.program_prefix}swap_out", self._swap_export_fn,
            (caches_aval, scalar))
        self._swap_in_p = self.rt.compile_step(
            f"{self.program_prefix}swap_in", self._swap_import_fn,
            (caches_aval, saved_aval, scalar, scalar), donate_argnums=(0,))

    @staticmethod
    def _postprocess(logits, positions, active):
        # active-slot mask: retired slots emit a fixed token id 0 and
        # never advance — finished requests cannot pollute the stream
        nxt = jnp.argmax(logits[:, 0], -1).astype(jnp.int32)
        return jnp.where(active, nxt, 0),\
            positions + active.astype(jnp.int32)

    def _build_decode_programs(self, params, caches_aval, kv_bucket_chunk,
                               prefix, slotted_fn, block_fn):
        """Compile the decode half shared by every backend: one
        ``{prefix}decode_block[_s{N}]`` per KV bucket for T > 1, else the
        single ``{prefix}decode`` step program. Backends differ only in the
        step callables and the program-name prefix — the halting operands,
        donation and postprocess wiring cannot diverge between them.

        slotted_fn(params, caches, tokens, positions, active)
            → (caches, logits)
        block_fn(params, caches, tok, pos, act, rem, eos, kv_bucket)
            → the ``make_decode_block`` 7-tuple
        """
        B, T = self.slots, self.block_size
        pos0 = jnp.zeros((B,), jnp.int32)
        act0 = jnp.zeros((B,), bool)
        tok0 = jnp.zeros((B,), jnp.int32)
        # overlap depth is a build-time static baked into the SAME program
        # names (depth 1 compiles today's exact program set); record it as
        # program metadata so stats()/logs can say which variant serves
        meta = {"overlap": self.overlap} if self.overlap > 1 else None
        if T > 1:
            # -- macro-step block programs, one per KV bucket --------------
            self.buckets = self._bucket_set(caches_aval, kv_bucket_chunk)
            rem0 = jnp.zeros((B,), jnp.int32)
            eos0 = jnp.full((B,), -1, jnp.int32)
            for sb in self.buckets:
                name = f"{prefix}decode_block" if len(self.buckets) == 1\
                    else f"{prefix}decode_block_s{sb}"

                def block_step(p, caches, tok, pos, act, rem, eos, _sb=sb):
                    return block_fn(p, caches, tok, pos, act, rem, eos, _sb)

                self._decode_blocks[sb] = self.rt.compile_step(
                    name, block_step,
                    (params, caches_aval, tok0, pos0, act0, rem0, eos0),
                    donate_argnums=(1,), meta=meta)
            return

        def decode_fn(p, caches, tokens, positions, active):
            caches, logits = slotted_fn(p, caches, tokens, positions, active)
            return (caches,) + self._postprocess(logits, positions, active)

        self._decode = self.rt.compile_step(
            f"{prefix}decode", decode_fn,
            (params, caches_aval, tok0, pos0, act0),
            donate_argnums=(1,), meta=meta)

    def _build_continuous(self, params, caches_aval, kv_bucket_chunk,
                          prefill_chunk, debug_reset_slots):
        raise NotImplementedError

    def _build_drain(self, params):
        raise NotImplementedError(
            f"the {self.name} backend has no drain mode")

    # -- execution --------------------------------------------------------
    @property
    def has_reset(self) -> bool:
        return self._reset is not None

    def fresh(self):
        """Fresh slot caches for a new run (AOT programs persist). The last
        run's caches are released first: two KV stacks never coexist."""
        self.caches = None
        self.caches = self.api.init_caches(self.slots,
                                           self.prompt_len + self.max_new_cap)

    def admit_full(self, params, row: np.ndarray, slot: int):
        """Monolithic admission of a full-width padded prompt row. Returns
        the device array holding the first token."""
        raise NotImplementedError

    def run_chunk(self, params, row: np.ndarray, slot: int, start: int,
                  valid: int):
        """One fixed-(1,C) prefill chunk at the slot's offset. Returns a
        tuple of device arrays: the chunk's last-valid-position argmax (the
        first token when this was the prompt's final chunk), then the
        chunk's counts where the program returns any (a held-expert
        model's picks)."""
        self.caches, *out = self._chunk(
            params, self.caches, jnp.asarray(row[None]),
            jnp.asarray(slot, jnp.int32), jnp.asarray(start, jnp.int32),
            jnp.asarray(valid, jnp.int32))
        return tuple(out)

    def decode_step(self, params, last_tok, positions, active):
        self.caches, nxt, new_pos = self._decode(
            params, self.caches, jnp.asarray(last_tok),
            jnp.asarray(positions), jnp.asarray(active))
        return nxt, new_pos

    def decode_block(self, params, bucket, last_tok, positions, active,
                     remaining, eos):
        """(toks, emitted, last_tok, positions, active, remaining) of one
        block, then the block's counts where the program returns any."""
        self.caches, *out = self._decode_blocks[bucket](
            params, self.caches, jnp.asarray(last_tok),
            jnp.asarray(positions), jnp.asarray(active),
            jnp.asarray(remaining), jnp.asarray(eos))
        return tuple(out)

    def place_params(self, params):
        """``params`` placed as the step programs take them. The colocated
        programs take the caller's placement as it is."""
        return params

    def reset(self, slot: int):
        self.caches = self._reset(self.caches, jnp.asarray(slot, jnp.int32))

    def swap_out(self, slot: int):
        """Export one slot's stored KV (device tuple; caller hosts it).
        Read-only: the resident caches are NOT donated or modified."""
        return self._swap_out_p(self.caches, jnp.asarray(slot, jnp.int32))

    def swap_in(self, saved, slot: int, valid_len: int):
        """Masked true-length restore of an exported slot image."""
        self.caches = self._swap_in_p(
            self.caches, saved, jnp.asarray(slot, jnp.int32),
            jnp.asarray(valid_len, jnp.int32))

    def drain_prefill(self, params, toks: np.ndarray):
        raise NotImplementedError

    def drain_decode(self, params, caches, last):
        raise NotImplementedError


class ColocatedBackend(ExecutorBackend):
    """Single-domain executor: weights and KV share every device; the step
    programs are the family's own ``ModelAPI`` slotted extensions."""

    name = "colocated"

    # -- program construction --------------------------------------------
    def _build_continuous(self, params, caches_aval, kv_bucket_chunk,
                          prefill_chunk, debug_reset_slots):
        api, ctx = self.api, self.ctx
        B, P, T = self.slots, self.prompt_len, self.block_size
        scalar = jnp.zeros((), jnp.int32)
        self._prefill1 = None

        # tiered caches admit through the chunk program even monolithically:
        # write_prefill has no cold-staging path (the chunk program quantizes
        # the cold prefix and rings the hot tail inside ONE compiled body),
        # so monolithic admission compiles the degenerate full-width chunk —
        # the WA backend's serve_wa_admit shape, same semantics (padding
        # attended, cursor at the padded width)
        tiered = isinstance(caches_aval, KVCache) and caches_aval.is_tiered
        if prefill_chunk or tiered:
            def chunk_fn(p, caches, toks, slot, start, valid):
                caches, logits, *counts = api.prefill_chunk(
                    p, caches, toks, slot, start, valid, ctx)
                return (caches, jnp.argmax(logits[:, -1], -1).astype(
                    jnp.int32), *counts)

            toks_c = jnp.zeros((1, prefill_chunk or P), jnp.int32)
            self._chunk = self.rt.compile_step(
                "serve_prefill_chunk" if prefill_chunk else "serve_admit",
                chunk_fn,
                (params, caches_aval, toks_c, scalar, scalar, scalar),
                donate_argnums=(1,))
        else:
            def prefill1_fn(p, toks):
                caches, logits = api.prefill(p, {"tokens": toks}, ctx)
                return caches, jnp.argmax(logits[:, -1], -1).astype(jnp.int32)

            def admit_fn(caches, single, slot):
                caches = _pin_cache_tree(caches, ctx)
                return _pin_cache_tree(api.write_slot(caches, single, slot),
                                       ctx)

            toks1 = jnp.zeros((1, P), jnp.int32)
            single_aval, _ = jax.eval_shape(prefill1_fn, params, toks1)
            self._prefill1 = self.rt.compile_step(
                "serve_prefill1", prefill1_fn, (params, toks1))
            self._admit = self.rt.compile_step(
                "serve_admit", admit_fn, (caches_aval, single_aval, scalar),
                donate_argnums=(0,))

        self._build_reset(caches_aval, debug_reset_slots)
        # split-KV decode (a_shards > 1) is forwarded only when on:
        # attention-free families' decode_slotted has no kv_shards kwarg
        sh = {"kv_shards": self.a_shards} if self.a_shards > 1 else {}
        self._build_decode_programs(
            params, caches_aval, kv_bucket_chunk, "serve_",
            lambda p, c, t, pos, act: api.decode_slotted(p, c, t, pos, act,
                                                         ctx, **sh),
            lambda p, c, t, pos, act, rem, eos, sb: api.decode_block(
                p, c, t, pos, act, rem, eos, ctx, block_size=T,
                kv_bucket=sb, **sh))

    def _build_drain(self, params):
        api, ctx = self.api, self.ctx

        def prefill_fn(p, toks):
            caches, logits = api.prefill(p, {"tokens": toks}, ctx)
            return caches, jnp.argmax(logits[:, -1], -1).astype(jnp.int32)

        def decode_fn(p, caches, tokens):
            caches, logits = api.decode(p, caches, tokens, ctx)
            return caches, jnp.argmax(logits[:, 0], -1).astype(jnp.int32)

        toks0 = jnp.zeros((self.slots, self.prompt_len), jnp.int32)
        caches_aval, tok_aval = jax.eval_shape(prefill_fn, params, toks0)
        self._prefill_b = self.rt.compile_step(
            "serve_prefill_batch", prefill_fn, (params, toks0))
        self._decode_b = self.rt.compile_step(
            "serve_decode_drain", decode_fn, (params, caches_aval, tok_aval),
            donate_argnums=(1,))

    # -- execution --------------------------------------------------------
    def admit_full(self, params, row: np.ndarray, slot: int):
        """Monolithic admission: batch-1 full-width prefill + slot write
        (flat caches), or — for tiered caches — ONE full-width chunk that
        lands both tiers directly in the slot (no separate write-slot copy:
        the cold quantization and hot ring write live inside the chunk
        program)."""
        if self._prefill1 is None:
            self.caches, tok, *_counts = self._chunk(
                params, self.caches, jnp.asarray(row[None]),
                jnp.asarray(slot, jnp.int32), jnp.asarray(0, jnp.int32),
                jnp.asarray(self.prompt_len, jnp.int32))
            return tok
        single, first = self._prefill1(params, jnp.asarray(row[None]))
        self.caches = self._admit(self.caches, single,
                                  jnp.asarray(slot, jnp.int32))
        return first

    def drain_prefill(self, params, toks: np.ndarray):
        caches, first = self._prefill_b(params, jnp.asarray(toks))
        return caches, first

    def drain_decode(self, params, caches, last):
        return self._decode_b(params, caches, last)


class WABackend(ExecutorBackend):
    """Weight–attention disaggregated executor (DESIGN.md §3): every step
    program runs ``core/wa.py``'s routed layer loop — QKV/FFN under the
    W-domain rules, KV writes / prefix reads / bucket slices / halt-mask
    advances under the A-domain rules, with the W→A→W hops as sharding
    constraints INSIDE the compiled program (``jax.device_put``-free).
    Per-slot cursors and KV buckets are A-side state; the scheduler's
    decisions arrive only as traced operands, so every program compiles
    exactly once across a staggered serve.

    Admission is ALWAYS the WA chunk program: the chunked lane runs the
    fixed (1,C) window; monolithic admission is the degenerate single
    full-width chunk (C = prompt_len, valid = prompt_len — padding
    attended, cursor at the padded width, exactly the colocated monolithic
    semantics).

    ``routed_bytes`` meters the W↔A hops (``core/wa.py::routing_bytes``):
    every dispatched micro-step routes the whole (B, d_model) batch twice
    per layer, every prefill chunk its (C, d_model) window — the measured
    form of the paper's "only embeddings move"."""

    name = "wa"
    program_prefix = "serve_wa_"

    @property
    def cache_ctx(self) -> ShardingCtx:
        return self.wa.a_ctx

    # the swap pair runs on the A domain through core/wa.py's own cache
    # pins (split-KV stays a read-time view — the exported bytes are
    # shard-agnostic); zero W↔A hops, so expected_routing has no entry
    def _swap_export_fn(self, caches, slot):
        return self.wa.swap_out_slot(caches, slot)

    def _swap_import_fn(self, caches, saved, slot, valid_len):
        return self.wa.swap_in_slot(caches, saved, slot, valid_len)

    def _build_continuous(self, params, caches_aval, kv_bucket_chunk,
                          prefill_chunk, debug_reset_slots):
        api, ctx = self.api, self.ctx
        B, P, T = self.slots, self.prompt_len, self.block_size
        self.wa = WADisaggregated(api.config, ctx.mesh, routing="sharding",
                                  a_shards=self.a_shards,
                                  overlap=self.overlap)
        self._el = jnp.dtype(dtype_of(api.config)).itemsize
        self.routed_bytes = 0
        scalar = jnp.zeros((), jnp.int32)
        # weights live under the W-domain rules on the serving mesh: the
        # programs compile for that placement and place_params() moves the
        # caller's tree there once per run (a tree left on one device would
        # be re-sharded by every dispatch)
        self._w_shardings = None
        if ctx.mesh is not None:
            self._w_shardings = jax.tree.map(
                lambda s: jax.sharding.NamedSharding(ctx.mesh, s),
                param_specs(params, self.wa.w_ctx))
            params = jax.tree.map(
                lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                  sharding=s),
                params, self._w_shardings)

        def chunk_fn(p, caches, toks, slot, start, valid):
            caches, logits = self.wa.prefill_chunk(p, caches, toks, slot,
                                                   start, valid)
            return caches, jnp.argmax(logits[:, -1], -1).astype(jnp.int32)

        toks_c = jnp.zeros((1, prefill_chunk or P), jnp.int32)
        self._chunk = self.rt.compile_step(
            "serve_wa_prefill_chunk" if prefill_chunk else "serve_wa_admit",
            chunk_fn, (params, caches_aval, toks_c, scalar, scalar, scalar),
            donate_argnums=(1,))

        self._build_reset(caches_aval, debug_reset_slots)
        self._build_decode_programs(
            params, caches_aval, kv_bucket_chunk, "serve_wa_",
            lambda p, c, t, pos, act: self.wa.decode_step_slotted(
                p, c, t, pos, act),
            lambda p, c, t, pos, act, rem, eos, sb: self.wa.decode_block(
                p, c, t, pos, act, rem, eos, None, block_size=T,
                kv_bucket=sb))

    # -- W↔A traffic model -------------------------------------------------
    def expected_routing(self, name: str) -> Tuple[int, int]:
        """Analytic routing model for ONE dispatch of program ``name``:
        returns ``(rows, trips)`` meaning the dispatch routes
        ``trips × routing_bytes(cfg, rows, el)`` W↔A bytes (``trips`` =
        micro-steps inside the program; a T-block scans T micro-steps).
        Single source of truth shared by the runtime meter (``_meter``) and
        the static verifier's routing cross-check
        (``repro.analysis.routing_check``) — the meter and the compiled
        programs cannot drift apart without the gate failing."""
        if name == "serve_wa_admit":
            return self.prompt_len, 1
        if name == "serve_wa_prefill_chunk":
            return self.prefill_chunk, 1
        if name == "serve_wa_decode":
            return self.slots, 1
        if name.startswith("serve_wa_decode_block"):
            return self.slots, self.block_size
        raise KeyError(f"no routing model for WA program {name!r}")

    def _meter(self, name: str):
        rows, trips = self.expected_routing(name)
        self.routed_bytes += trips * routing_bytes(self.api.config, rows,
                                                   self._el)

    # -- execution (adds the W↔A traffic meter) ---------------------------
    def place_params(self, params):
        if self._w_shardings is None:
            return params
        return jax.device_put(params, self._w_shardings)

    def fresh(self):
        super().fresh()
        self.routed_bytes = 0

    def admit_full(self, params, row: np.ndarray, slot: int):
        """Monolithic WA admission: ONE full-width chunk (start 0, the
        padded width valid) — KV lands directly in the slot, no separate
        write-slot copy (the cache never leaves the A domain)."""
        self.caches, tok = self._chunk(
            params, self.caches, jnp.asarray(row[None]),
            jnp.asarray(slot, jnp.int32), jnp.asarray(0, jnp.int32),
            jnp.asarray(self.prompt_len, jnp.int32))
        # metered AFTER the dispatch ran: a failed/retried dispatch never
        # reached the device, so it must not inflate the routed-bytes claim
        self._meter("serve_wa_admit")
        return tok

    def run_chunk(self, params, row, slot, start, valid):
        out = super().run_chunk(params, row, slot, start, valid)
        self._meter("serve_wa_prefill_chunk")
        return out

    def decode_step(self, params, last_tok, positions, active):
        out = super().decode_step(params, last_tok, positions, active)
        self._meter("serve_wa_decode")
        return out

    def decode_block(self, params, bucket, last_tok, positions, active,
                     remaining, eos):
        out = super().decode_block(params, bucket, last_tok, positions,
                                   active, remaining, eos)
        self._meter("serve_wa_decode_block")
        return out

    def routing_stats(self, decode_tokens: int) -> Dict[str, Any]:
        """The measured 'only embeddings move' numbers for ``run()`` stats:
        the per-token claim (2 hops × L × d_model for one row) plus the
        metered total across every dispatched program this run. Both are
        overlap-invariant: depth D routes D× as many hops each carrying
        B/D rows."""
        return {
            "routing_bytes_per_token": routing_bytes(self.api.config, 1,
                                                     self._el),
            "routing_total_bytes": int(self.routed_bytes),
            "routing_bytes_per_decode_token":
                float(self.routed_bytes / max(decode_tokens, 1)),
        }

    def overlap_stats(self, decode_time_s: float, macro_steps: int,
                      mb_live: int, mb_total: int) -> Dict[str, Any]:
        """Per-domain stall accounting for the sub-operator overlap
        schedule (DESIGN.md §3). The skewed schedule is STATIC, so each
        domain's idle ticks are exact schedule arithmetic
        (``core.pipeline.wa_schedule_occupancy``) — the measured decode
        wall-time per macro-step splits by those fractions into W-idle vs
        A-idle time, and ``overlap_efficiency`` is busy ticks over total
        (both domains): ~0.5 sequential, → 1 as depth grows.
        ``micro_batch_occupancy`` is the scheduler-view fraction of
        dispatched micro-batches that carried a live slot (a fully-idle
        micro-batch still executes — static programs dispatch all rows)."""
        occ = wa_schedule_occupancy(self.api.config.n_layers, self.overlap)
        step_ms = decode_time_s * 1e3 / max(macro_steps, 1)
        return {
            "overlap": self.overlap,
            "overlap_efficiency": occ["overlap_efficiency"],
            "schedule_ticks": occ["total_ticks"],
            "w_busy_ticks": occ["w_busy_ticks"],
            "a_busy_ticks": occ["a_busy_ticks"],
            "w_idle_ms_per_macro_step": step_ms * occ["w_idle_frac"],
            "a_idle_ms_per_macro_step": step_ms * occ["a_idle_frac"],
            "micro_batch_occupancy": float(mb_live / max(mb_total, 1)),
        }


BACKENDS: Dict[str, type] = {"colocated": ColocatedBackend, "wa": WABackend}


# ---------------------------------------------------------------------------
# KVArbiter — host-side placement arbiter for the tiered KV cache
# ---------------------------------------------------------------------------

class KVArbiter:
    """Host-side placement arbiter for the tiered KV cache (DESIGN.md §7).

    Demotion itself happens INSIDE the compiled programs — the read-side
    cold boundary advances with each slot's cursor, so no host round-trip
    ever moves a token between tiers. What remains for the host is pure
    accounting and policy, and that is this class: it observes per-slot
    cursors at the block boundaries the engine already syncs at (zero extra
    device traffic), derives tier occupancy from the same
    ``cold_boundary()`` arithmetic the programs compiled in, counts
    demotions from cursor watermarks, tracks live/peak KV bytes against an
    optional byte budget (the pressure loop preempts victims while over
    it), and recommends a placement policy from the observed access
    pattern (the LLaMCAT-style arbiter of the paper's §6 discussion).

    The byte model reads off the cache aval: a hot token costs the
    cache-resident dtype across every layer/head; a cold token costs the
    packed cold store (int4 packs two lanes per byte) plus its per-row
    f32 scales. ``cold_bytes_saved`` is live occupancy priced at the hot
    rate minus the cold rate — the bytes the LLC does NOT hold because the
    cold prefix is quantized."""

    def __init__(self, caches_aval: KVCache, budget_bytes: int = 0):
        if not caches_aval.is_tiered:
            raise ValueError("KVArbiter requires a tiered cache aval")
        self.hot_window = int(caches_aval.hot_window)
        self.cold_block = int(caches_aval.cold_block)
        self.cold_dtype = str(caches_aval.cold_dtype)
        self.budget = int(budget_bytes)
        L, B, n_kv, S, hd_c = caches_aval.k.shape
        H = caches_aval.hot_k.shape[3]
        hd = caches_aval.hot_k.shape[4]
        hot_el = jnp.dtype(caches_aval.hot_k.dtype).itemsize
        cold_el = jnp.dtype(caches_aval.k.dtype).itemsize
        scale_b = 0 if caches_aval.k_scale is None else\
            jnp.dtype(caches_aval.k_scale.dtype).itemsize
        # per-token rates, K + V, across all layers and KV heads
        self.hot_bytes_per_token = 2 * L * n_kv * hd * hot_el
        self.cold_bytes_per_token = 2 * L * n_kv * (hd_c * cold_el + scale_b)
        # allocated footprint of ONE slot (what fresh() reserves for it):
        # full-extent cold store + scales + the hot ring
        self.kv_bytes_per_slot = (S * self.cold_bytes_per_token
                                  + H * self.hot_bytes_per_token)
        self.n_slots = B
        self.reset()

    def reset(self):
        """Per-run accounting reset (mirrors the engine's accumulators)."""
        self._cursor: Dict[int, int] = {}
        self._watermark: Dict[int, int] = {}    # last-seen cold boundary
        self.demotions = 0                      # cold blocks crossed, total
        self.peak_bytes = 0
        self.peak_saved = 0
        self._last_rec = "no live slots observed"

    # -- bookkeeping (called at host-sync boundaries only) ---------------
    def _boundary(self, cursor: int) -> int:
        return int(cold_boundary(np.int32(cursor), self.hot_window,
                                 self.cold_block))

    def observe(self, slot: int, cursor: int):
        """One slot's cursor at a block boundary. Cold-boundary advances
        since the last observation count as demotions (one per crossed
        ``cold_block``)."""
        cursor = int(cursor)
        nb = self._boundary(cursor)
        prev = self._watermark.get(slot, 0)
        if nb > prev:
            self.demotions += (nb - prev) // self.cold_block
        self._watermark[slot] = nb
        self._cursor[slot] = cursor
        self.peak_bytes = max(self.peak_bytes, self.live_bytes())
        self.peak_saved = max(self.peak_saved, self.cold_bytes_saved())
        self._last_rec = self._recommend_live()

    def seed(self, slot: int, cursor: int):
        """Swap-in restore: the slot resumes at ``cursor`` with its cold
        prefix already staged and already COUNTED pre-preemption — seed the
        watermark so the restore recounts nothing."""
        cursor = int(cursor)
        self._watermark[slot] = self._boundary(cursor)
        self._cursor[slot] = cursor

    def release(self, slot: int):
        """Slot freed (retire / preempt / quarantine): its occupancy and
        watermark leave the live view; cumulative counters stay."""
        self._cursor.pop(slot, None)
        self._watermark.pop(slot, None)

    # -- occupancy / budget ----------------------------------------------
    def slot_occupancy(self, slot: int) -> Dict[str, int]:
        c = self._cursor.get(slot, 0)
        cold = self._boundary(c)
        hot = c - cold
        return {"slot": slot, "tokens": c, "hot_tokens": hot,
                "cold_tokens": cold,
                "kv_bytes": hot * self.hot_bytes_per_token
                + cold * self.cold_bytes_per_token}

    def live_bytes(self) -> int:
        """Occupancy-priced KV bytes across every live slot (hot tokens at
        the resident rate, cold tokens at the quantized rate)."""
        total = 0
        for c in self._cursor.values():
            cold = self._boundary(c)
            total += (c - cold) * self.hot_bytes_per_token\
                + cold * self.cold_bytes_per_token
        return total

    def cold_bytes_saved(self) -> int:
        saved_rate = self.hot_bytes_per_token - self.cold_bytes_per_token
        return sum(self._boundary(c) for c in self._cursor.values())\
            * saved_rate

    def over_budget(self) -> bool:
        return bool(self.budget) and self.live_bytes() > self.budget

    # -- policy -----------------------------------------------------------
    def recommend(self) -> str:
        """Placement recommendation from the observed pattern: deepen the
        quantized tier while the cold fraction dominates, surface the hot
        window when the working set already fits it. After a drained run
        (no live slots) the last live-boundary verdict stands."""
        return self._recommend_live() if self._cursor else self._last_rec

    def _recommend_live(self) -> str:
        cursors = list(self._cursor.values())
        if not cursors:
            return "no live slots observed"
        total = sum(cursors)
        cold = sum(self._boundary(c) for c in cursors)
        if cold == 0:
            return (f"working set fits hot_window={self.hot_window}; cold "
                    "tier idle — a smaller hot_window frees resident bytes")
        frac = cold / max(total, 1)
        if frac > 0.75 and self.cold_dtype == "int8":
            return ("cold tier dominates (>75% of tokens); int4 cold "
                    "storage would halve its footprint")
        if frac > 0.5 and self.cold_dtype == "bfloat16":
            return ("cold tier holds most tokens at full width; quantize "
                    "it (kv_cold_dtype=int8 or int4)")
        return "placement balanced for the observed access pattern"

    def stats(self) -> Dict[str, Any]:
        return {
            "hot_window": self.hot_window,
            "cold_block": self.cold_block,
            "cold_dtype": self.cold_dtype,
            "hot_bytes_per_token": self.hot_bytes_per_token,
            "cold_bytes_per_token": self.cold_bytes_per_token,
            "kv_bytes_per_slot": self.kv_bytes_per_slot,
            "kv_budget_bytes": self.budget,
            "demotions": self.demotions,
            "live_kv_bytes": self.live_bytes(),
            "peak_kv_bytes": self.peak_bytes,
            "cold_bytes_saved": max(self.peak_saved,
                                    self.cold_bytes_saved()),
            "per_slot": [self.slot_occupancy(s)
                         for s in sorted(self._cursor)],
            "recommendation": self.recommend(),
        }


# ---------------------------------------------------------------------------
# ServingEngine — the boundary loop connecting scheduler and executor
# ---------------------------------------------------------------------------

class ServingEngine:
    """Greedy decoding over fixed batch slots with per-slot admission.

    mode="continuous": slot-level scheduler (requires the ModelAPI slotted
    extensions); mode="drain": legacy drain-then-refill baseline;
    mode="auto": continuous when the family supports it.

    ``block_size`` (T): decode micro-steps per host round-trip. T == 1 is the
    per-step engine (one ``serve_decode`` program, one host sync per token);
    T > 1 runs ``ModelAPI.decode_block`` with on-device halt masks — one host
    sync per T tokens, admission at block boundaries only.

    ``prefill_chunk`` (C, continuous mode, families with
    ``ModelAPI.prefill_chunk``): admission runs as fixed-(1,C) prompt chunks,
    AT MOST ONE per block boundary, interleaved with the decode block — the
    chunked-prefill lane. Prompts carry TRUE lengths end to end: the decode
    cursor starts at the real prompt length and any length that fits the KV
    extent (prompt + max_new_tokens ≤ prompt_len + max_new_cap) is admitted
    chunk by chunk. 0 → monolithic admission (one full-width prefill program;
    prompts longer than ``prompt_len`` raise ``ValueError`` at submit —
    nothing is ever silently truncated).

    ``kv_bucket_chunk`` (block mode, KV-cache families): > 0 compiles one
    decode-block program per KV bucket (chunk multiples up to the cache
    extent) and picks the smallest covering bucket per macro-step on the
    host. 0 disables bucketing (single full-extent block program).

    ``debug_reset_slots``: zero a slot's cache state when its request
    retires (``ModelAPI.reset_slot``, one more AOT program). Never required
    for correctness — masked attention cannot read past a cursor — but keeps
    cache dumps clean and slot-state invariants checkable.

    ``backend``: the executor implementation. ``"colocated"`` (default)
    runs the family's own slotted programs; ``"wa"`` runs the SAME feature
    set — macro-step blocks, KV buckets, chunked prefill, slot admission —
    through the weight–attention disaggregated layer loop (``core/wa.py``,
    DESIGN.md §3): QKV/FFN under the W-domain rules, all slot state (KV
    writes, prefix reads, bucket slices) under the A-domain rules, with the
    per-layer W→A→W routing compiled INTO each step program. The scheduler
    is backend-agnostic; ``stats()["wa"]`` reports the measured W↔A routing
    bytes. Requires ``ModelAPI.wa_servable`` (prefix-ordered KV-cache
    transformers) and the continuous scheduler.

    ``a_shards``: split-KV flash decode width (KV-cache families,
    continuous mode). > 1 splits every slot's KV walk into that many equal
    contiguous sequence shards; each shard computes partial softmax
    statistics (running max, normalizer, un-normalized accumulator) and the
    shards recombine through the LSE merge (``kernels/flash_decode/
    combine.py``) — token-exact vs the sequential walk. Under
    ``backend="wa"`` on a mesh the shard axis is the A-domain model axis
    (``seq_sharded_kv``), so attention latency scales with A-width; on the
    colocated backend (and any single-device run) the same math runs
    unsharded. The KV extent (prompt_len + max_new_cap) must divide by
    ``a_shards``; bucket sets are rounded so every bucket splits evenly.
    Program names do not change — the shard count is a build-time static
    baked into the same programs, so compiles == 1 still holds per bucket.

    ``overlap``: sub-operator micro-batch pipelining of the W/A boundary
    (WA backend only, DESIGN.md §3). > 1 splits each decode dispatch's
    batch into that many equal micro-batches and software-pipelines them
    through the routed layer loop with skewed layer indices
    (``core/wa.py::_layer_loop_pipelined``): W runs QKV/FFN for one
    micro-batch while A attends another — true sub-operator dependencies
    instead of a per-layer W→A→W barrier. Token-exact at every depth,
    program names unchanged (the depth is a build-time static; depth 1
    compiles today's exact sequential programs), composes with macro-step
    blocks, KV buckets, split-KV ``a_shards``, chunked prefill and the
    preemption swap pair (the swap programs are cache-only — no layer
    loop, nothing to pipeline). ``batch_slots`` must divide by
    ``overlap``. ``stats()['wa']`` reports the schedule's per-domain
    stall accounting (W-idle / A-idle per macro-step, overlap
    efficiency).

    ``preemptible``: compile the token-exact swap pair
    (``serve_[wa_]swap_out`` / ``serve_[wa_]swap_in``) and allow the
    boundary loop to preempt a decoding slot — swap its true-length KV to
    a host-side buffer, free the slot for higher-priority work (or under
    injected KV pressure), and restore later with cursors intact. Requires
    the continuous scheduler and a prefix-ordered non-windowed KV-cache
    family. Restored sequences are byte-identical to uninterrupted ones.

    ``max_queue``: bounded-queue backpressure. > 0 sheds the
    lowest-priority (then most recently enqueued) queued request as a
    structured rejection whenever the queue exceeds the bound — overload
    degrades to explicit rejections, not unbounded queueing.

    ``max_retries`` / ``retry_backoff_s`` / ``watchdog_s``: dispatch
    hardening. Every program dispatch retries up to ``max_retries`` times
    on ``DispatchError`` (with exponential backoff when ``retry_backoff_s``
    > 0); a dispatch exceeding ``watchdog_s`` wall-clock bumps the watchdog
    counter. A dispatch that exhausts its budget demotes the responsible
    request to a structured rejection and quarantines the slot whose cache
    bytes are suspect (``stats()['quarantined_slots']``).

    ``strict_invariants``: run the scheduler's occupancy/cursor invariant
    check at every block boundary (the chaos harness turns this on);
    violations raise ``AssertionError`` immediately.

    ``fault_injector``: deterministic chaos hook
    (``repro.runtime.faults.FaultInjector`` or compatible). Its
    ``on_dispatch(name)`` is installed as the ``StaticRuntime`` dispatch
    interceptor for the run (slow/failed dispatches); its
    ``slots_held(step)`` models artificial KV pressure — that many slots
    are withheld at each boundary, preempting victims when preemptible.

    An engine instance may be ``run()`` repeatedly: per-run accumulators
    (timings, sync counts, queues) reset and the slot caches are allocated
    fresh each run, while the AOT-compiled programs persist (compiles == 1
    across every run of the engine's lifetime).
    """

    def __init__(self, api: ModelAPI, ctx: ShardingCtx, batch_slots: int,
                 prompt_len: int, runtime: Optional[StaticRuntime] = None,
                 greedy: bool = True, mode: str = "auto",
                 max_new_cap: int = DECODE_SLACK,
                 block_size: int = 1, kv_bucket_chunk: int = 0,
                 prefill_chunk: int = 0,
                 debug_reset_slots: bool = False,
                 backend: str = "colocated", a_shards: int = 1,
                 overlap: int = 1,
                 preemptible: bool = False, max_queue: int = 0,
                 max_retries: int = 2, retry_backoff_s: float = 0.0,
                 watchdog_s: float = 0.0,
                 strict_invariants: bool = False,
                 fault_injector: Optional[Any] = None,
                 kv_budget_bytes: int = 0):
        if mode not in ("auto", "continuous", "drain"):
            raise ValueError(mode)
        if a_shards < 1:
            raise ValueError(f"a_shards must be >= 1, got {a_shards}")
        if backend not in BACKENDS:
            raise ValueError(f"unknown backend {backend!r}; choose from "
                             f"{sorted(BACKENDS)}")
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        if prefill_chunk < 0:
            raise ValueError(f"prefill_chunk must be >= 0, got {prefill_chunk}")
        if overlap < 1:
            raise ValueError(f"overlap must be >= 1, got {overlap}")
        if overlap > 1:
            # sub-operator pipelining splits the decode batch into overlap
            # micro-batches and skews them across the W/A boundary — it
            # needs that boundary (the WA backend) and equal micro-batches
            if backend != "wa":
                raise ValueError(
                    f"overlap={overlap} pipelines the W/A boundary; the "
                    f"{backend} backend has no W↔A hops to overlap "
                    "(use backend='wa', DESIGN.md §3)")
            if batch_slots % overlap:
                raise ValueError(
                    f"batch_slots={batch_slots} does not divide into "
                    f"overlap={overlap} equal micro-batches")
        if backend == "wa":
            # the WA backend carries its own decode/admission programs
            # (core/wa.py) — it needs the continuous scheduler and a family
            # whose KV the W/A split can decouple (DESIGN.md §6)
            if mode == "drain":
                raise ValueError("the WA backend serves through the "
                                 "continuous scheduler; drain mode is "
                                 "colocated-only")
            if not api.wa_servable:
                raise ValueError(
                    f"{api.config.family} family has no WA-disaggregated "
                    "serving support (DESIGN.md §6)")
            resolved_mode = "continuous"
        else:
            # continuous mode needs a decode half (api.decode_block for
            # T > 1, api.decode_slotted for T == 1) AND an admission half
            # (api.prefill_chunk for the chunked lane, api.write_slot for
            # monolithic admission)
            decode_ok = (api.decode_block is not None if block_size > 1 else
                         api.decode_slotted is not None)
            if mode == "auto" and prefill_chunk > 0\
                    and api.prefill_chunk is None:
                # fall back to monolithic admission — LOUDLY: a benchmark
                # config that asked for the chunk lane must not quietly
                # measure the monolithic one
                warnings.warn(
                    f"prefill_chunk={prefill_chunk} requested but the "
                    f"{api.config.family} family has no prefill_chunk "
                    "support; falling back to monolithic admission (the "
                    "chunked-prefill lane is OFF for this engine)",
                    UserWarning, stacklevel=2)
                prefill_chunk = 0
            admit_ok = (api.prefill_chunk is not None if prefill_chunk > 0
                        else api.write_slot is not None)
            slotted_ok = admit_ok and decode_ok
            if mode == "continuous" and not slotted_ok:
                raise ValueError(
                    f"{api.config.family} family has no "
                    f"{'chunked-prefill' if prefill_chunk > 0 else 'slotted'} "
                    "serving support")
            if mode == "drain" and prefill_chunk > 0:
                raise ValueError("chunked prefill requires the continuous "
                                 "scheduler (drain prefills the whole batch)")
            resolved_mode = ("continuous" if slotted_ok else "drain")\
                if mode == "auto" else mode
        self.api = api
        self.ctx = ctx
        self.slots = batch_slots
        self.prompt_len = prompt_len
        self.max_new_cap = min(max_new_cap, DECODE_SLACK)
        self.mode = resolved_mode
        self.backend = backend
        if self.mode == "drain":
            prefill_chunk = 0                    # auto fallback: no lane
        self.block_size = block_size
        self.kv_bucket_chunk = kv_bucket_chunk
        self.prefill_chunk = prefill_chunk
        self.a_shards = a_shards
        self.overlap = overlap
        self.debug_reset_slots = debug_reset_slots
        self.preemptible = preemptible
        self.max_queue = max_queue
        if max_retries < 0:
            raise ValueError(f"max_retries must be >= 0, got {max_retries}")
        self.max_retries = max_retries
        self.retry_backoff_s = retry_backoff_s
        self.watchdog_s = watchdog_s
        self.strict_invariants = strict_invariants
        self.fault_injector = fault_injector
        self.rt = runtime or StaticRuntime()
        self.queue: List[Request] = []
        # the weights as the step programs take them (after the backend
        # placed them) — set by every run()
        self.params = None
        self._ex: Optional[ExecutorBackend] = None
        # the ONE derivation of the slot-cache aval: the executor compiles
        # against it and the KV-extent admission bound reads off it
        # (None extent → no length axis to bound, e.g. recurrent state)
        self._caches_aval = jax.eval_shape(
            lambda: api.init_caches(batch_slots,
                                    prompt_len + self.max_new_cap))
        self._kv_extent = self._caches_aval.k.shape[3]\
            if isinstance(self._caches_aval, KVCache)\
            and not self._caches_aval.window else None
        self._tiered = isinstance(self._caches_aval, KVCache)\
            and self._caches_aval.is_tiered
        if self._tiered:
            # the tiered cache stages its cold prefix inside the chunk
            # program — only the continuous scheduler has one, and only
            # families exposing prefill_chunk can compile it (monolithic
            # tiered admission is the degenerate full-width chunk)
            if self.mode != "continuous":
                raise ValueError(
                    "tiered KV caches (hot_window > 0) serve through the "
                    "continuous scheduler; drain mode has no chunk program "
                    "to stage the cold tier")
            if api.prefill_chunk is None:
                raise ValueError(
                    f"{api.config.family} family has no prefill_chunk "
                    "support; tiered admission stages the cold tier "
                    "through the chunk program")
        if kv_budget_bytes < 0:
            raise ValueError(
                f"kv_budget_bytes must be >= 0, got {kv_budget_bytes}")
        if kv_budget_bytes and not self._tiered:
            raise ValueError(
                "kv_budget_bytes is the tiered-KV arbiter's pressure knob "
                "(hot_window > 0); flat caches have no arbiter to enforce "
                "it")
        self.kv_budget_bytes = kv_budget_bytes
        self._arbiter = KVArbiter(self._caches_aval, kv_budget_bytes)\
            if self._tiered else None
        if self.a_shards > 1:
            # split-KV flash decode shards the *prefix-ordered* KV walk of
            # one slot along the sequence axis; families without such a
            # cache (recurrent state, ring windows) have nothing to shard
            if self.mode == "drain":
                raise ValueError("split-KV decode (a_shards > 1) runs "
                                 "through the slotted decode programs; "
                                 "drain mode has none")
            if self._kv_extent is None:
                raise ValueError(
                    f"a_shards={self.a_shards} requires a prefix-ordered "
                    "(non-windowed) KV-cache family; the "
                    f"{api.config.family} family has no KV sequence axis "
                    "to shard")
            if self._kv_extent % self.a_shards:
                raise ValueError(
                    f"KV extent {self._kv_extent} (prompt_len + "
                    "max_new_cap) not divisible by a_shards="
                    f"{self.a_shards}; every shard must own an equal "
                    "contiguous block")
        if self.prefill_chunk and isinstance(self._caches_aval, KVCache)\
                and self._caches_aval.window:
            raise ValueError("chunked prefill requires a non-windowed KV "
                             "cache (ring order has no per-position write "
                             "offset)")
        if self.prefill_chunk and self._kv_extent is not None\
                and self.prefill_chunk > self._kv_extent:
            raise ValueError(
                f"prefill_chunk={self.prefill_chunk} exceeds the KV extent "
                f"{self._kv_extent}; the fixed (1,C) window must fit the "
                "cache")
        if self.preemptible:
            # swap-out/restore slices one slot of a prefix-ordered KV
            # cache at its true length — recurrent states and ring windows
            # have no such slice, drain mode has no slot scheduler
            if self.mode != "continuous":
                raise ValueError("preemptible serving requires the "
                                 "continuous scheduler (drain has no slots "
                                 "to swap)")
            if self._kv_extent is None:
                raise ValueError(
                    f"preemptible=True requires a prefix-ordered "
                    "(non-windowed) KV-cache family; the "
                    f"{api.config.family} family has no slot KV extent to "
                    "swap out")
        self._reset_per_run()

    # ------------------------------------------------------------------
    def _reset_per_run(self):
        """Per-run accumulators. An engine reused across ``run()`` calls
        must not leak timing samples or sync counts from a previous run
        (stats would blend workloads), and the executor's caches from a
        finished run must never seed the next one (stale KV in freed
        slots)."""
        # every engine timing: spans per boundary phase, counters per
        # decode dispatch (runtime/spans.py)
        self.spans = SpanTable()
        self.host_syncs = 0
        self._decode_tokens = 0
        self._prefill_chunks = 0
        self._block_tokens: List[int] = []
        self._macro_steps = 0
        # a held-expert model's picks per (layer, held expert), by phase,
        # and the decode micro-steps that produced a token
        self._expert_picks: Dict[str, np.ndarray] = {}
        self._decode_micro_steps = 0
        # micro-batch occupancy under overlap > 1 (scheduler view)
        self._micro_batches_live = 0
        self._micro_batches_total = 0
        self.queue = []
        # pressure/robustness accounting (DESIGN.md §7 failure model)
        self._rejected: List[Request] = []
        self._deadline_missed: List[Request] = []
        self._preemptions = 0
        self._restores = 0
        self._retries = 0
        self._watchdog_timeouts = 0
        self._quarantined: set = set()
        # emission log: (rid, token_index) in host-visible order — the
        # chaos invariant checker proves no token was duplicated, lost or
        # reordered from this alone
        self._emit_log: List[Tuple[int, int]] = []
        self._cursor_watermark: Dict[int, int] = {}
        self._slot_cap = self.slots
        if self._arbiter is not None:
            self._arbiter.reset()

    def _emit_token(self, r: Request, tok: int):
        r.generated.append(int(tok))
        self._emit_log.append((r.rid, len(r.generated) - 1))

    def _finish(self, r: Request, now: float):
        r.status = "completed"
        r.t_done = now

    def _reject(self, r: Request, reason: str):
        r.status = "rejected"
        r.reject_reason = reason
        r.t_done = time.monotonic()
        r.swap = None                    # drop any held KV image
        self._rejected.append(r)

    def _miss_deadline(self, r: Request, reason: str):
        r.status = "deadline_missed"
        r.reject_reason = reason
        r.t_done = time.monotonic()
        r.swap = None
        self._deadline_missed.append(r)

    # -- hardened dispatch ---------------------------------------------
    def _dispatch(self, name: str, fn, *args):
        """Bounded retry-with-backoff around one program dispatch.
        ``DispatchError`` is raised by the interceptor layer BEFORE the
        compiled call touches its operands (donated buffers still valid),
        so the dispatch retries verbatim; exhausting the budget raises
        ``DispatchFailure`` for the boundary loop to demote to a structured
        rejection. Any other exception is a real bug and propagates. A
        dispatch exceeding ``watchdog_s`` wall-clock bumps the watchdog
        counter (the work DID run — JAX cannot cancel an in-flight
        dispatch — so the watchdog detects and records stalls rather than
        aborting them). Each attempt that returns is the in-memory span
        ``dispatch``, which the watchdog reads."""
        attempt = 0
        while True:
            try:
                with self.spans.span("dispatch", emit=False) as sp:
                    out = fn(*args)
            except DispatchError as e:
                if attempt >= self.max_retries:
                    raise DispatchFailure(name, attempt + 1, e) from e
                attempt += 1
                self._retries += 1
                if self.retry_backoff_s:
                    time.sleep(self.retry_backoff_s * (2 ** (attempt - 1)))
                continue
            if self.watchdog_s and sp.seconds > self.watchdog_s:
                self._watchdog_timeouts += 1
            return out

    def _quarantine_slot(self, sched: SlotScheduler, slot: int):
        sched.quarantined.add(slot)
        self._quarantined.add(slot)

    def _host_sync(self, *arrays):
        """THE counted device→host round-trip of the decode loop — the
        coordination cost the macro-step engine amortizes (1 sync per
        ``block_size`` tokens). Tests assert on ``self.host_syncs``."""
        self.host_syncs += 1
        out = tuple(np.asarray(a) for a in arrays)
        return out if len(out) > 1 else out[0]

    def _validate_request(self, r: Request):
        """Admission-time length contract — the silent-truncation fix: a
        prompt the engine cannot represent is REJECTED here, never cut.
        Raises ``RequestRejected`` (a ``ValueError``) carrying the request
        id, the offending length and the per-mode limit as fields, so a
        fleet log can say WHICH knob the request overflowed."""
        L = len(r.prompt)
        if L == 0:
            raise RequestRejected(r.rid, "empty prompt", length=0,
                                  limit=1, limit_name="min prompt length")
        if r.max_new_tokens < 1:
            raise RequestRejected(
                r.rid,
                f"max_new_tokens={r.max_new_tokens} must be >= 1 (every "
                "admission produces a first token)",
                length=r.max_new_tokens, limit=1,
                limit_name="min max_new_tokens")
        if r.max_new_tokens > self.max_new_cap:
            raise RequestRejected(
                r.rid,
                f"max_new_tokens={r.max_new_tokens} exceeds cache slack "
                f"{self.max_new_cap} (raise max_new_cap)",
                length=r.max_new_tokens, limit=self.max_new_cap,
                limit_name="max_new_cap")
        if self.mode == "drain" or not self.prefill_chunk:
            if L > self.prompt_len:
                raise RequestRejected(
                    r.rid,
                    f"prompt length {L} exceeds the static prompt width "
                    f"{self.prompt_len} (monolithic admission) and would "
                    "be silently truncated; raise prompt_len or enable the "
                    "chunked-prefill lane (prefill_chunk > 0)",
                    length=L, limit=self.prompt_len,
                    limit_name="prompt_len")
        elif self._kv_extent is not None\
                and L + r.max_new_tokens > self._kv_extent:
            raise RequestRejected(
                r.rid,
                f"prompt length {L} + max_new_tokens={r.max_new_tokens} "
                f"= {L + r.max_new_tokens} exceeds the KV extent "
                f"{self._kv_extent} (chunked admission; raise prompt_len "
                "or max_new_cap)",
                length=L + r.max_new_tokens, limit=self._kv_extent,
                limit_name="kv_extent")

    def submit(self, req: Request):
        self._validate_request(req)
        req.t_enqueue = time.monotonic()
        req.status = "queued"
        self.queue.append(req)

    # ------------------------------------------------------------------
    def _prepare(self, params):
        if self._ex is None:
            self._ex = BACKENDS[self.backend](
                self.api, self.ctx, self.rt, params, self._caches_aval,
                mode=self.mode,
                slots=self.slots, prompt_len=self.prompt_len,
                max_new_cap=self.max_new_cap, block_size=self.block_size,
                kv_bucket_chunk=self.kv_bucket_chunk,
                prefill_chunk=self.prefill_chunk,
                debug_reset_slots=self.debug_reset_slots,
                a_shards=self.a_shards, overlap=self.overlap,
                preemptible=self.preemptible)

    def run(self, params, requests: List[Request],
            max_steps: int = 10_000) -> Dict[str, Any]:
        """Serve all requests to completion; returns latency stats.
        Requests enqueued via ``submit()`` before this call are served too
        (never silently dropped). Reusable: each call starts from fresh
        caches and fresh accumulators (AOT programs persist — zero
        recompilation across runs)."""
        pre = list(self.queue)
        seen = {id(r) for r in pre}
        requests = pre + [r for r in requests if id(r) not in seen]
        for r in requests:
            self._validate_request(r)
        self._prepare(params)
        self.params = params = self._ex.place_params(params)
        self._reset_per_run()
        # fault-injection hook: installed (or cleared) per run so a clean
        # reference run on the same engine sees zero injected faults
        self.rt.set_interceptor(
            getattr(self.fault_injector, "on_dispatch", None)
            if self.fault_injector is not None else None)
        if self.mode == "continuous":
            return self._run_continuous(params, requests, max_steps)
        return self._run_drain(params, requests, max_steps)

    # ------------------------------------------------------------------
    # continuous scheduler: ONE boundary loop for T == 1 and T > 1,
    # monolithic and chunked admission
    # ------------------------------------------------------------------

    def _run_continuous(self, params, requests, max_steps):
        T = self.block_size
        ex = self._ex
        self._caches = None      # the last run's caches go before fresh() allocates
        ex.fresh()
        sched = SlotScheduler(self.slots, requests, self.queue)
        self._sched = sched
        done: List[Request] = []
        steps = admissions = overlapped = 0
        s_max = self.prompt_len + self.max_new_cap
        spans = self.spans
        while sched.work_remaining():
            if steps >= max_steps:
                break
            spans.open_boundary()
            with spans.span("policies"):
                sched.pump(steps)
                if sched.usable_capacity() == 0:
                    # every slot quarantined: nothing can ever be admitted
                    # again — demote ALL remaining work to structured
                    # rejections instead of spinning to max_steps
                    for r in sched.pending + sched.queue:
                        self._reject(r, "no usable slots (all quarantined)")
                    sched.pending.clear()
                    sched.queue.clear()
                    break
                self._shed_deadlines(sched)
                self._bound_queue(sched)
                self._apply_pressure(sched, steps)
                self._apply_kv_budget(sched)
                self._priority_preempt(sched)
            # "overlapped" = admitted while the batch was already live at
            # the start of this boundary (cold-start fills don't count)
            batch_live = sched.occupied()
            if self.prefill_chunk:
                while True:
                    with spans.span("admit"):
                        n_adm, n_ovl, fin = self._admission_phase(
                            params, sched, steps, batch_live)
                    admissions += n_adm
                    overlapped += n_ovl
                    done.extend(fin)
                    done.extend(self._advance_chunk_lane(params, sched))
                    # the one-chunk-per-boundary throttle exists to bound
                    # the stall inflicted on LIVE decoders; with none live
                    # there is nothing to protect — keep chunking so a
                    # cold start does not serialize admission
                    if sched.decode_active().any() or not sched.prefill_fifo:
                        break
            else:
                with spans.span("admit"):
                    n_adm, n_ovl, fin = self._admission_phase(
                        params, sched, steps, batch_live)
                admissions += n_adm
                overlapped += n_ovl
                done.extend(fin)
            self._observe_tiers(sched)
            if self.strict_invariants:
                self._assert_invariants(sched)
            active = sched.decode_active()
            if not active.any():
                steps += 1                       # idle/prefill-only boundary
                continue
            done.extend(self._decode_round(params, sched, active, s_max))
            self._observe_tiers(sched)
            spans.close_boundary()
            steps += T
        self._caches = ex.caches
        return self._stats(done, steps, admissions, overlapped)

    # -- pressure / SLO policies ---------------------------------------
    def _shed_deadlines(self, sched: SlotScheduler):
        """A queued request whose TTFT deadline already expired can only
        miss — shed it NOW as deadline_missed (terminal, structured)
        instead of wasting a slot on it. Preempted requests already
        produced their first token and are never TTFT-shed."""
        now = time.monotonic()
        for r in list(sched.queue):
            if r.ttft_deadline_ms > 0 and not r.generated\
                    and (now - r.t_enqueue) * 1e3 > r.ttft_deadline_ms:
                sched.queue.remove(r)
                self._miss_deadline(
                    r, f"ttft_deadline_ms={r.ttft_deadline_ms:g} expired "
                       "in queue")

    def _bound_queue(self, sched: SlotScheduler):
        """Bounded-queue backpressure: shed the lowest-priority (then most
        recently enqueued) request while the queue exceeds ``max_queue``.
        Preempted requests (holding swapped-out KV and emitted tokens) are
        shed only when nothing else is left."""
        if not self.max_queue:
            return
        while len(sched.queue) > self.max_queue:
            pool = [r for r in sched.queue if r.swap is None]\
                or list(sched.queue)
            v = min(pool, key=lambda r: (r.priority, -r.t_enqueue, -r.rid))
            sched.queue.remove(v)
            self._reject(v, f"queue_full (max_queue={self.max_queue})")

    def _pick_victim(self, sched: SlotScheduler) -> Optional[int]:
        """Lowest-priority decoding slot; most recently admitted within a
        priority class (least sunk work — its wait already counted and it
        re-admits first among equals)."""
        victims = sched.decode_slots()
        if not victims:
            return None
        return min(victims, key=lambda i: (sched.req[i].priority,
                                           -sched.req[i].t_admitted))

    def _apply_pressure(self, sched: SlotScheduler, steps: int):
        """Artificial KV pressure from the fault injector: ``slots_held``
        slots are withheld this boundary — preempt decoding victims until
        the occupancy fits the reduced capacity, and hold admissions to the
        same cap (``_slot_cap``) so the boundary doesn't immediately
        restore what it just swapped out."""
        self._slot_cap = self.slots
        inj = self.fault_injector
        if inj is None or not self.preemptible:
            return
        held_fn = getattr(inj, "slots_held", None)
        if held_fn is None:
            return
        cap = max(0, self.slots - int(held_fn(steps)))
        self._slot_cap = cap
        for _ in range(self.slots):
            busy = sum(1 for p in sched.phase if p != sched.FREE)
            if busy <= cap:
                break
            v = self._pick_victim(sched)
            if v is None or not self._preempt_slot(sched, v):
                break

    def _apply_kv_budget(self, sched: SlotScheduler):
        """Real (not injected) KV pressure: while the arbiter's
        occupancy-priced live bytes exceed ``kv_budget_bytes``, preempt the
        usual lowest-priority victim; if preemption cannot get under the
        budget (or the engine is not preemptible), hold admissions this
        boundary instead — over-budget occupancy must never grow."""
        arb = self._arbiter
        if arb is None or not arb.budget:
            return
        self._observe_tiers(sched)
        while self.preemptible and arb.over_budget():
            v = self._pick_victim(sched)
            if v is None or not self._preempt_slot(sched, v):
                break
        if arb.over_budget():
            busy = sum(1 for p in sched.phase if p != sched.FREE)
            self._slot_cap = min(self._slot_cap, busy)

    def _observe_tiers(self, sched: SlotScheduler):
        """Sync the arbiter's per-slot cursor view at a host boundary: live
        decoders report their cursor (demotions count off the cold-boundary
        watermark), freed slots leave the live view. Pure host arithmetic —
        no device traffic."""
        arb = self._arbiter
        if arb is None:
            return
        for i in range(sched.n):
            if sched.phase[i] == sched.DECODE:
                arb.observe(i, int(sched.positions[i]))
            elif sched.phase[i] == sched.FREE:
                arb.release(i)

    def _priority_preempt(self, sched: SlotScheduler):
        """Priority lane: while the queue's best request outranks the
        lowest-priority decoding slot and no usable slot is free, swap the
        victim out (a block boundary is the ONLY preemption point — KV is
        consistent there, mid-block it is not host-visible)."""
        if not self.preemptible:
            return
        for _ in range(self.slots):
            if not sched.queue or sched.usable_free() is not None:
                break
            head = sched.top_priority()
            v = self._pick_victim(sched)
            if v is None or sched.req[v].priority >= head:
                break
            if not self._preempt_slot(sched, v):
                break

    def _preempt_slot(self, sched: SlotScheduler, slot: int) -> bool:
        """Token-exact swap-out of one decoding slot: export the stored
        bytes (read-only program — a failed dispatch leaves the victim
        decoding), host the image + cursor triple on the request, free the
        slot and requeue. False if the swap-out dispatch failed."""
        ex = self._ex
        r = sched.req[slot]
        try:
            with self.spans.span("swap_out", rid=r.rid, slot=slot):
                saved = self._dispatch(ex.program_prefix + "swap_out",
                                       ex.swap_out, slot)
                saved = tuple(None if a is None else np.asarray(a)
                              for a in saved)
        except DispatchFailure:
            return False                 # victim keeps its slot
        r.swap = SwapState(saved=saved,
                           kv_len=int(sched.positions[slot]),
                           last_tok=int(sched.last_tok[slot]),
                           remaining=int(sched.remaining[slot]))
        r.preemptions += 1
        self._preemptions += 1
        sched.preempt(slot)
        if self._arbiter is not None:
            self._arbiter.release(slot)
        return True

    def _restore(self, params, sched: SlotScheduler, slot: int,
                 r: Request) -> bool:
        """Swap a preempted request back in: masked true-length write of
        its host image, then resume decode with the saved cursor triple —
        byte-identical to never having been preempted."""
        ex = self._ex
        st = r.swap
        try:
            with self.spans.span("swap_in", rid=r.rid, slot=slot):
                self._dispatch(ex.program_prefix + "swap_in", ex.swap_in,
                               st.saved, slot, st.kv_len)
        except DispatchFailure as e:
            # the restore never touched the device (DispatchError fires
            # pre-call): the slot stays clean and FREE; the request is
            # demoted to a structured rejection
            self._reject(r, f"dispatch_failed:{e.name}")
            return False
        r.swap = None
        sched.resume_decode(slot, r, st)
        if self._arbiter is not None:
            # the restored prefix's demotions were counted pre-preemption —
            # seed the watermark so nothing is recounted
            self._arbiter.seed(slot, st.kv_len)
        self._restores += 1
        return True

    # -- admission ------------------------------------------------------
    def _admission_phase(self, params, sched: SlotScheduler, steps: int,
                         batch_live: bool):
        """Drain the queue into usable free slots in priority order. A
        preempted request re-enters DECODE directly through the swap-in
        program (no prefill — its KV and cursors are the saved ones); a
        fresh request enters the chunk lane (PREFILL) or admits
        monolithically. Returns (fresh admissions, overlapped, finished)."""
        admissions = overlapped = 0
        finished: List[Request] = []
        while True:
            busy = sum(1 for p in sched.phase if p != sched.FREE)
            if busy >= self._slot_cap:
                break                    # injected KV pressure holds slots
            slot = sched.usable_free()
            if slot is None:
                break
            r = sched.pop_queue()
            if r is None:
                break
            if r.swap is not None:
                self._restore(params, sched, slot, r)
                continue
            admissions += 1
            if batch_live:
                overlapped += 1
            if self.prefill_chunk:
                sched.begin_prefill(slot, r, steps)
            else:
                finished.extend(self._admit_one_monolithic(
                    params, sched, slot, r, steps))
        return admissions, overlapped, finished

    def _admit_one_monolithic(self, params, sched: SlotScheduler, slot: int,
                              r: Request, steps: int) -> List[Request]:
        """Full-width batch-1 prefill + slot write (the pre-chunking
        admission path, kept as the measured baseline). Prompts are
        zero-padded up to ``prompt_len`` — never truncated (submit rejects
        longer) — and the cursor starts at the padded width (the padding IS
        attended; the chunked lane is the length-true path). A one-token
        request (instant EOS / budget 1) finishes AT admission and frees
        the slot for the caller's loop to reuse this same boundary."""
        ex = self._ex
        r.t_admitted = time.monotonic()
        r.admit_step = steps
        r.status = "active"
        sched.req[slot] = r
        try:
            with self.spans.span("prefill", rid=r.rid, slot=slot) as sp:
                r.t_first_chunk = sp.t0
                first = self._dispatch(
                    ex.program_prefix + "admit", ex.admit_full, params,
                    pad_row(r.prompt, self.prompt_len), slot)
                first.block_until_ready()
        except DispatchFailure as e:
            self._demote_admission(sched, slot, r, e)
            return []
        now = time.monotonic()
        r.t_first_token = now
        r.note_emit(now)
        self._emit_token(r, np.asarray(first)[0])
        if r.done:
            self._finish(r, now)
            sched.req[slot] = None
            # the admit DID write its prompt KV — zero it like any other
            # retirement so dumps stay clean
            self._safe_reset(sched, slot)
            return [r]
        sched.start_decode(slot, self.prompt_len, r.generated[-1])
        return []

    def _demote_admission(self, sched: SlotScheduler, slot: int, r: Request,
                          exc: DispatchFailure):
        """An admission dispatch exhausted its retries: the slot's cache
        bytes are suspect (the prompt may be partially written), so the
        request demotes to a structured rejection and the slot is
        quarantined — one poisoned request costs one slot, not the
        engine."""
        self._reject(r, f"dispatch_failed:{exc.name}")
        sched.req[slot] = None
        sched.phase[slot] = sched.FREE
        if slot in sched.prefill_fifo:
            sched.prefill_fifo.remove(slot)
        self._quarantine_slot(sched, slot)

    def _safe_reset(self, sched: SlotScheduler, slot: int):
        """Debug slot zeroing, hardened: a reset that keeps failing
        quarantines the slot (its bytes are unknown) instead of killing
        the serve."""
        if not self._ex.has_reset:
            return
        try:
            self._dispatch("serve_reset", self._ex.reset, slot)
        except DispatchFailure:
            self._quarantine_slot(sched, slot)

    def _assert_invariants(self, sched: SlotScheduler):
        bad = sched.invariant_violations()
        for i in range(sched.n):
            r = sched.req[i]
            if r is None or sched.phase[i] != sched.DECODE:
                continue
            wm = self._cursor_watermark.get(r.rid, -1)
            pos = int(sched.positions[i])
            if pos < wm:
                bad.append(f"rid {r.rid}: cursor moved backwards "
                           f"{wm} -> {pos}")
            self._cursor_watermark[r.rid] = pos
        if bad:
            raise AssertionError("scheduler invariant violation(s): "
                                 + "; ".join(bad))

    # -- admission: chunked-prefill lane -------------------------------
    def _advance_chunk_lane(self, params, sched: SlotScheduler):
        """Run AT MOST ONE fixed-shape prefill chunk this boundary (the
        admitting slot at the head of the FIFO). In-flight decoders stall
        for one chunk, not one prompt; the final chunk's logits are the
        request's first token and flip the slot to the decode phase with
        its cursor at the TRUE prompt length."""
        ex = self._ex
        job = sched.next_chunk(self.prefill_chunk, self._kv_extent)
        if job is None:
            return []
        slot, r, start, n_valid = job
        row = pad_row(r.prompt[start:start + n_valid], self.prefill_chunk)
        try:
            with self.spans.span("chunk_dispatch", rid=r.rid,
                                 slot=slot) as sp:
                if not r.t_first_chunk:
                    r.t_first_chunk = sp.t0
                out = self._dispatch(ex.program_prefix + "prefill_chunk",
                                     ex.run_chunk, params, row, slot, start,
                                     n_valid)
        except DispatchFailure as e:
            # the slot may hold a partially-written prompt — demote the
            # request, quarantine the slot (drops it from the FIFO too)
            self._demote_admission(sched, slot, r, e)
            return []
        with self.spans.span("chunk_wait", rid=r.rid, slot=slot):
            # blocks: chunk wall-time
            first, *counts = (np.asarray(a) for a in out)
        now = time.monotonic()
        self._prefill_chunks += 1
        if counts:
            self._count_picks("prefill", counts[0])
        finished: List[Request] = []
        if sched.chunk_done(slot, start, n_valid):
            r.t_first_token = now
            r.note_emit(now)
            self._emit_token(r, first[0])
            if r.done:
                self._finish(r, now)
                finished.append(r)
                sched.retire(slot)
                self._safe_reset(sched, slot)
            else:
                sched.start_decode(slot, len(r.prompt), r.generated[-1])
        return finished

    # -- decode round ---------------------------------------------------
    def _demote_decode(self, sched: SlotScheduler, finished: List[Request],
                       exc: DispatchFailure) -> np.ndarray:
        """A decode dispatch exhausted its retries. The fault is the
        DISPATCH, not an identifiable request — demote the lowest-priority
        decoding victim (least lost work among the suspects), quarantine
        its slot, and hand back the shrunken active mask so the caller can
        retry the round for the survivors. Survivor KV is intact: the
        failed dispatch never touched its (donated) operands."""
        v = self._pick_victim(sched)
        if v is not None:
            self._reject(sched.req[v], f"dispatch_failed:{exc.name}")
            sched.retire(v)
            self._quarantine_slot(sched, v)
        return sched.decode_active()

    def _decode_round(self, params, sched: SlotScheduler, active, s_max):
        """One decode dispatch + ONE counted host sync: a single slotted
        step (T == 1) or a T-micro-step block with on-device halting. A
        dispatch that exhausts its retry budget sheds one victim and
        retries for the survivors — a poisoned round degrades to one
        structured rejection, never a hung engine."""
        T = self.block_size
        ex = self._ex
        spans = self.spans
        finished: List[Request] = []
        self._sample_occupancy(sched)
        if ex.overlap > 1:
            # scheduler-view micro-batch occupancy (single source of truth
            # with the layer loop's row split: micro_batch_slices) — a
            # fully-idle micro-batch still dispatches, so this measures
            # how much of the pipelined work carried live slots
            for _slots, act in sched.micro_batch_view(ex.overlap, active):
                self._micro_batches_total += 1
                self._micro_batches_live += bool(act.any())
        if T == 1:
            self._sample_kv_read(sched, active, 0)
            while True:
                try:
                    with spans.span("decode_dispatch"):
                        out = self._dispatch(
                            ex.program_prefix + "decode", ex.decode_step,
                            params, sched.last_tok, sched.positions, active)
                except DispatchFailure as e:
                    active = self._demote_decode(sched, finished, e)
                    if not active.any():
                        return finished
                    continue
                break
            with spans.span("decode_wait"):
                out = self._host_sync(*out)
            with spans.span("unpack"):
                n_tok = self._unpack_step(sched, active, finished, *out)
        else:
            while True:
                # length-aware bucket: smallest compiled extent covering
                # every live cursor for the whole block (short prompts
                # start low); recomputed if a shed victim shrank the mask
                if len(ex.buckets) > 1:
                    needed = int(sched.positions[active].max()) + T
                    sb = bucket_for(min(needed, s_max), ex.buckets)
                else:
                    sb = ex.buckets[0]
                self._sample_kv_read(sched, active, sb)
                try:
                    with spans.span("decode_dispatch"):
                        out = self._dispatch(
                            ex.program_prefix + "decode_block",
                            ex.decode_block, params, sb, sched.last_tok,
                            sched.positions, active, sched.remaining,
                            sched.eos)
                except DispatchFailure as e:
                    active = self._demote_decode(sched, finished, e)
                    if not active.any():
                        return finished
                    continue
                break
            with spans.span("decode_wait"):
                out = self._host_sync(*out)
            with spans.span("unpack"):
                n_tok = self._unpack_block(sched, finished, *out)
        self._decode_tokens += n_tok
        self._block_tokens.append(n_tok)
        self._macro_steps += 1
        return finished

    def _unpack_step(self, sched: SlotScheduler, active, finished,
                     nxt, new_pos) -> int:
        """Emit one synced slotted step's tokens, retire what finished;
        returns the tokens decoded."""
        sched.positions = new_pos.copy()
        sched.last_tok = nxt.copy()
        now = time.monotonic()
        for i, r in enumerate(sched.req):
            if r is None or sched.phase[i] != sched.DECODE:
                continue
            self._emit_token(r, nxt[i])
            # host-side budget mirror (the device manages it only in
            # block mode) — keeps SwapState and the invariant checker
            # uniform across T
            sched.remaining[i] -= 1
            r.note_emit(now)
            if r.done:
                self._finish(r, now)
                finished.append(r)
                sched.retire(i)              # freed → next boundary
                self._safe_reset(sched, i)
        return int(active.sum())

    def _unpack_block(self, sched: SlotScheduler, finished, toks, emitted,
                      last_d, pos_d, act_np, rem_d, *counts) -> int:
        """Emit one synced block's tokens, retire the slots the device
        halted; returns the tokens decoded. ``counts``: the block's picks
        per layer and held expert, where the model holds experts."""
        T = self.block_size
        if counts:
            self._count_picks("decode", counts[0])
            self._decode_micro_steps += int(emitted.any(axis=1).sum())
        sched.last_tok = last_d.copy()
        sched.positions = pos_d.copy()
        sched.remaining = rem_d.copy()
        now = time.monotonic()
        for i, r in enumerate(sched.req):
            if r is None or sched.phase[i] != sched.DECODE:
                continue
            emitted_any = False
            for t in range(T):
                if emitted[t, i]:
                    self._emit_token(r, toks[t, i])
                    emitted_any = True
            if emitted_any:
                r.note_emit(now)
            if not act_np[i]:                # budget/EOS halt on device
                self._finish(r, now)
                finished.append(r)
                sched.retire(i)              # freed → next boundary
                self._safe_reset(sched, i)
        return int(emitted.sum())

    def _count_picks(self, phase: str, picks: np.ndarray):
        """Add one program's picks (layers, held experts) to the run's."""
        have = self._expert_picks.get(phase)
        self._expert_picks[phase] = picks.astype(np.int64) if have is None \
            else have + picks

    def _sample_occupancy(self, sched: SlotScheduler):
        """Counters at a decode dispatch: the chunk lane's depth and, for
        KV-cache families, the KV positions written in occupied slots (a
        PREFILL slot its prompt tokens so far, a DECODE slot its cursor)."""
        self.spans.sample("lane_depth", len(sched.prefill_fifo))
        if self._kv_extent is None:
            return
        used = 0
        for i, ph in enumerate(sched.phase):
            if ph == sched.PREFILL:
                used += sched.filled[i]
            elif ph == sched.DECODE:
                used += int(sched.positions[i])
        self.spans.sample("kv_in_use", used)

    def _sample_kv_read(self, sched: SlotScheduler, active, bucket: int):
        """Counter at a decode dispatch: the KV positions its first
        micro-step reads, summed over rows and averaged over layers
        (``ModelAPI.decode_kv_read``); the full read extent of every slot
        where the family or the W/A backend reads it whole."""
        if self._kv_extent is None:
            return
        reads = self.api.decode_kv_read if self.backend == "colocated" \
            else None
        if reads is None:
            n = self.slots * (bucket or self._kv_extent)
        else:
            n = reads(self._caches_aval, self.ctx, sched.positions, active,
                      bucket, self.a_shards)
        self.spans.sample("kv_read", n)

    # ------------------------------------------------------------------
    def _run_drain(self, params, requests, max_steps):
        """Legacy baseline: prefill only when the WHOLE batch has drained —
        one long request starves every queued request (kept for comparison
        and for families without slotted support)."""
        ex = self._ex
        pending = sorted(requests, key=lambda r: r.arrival_step)
        active_req: List[Optional[Request]] = [None] * self.slots
        caches = None
        last = None
        done: List[Request] = []
        steps = admissions = 0
        spans = self.spans
        while pending or self.queue or any(r is not None for r in active_req):
            if steps >= max_steps:
                break
            spans.open_boundary()
            while pending and pending[0].arrival_step <= steps:
                r = pending.pop(0)            # validated by run()
                if not r.t_enqueue:           # keep a pre-run submit() stamp
                    r.t_enqueue = time.monotonic()
                self.queue.append(r)
            if caches is None:
                toks = np.zeros((self.slots, self.prompt_len), np.int32)
                with spans.span("admit"):
                    for i in range(self.slots):
                        if active_req[i] is None and self.queue:
                            r = self.queue.pop(0)
                            r.t_admitted = time.monotonic()
                            r.admit_step = steps
                            active_req[i] = r
                            admissions += 1
                        if active_req[i] is not None:
                            toks[i] = pad_row(active_req[i].prompt,
                                              self.prompt_len)
                if not any(r is not None for r in active_req):
                    steps += 1                   # idle tick: await arrivals
                    continue
                with spans.span("prefill") as sp:
                    caches, first = ex.drain_prefill(params, toks)
                    first.block_until_ready()
                now = time.monotonic()
                first = np.asarray(first)
                for i, r in enumerate(active_req):
                    if r is not None and not r.generated:
                        r.t_first_chunk = sp.t0
                        r.t_first_token = now
                        r.note_emit(now)
                        self._emit_token(r, first[i])
                        if r.done:
                            self._finish(r, now)
                last = jnp.asarray(first.astype(np.int32))
            with spans.span("decode_dispatch"):
                caches, nxt = ex.drain_decode(params, caches, last)
            with spans.span("decode_wait"):
                nxt_np = self._host_sync(nxt)
            self._macro_steps += 1
            last = nxt
            steps += 1
            with spans.span("unpack"):
                now = time.monotonic()
                n_tok = 0
                for i, r in enumerate(active_req):
                    if r is None or r.done:
                        continue
                    self._emit_token(r, nxt_np[i])
                    r.note_emit(now)
                    n_tok += 1
                    if r.done:
                        self._finish(r, now)
                self._decode_tokens += n_tok
                self._block_tokens.append(n_tok)
                for i, r in enumerate(active_req):
                    if r is not None and r.done:
                        done.append(r)
                        active_req[i] = None
            if all(r is None for r in active_req):
                caches = None                    # drained → allow re-prefill
            spans.close_boundary()
        return self._stats(done, steps, admissions, 0)

    # ------------------------------------------------------------------
    def _stats(self, done, steps, admissions, overlapped) -> Dict[str, Any]:
        """The run's counters and latencies. Every time here comes from
        the span table: a decode round is its dispatch plus its sync (per
        token: over the block size in the continuous scheduler), prefill
        time is every chunk dispatch and sync plus monolithic and drain
        prefill, swap time the swap-out and swap-in spans."""
        spans = self.spans
        per_tok = self.block_size if self.mode == "continuous" else 1
        rounds = [(d + w) / per_tok for d, w in zip(
            spans.seconds("decode_dispatch"), spans.seconds("decode_wait"))]
        tp = np.array(rounds[1:] or [0.0])
        decode_time = spans.total("decode_dispatch", "decode_wait")
        per_req = [r.metrics() for r in sorted(done, key=lambda r: r.rid)]
        ttfts = np.array([m["ttft_ms"] for m in per_req] or [0.0])
        qd = np.array([m["queue_delay_ms"] for m in per_req] or [0.0])
        gaps = np.array([m["max_gap_ms"] for m in per_req] or [0.0])
        blk = np.array(self._block_tokens or [0.0])
        # decode-token throughput: decode-PRODUCED tokens over decode
        # wall-time — prefill AND chunk-prefill wall-time are excluded from
        # both sides (their first tokens are not in the numerator, their
        # stalls not in the denominator)
        n_dec = self._decode_tokens
        out = {
            "mode": self.mode,
            "backend": self.backend,
            "block_size": self.block_size,
            "a_shards": self.a_shards,
            "prefill_mode": ("chunked" if self.prefill_chunk
                             else "monolithic"),
            "prefill_chunk": self.prefill_chunk,
            "completed": len(done),
            "decode_steps": steps,
            "macro_steps": self._macro_steps,
            "admissions": admissions,
            "overlapped_admissions": overlapped,
            "tpot_mean_ms": float(tp.mean() * 1e3),
            "tpot_p50_ms": float(np.percentile(tp, 50) * 1e3) if len(tp) else 0.0,
            "tpot_p99_ms": float(np.percentile(tp, 99) * 1e3) if len(tp) else 0.0,
            "ttft_mean_ms": float(ttfts.mean()),
            "ttft_p99_ms": float(np.percentile(ttfts, 99)),
            "queue_delay_mean_ms": float(qd.mean()),
            "max_inter_token_gap_ms": float(gaps.max()),
            "decode_tokens": n_dec,
            "throughput_tok_s": float(n_dec / max(decode_time, 1e-9)),
            "prefill_time_ms": float(spans.total(
                "chunk_dispatch", "chunk_wait", "prefill") * 1e3),
            "prefill_chunks": self._prefill_chunks,
            "host_syncs": self.host_syncs,
            "syncs_per_token": float(self.host_syncs / max(n_dec, 1)),
            "tokens_per_macro_step_mean": float(blk.mean()),
            "per_request": per_req,
            "runtime": self.rt.stats(),
            # pressure / robustness counters (DESIGN.md §7 failure model):
            # every submitted request is terminally accounted in exactly
            # one of completed / rejected / deadline_missed
            "preemptions": self._preemptions,
            "restores": self._restores,
            "rejections": len(self._rejected),
            "deadline_misses": len(self._deadline_missed),
            "retries": self._retries,
            "watchdog_timeouts": self._watchdog_timeouts,
            "quarantined_slots": sorted(self._quarantined),
            "swap_time_ms": float(spans.total("swap_out", "swap_in") * 1e3),
            "rejected": [
                {"rid": r.rid, "status": r.status, "priority": r.priority,
                 "reason": r.reject_reason}
                for r in sorted(self._rejected + self._deadline_missed,
                                key=lambda r: r.rid)],
            # per boundary phase: n and ms (nested spans count in both)
            "spans": spans.summary(),
            # host time per decode boundary: wall less the device waits
            "boundary": spans.boundary_summary(),
        }
        in_use = spans.mean("kv_in_use")
        if in_use is not None:
            # KV positions written in occupied slots, per decode dispatch,
            # against every slot's full extent
            reserved = self.slots * self._kv_extent
            # and the positions the decode reads, over the same
            out["kv"] = {"reserved_tokens": reserved,
                         "in_use_share_mean": in_use / reserved,
                         "read_share_mean": spans.mean("kv_read") / reserved,
                         "lane_depth_mean": spans.mean("lane_depth")}
        if self._expert_picks:
            # token-expert picks of the held experts, per layer and expert
            picks = self._expert_picks
            out["moe"] = {
                "decode_picks": picks.get("decode", np.zeros(0)).tolist(),
                "prefill_picks": picks.get("prefill", np.zeros(0)).tolist(),
                "picks": int(sum(int(p.sum()) for p in picks.values())),
                "decode_micro_steps": self._decode_micro_steps}
        if self._arbiter is not None:
            # tiered-KV occupancy and placement policy: tier splits,
            # demotions counted off cursor watermarks, live/peak bytes and
            # the byte-budget verdict — stats() is the arbiter's output
            out["tiered"] = self._arbiter.stats()
        if self.backend == "wa" and self._ex is not None:
            # measured W↔A traffic — the paper's "only embeddings move"
            # claim as a number in every run's output — plus the
            # per-domain stall accounting of the overlap schedule
            out["wa"] = self._ex.routing_stats(n_dec)
            out["wa"].update(self._ex.overlap_stats(
                decode_time, self._macro_steps,
                self._micro_batches_live, self._micro_batches_total))
        return out
