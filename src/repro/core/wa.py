"""Weight–Attention (WA) disaggregated execution (paper §3.1 / §4.1).

The paper splits each transformer layer across two sockets: a *weight node*
(QKV proj + FFN, weights resident, no KV) and an *attention node* (owns KV
state, runs attention). Activations — "only embeddings" — hop W→A→W per
layer.

TPU instantiation, in two routing modes:

- ``routing="device_put"`` (eager, two SUBMESHES): carve the pod into a
  weight submesh and an attention submesh and move the per-layer activations
  between them with explicit ``jax.device_put`` — the honest JAX analogue of
  two pinned per-socket thread pools (on hardware the transfer lowers to
  ICI). Python-orchestrated per layer; used for the Fig 11 breakdown and the
  equivalence demos. A ``device_put`` across disjoint device sets cannot be
  staged into ONE compiled program, so this mode stays per-step/eager.

- ``routing="sharding"`` (AOT, one mesh): the serving path. The W and A
  domains become two *sharding regimes* over the single serving mesh — the W
  domain keeps the sub-operator rules (weights + per-head activations on the
  model axis), the A domain keeps the KV-sequence-sharded rules
  (``seq_sharded_kv``: the cache's positions live distributed, attention
  reductions are the LSE-merge collectives — the paper's "add attention
  nodes" axis). The W→A / A→W hops are ``with_sharding_constraint``
  boundaries inside the compiled program (``jax.device_put``-free inner
  loop), so ``StaticRuntime`` can AOT-compile whole macro-step blocks and
  prefill chunks around the routed layer loop — compiles == 1 across a
  staggered serve. With ``mesh=None`` (single-device dry-run) the
  constraints are no-ops and the math is the colocated math exactly.

The split is decided by ``core.residency.plan`` — WA separation is *optional*
and only pays under cache pressure (paper Fig 9: 1.00× at 3B, 1.16× at 70B);
``wa_plan`` encodes that policy.

This module provides:
  - ``split_mesh``        : carve (data) rows into weight/attention groups,
  - ``wa_plan``           : profitability policy from the residency report,
  - ``WADisaggregated``   : the W/A decode engine — eager per-step routing
                            (device_put mode) plus the AOT serving programs
                            ``decode_step_slotted`` / ``decode_block`` /
                            ``prefill_chunk`` (sharding mode) consumed by
                            ``runtime.serving.WABackend``,
  - ``routing_bytes``     : per-token W↔A traffic for the roofline
                            collective term (2 hops × B × d_model / layer).

Per-slot cursors, KV buckets and halt masks are all A-SIDE state: admission
(`prefill_chunk` KV writes), the length-aware bucket walk
(``layer_read_bucket``) and retirement masks live with the KV; the W side
only ever sees routed activations and per-row RoPE phases (DESIGN.md §3).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.configs.shapes import ShapeConfig
from repro.core.pipeline import skewed_schedule
from repro.core.residency import plan as residency_plan
from repro.models import common
from repro.models.attention import chunk_attention, chunk_attention_tiered,\
    decode_attention, decode_attention_split, qkv_project
from repro.models.registry import make_decode_block
from repro.models.sharding import ShardingCtx, seq_sharded_kv, sub_operator
from repro.kv.cache import (KVCache, batch_valid_mask, chunk_hot_image,
                            cold_boundary, export_slot_kv, import_slot_kv,
                            layer_append, layer_append_slotted,
                            layer_append_tiered, layer_read,
                            layer_read_bucket, layer_read_shards,
                            layer_read_slot, layer_read_slot_cold,
                            layer_read_tiered, layer_read_tiered_shards,
                            layer_write_chunk, layer_write_chunk_tiered,
                            slot_valid_mask)

# canonical order of a WA program's per-layer cache stacks; scale and hot
# entries are None for flat/unquantized caches and flow through untouched
_STACK_FIELDS = ("k", "v", "k_scale", "v_scale", "hot_k", "hot_v")


# ---------------------------------------------------------------------------
# Mesh split + policy
# ---------------------------------------------------------------------------

def split_mesh(mesh: Mesh, weight_rows: int) -> Tuple[Mesh, Mesh]:
    """Split the data axis: first ``weight_rows`` rows → weight submesh,
    rest → attention submesh (paper: CPU1=weight socket, CPU2=attn socket)."""
    devs = mesh.devices
    assert devs.ndim == 2, "split on the single-pod (data, model) mesh"
    w = Mesh(devs[:weight_rows], mesh.axis_names)
    a = Mesh(devs[weight_rows:], mesh.axis_names)
    return w, a


@dataclass(frozen=True)
class WAPlan:
    separate: bool
    weight_rows: int
    attention_rows: int
    reason: str


def wa_plan(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh) -> WAPlan:
    n_rows = mesh.devices.shape[0]
    n_chips = int(np.prod(mesh.devices.shape))
    if cfg.family == "ssm":
        return WAPlan(False, n_rows, 0,
                      "attention-free: no growing KV to decouple "
                      "(DESIGN.md §6 — WA inapplicable)")
    rep = residency_plan(cfg, shape, n_chips)
    if not rep.wa_profitable:
        return WAPlan(False, n_rows, 0,
                      "co-located hot set within budget; separation would "
                      "waste sockets (paper Fig 9 small-model regime)")
    half = n_rows // 2
    return WAPlan(True, half, n_rows - half, rep.notes)


def routing_bytes(cfg: ModelConfig, batch: int, bytes_per_el: int = 2) -> int:
    """Per-decoded-token W↔A activation traffic: 2 hops per layer of the
    (B, d_model) embedding — the paper's 'only embeddings move'. Invariant
    under ``overlap``: depth D routes D× as many hops each carrying B/D
    rows, so the analytic total is the same at every depth."""
    return 2 * cfg.n_layers * batch * cfg.d_model * bytes_per_el


def micro_batch_slices(batch: int, depth: int) -> Tuple[slice, ...]:
    """Contiguous per-micro-batch row slices for overlap depth ``depth`` —
    the SINGLE source of truth for per-micro-batch slot membership, shared
    by the pipelined layer loop below and the ``SlotScheduler``'s occupancy
    view (``runtime/serving.py``), so the overlap path cannot drift from
    the scheduler's idea of which slots ride which micro-batch."""
    if depth < 1:
        raise ValueError(f"overlap depth must be >= 1, got {depth}")
    if batch % depth:
        raise ValueError(
            f"batch {batch} does not divide into overlap depth {depth} "
            "equal micro-batches (pick slots divisible by overlap)")
    m = batch // depth
    return tuple(slice(i * m, (i + 1) * m) for i in range(depth))


# ---------------------------------------------------------------------------
# Statically-identifiable hop markers
# ---------------------------------------------------------------------------
# The sharding-mode W↔A hops are plain with_sharding_constraint boundaries,
# which on reduced test configs can degrade to a replicated spec (e.g. 4
# heads on an 8-wide model axis) and become indistinguishable from any other
# annotation in the jaxpr. Wrapping each hop in a named inner jit gives the
# static verifier (repro.analysis.routing_check) a stable anchor: a ``jit``
# eqn whose name is WA_HOP_TO_A / WA_HOP_TO_W, regardless of how the spec
# degraded. Semantically identical to the bare constraint.

WA_HOP_TO_A = "wa_hop_to_a"
WA_HOP_TO_W = "wa_hop_to_w"


def _make_hop(tag: str):
    def hop(x, sharding):
        return jax.lax.with_sharding_constraint(x, sharding)
    hop.__name__ = tag
    return jax.jit(hop, static_argnums=(1,))


_hop_to_a = _make_hop(WA_HOP_TO_A)
_hop_to_w = _make_hop(WA_HOP_TO_W)


def _tagged_ann(hop, ctx: ShardingCtx, x, logical):
    """ctx.ann with the constraint routed through a named hop marker."""
    if ctx.mesh is None or ctx.mesh.empty:
        return x
    spec = ctx.spec(tuple(logical), x.shape)
    return hop(x, NamedSharding(ctx.mesh, spec))


# ---------------------------------------------------------------------------
# Disaggregated decode engine (dense family)
# ---------------------------------------------------------------------------

class WADisaggregated:
    """Weight-ops on the W domain, attention on the A domain, activations
    routed per layer.

    Layer split (paper Fig 5b):
        W: x → ln1 → QKV proj ───route q,k,v───→ A: append KV, attention
        W: o·Wo + residual + ln2 + FFN ←──route o──┘

    ``routing="device_put"``: W/A are disjoint submeshes (``plan`` required)
    and the hops are eager ``jax.device_put`` transfers — per-step only.
    ``routing="sharding"``: W/A are two sharding regimes over ONE mesh
    (``mesh`` may be None for the single-device dry-run) and the hops are
    ``with_sharding_constraint`` boundaries — jit-safe, so
    ``decode_block``/``prefill_chunk`` AOT-compile (the serving backend).
    """

    def __init__(self, cfg: ModelConfig, mesh: Optional[Mesh],
                 plan: Optional[WAPlan] = None, *,
                 routing: str = "device_put", a_shards: int = 1,
                 overlap: int = 1):
        if routing not in ("device_put", "sharding"):
            raise ValueError(routing)
        if a_shards < 1:
            raise ValueError(f"a_shards must be >= 1, got {a_shards}")
        if a_shards > 1 and routing != "sharding":
            raise ValueError(
                "split-KV decode (a_shards > 1) is an AOT sharded read — "
                "build WADisaggregated(routing='sharding')")
        if overlap < 1:
            raise ValueError(f"overlap must be >= 1, got {overlap}")
        if overlap > 1 and routing != "sharding":
            raise ValueError(
                "sub-operator overlap (overlap > 1) software-pipelines the "
                "layer loop inside ONE compiled program — build "
                "WADisaggregated(routing='sharding')")
        self.cfg = cfg
        self.plan = plan
        self.routing = routing
        # a_shards > 1: split-KV flash decode — each slot's KV walk splits
        # into a_shards contiguous blocks along the sequence axis (the
        # "kv_shard" logical axis, mapped onto the A submesh), with the
        # LSE merge combining the per-shard partial softmax statistics
        self.a_shards = a_shards
        # overlap > 1: sub-operator pipelining — the slotted decode step
        # splits its batch into `overlap` micro-batches and runs the
        # skewed two-domain schedule (_layer_loop_pipelined) so W and A
        # are concurrently busy on DIFFERENT micro-batches. Depth 1 keeps
        # the sequential _layer_loop verbatim (today's exact programs).
        self.overlap = overlap
        if routing == "device_put":
            if plan is None:
                raise ValueError("device_put routing needs a WAPlan (submesh "
                                 "row split)")
            self.w_mesh, self.a_mesh = split_mesh(mesh, plan.weight_rows)
            self.w_ctx = ShardingCtx(self.w_mesh, sub_operator(False))
            self.a_ctx = ShardingCtx(self.a_mesh, sub_operator(False))
        else:
            # ONE mesh, two rule tables: W = sub-operator (weights/heads on
            # the model axis), A = KV-sequence-sharded (the cache's length
            # axis owns the model axis — "add attention nodes"). mesh=None →
            # every constraint is a no-op (single-device dry-run).
            self.w_ctx = ShardingCtx(mesh, sub_operator(False))
            self.a_ctx = ShardingCtx(mesh, seq_sharded_kv(sub_operator(False)))
        # macro-step block: the registry lift of the slotted WA step — the
        # same on-device halt masks / cursors every colocated family gets
        self.decode_block = make_decode_block(self._decode_slotted_api)

    def _require_aot(self, what: str):
        if self.routing != "sharding":
            raise ValueError(
                f"{what} must compile into ONE program; eager device_put "
                "routing cannot cross submeshes inside a jit trace — build "
                "WADisaggregated(routing='sharding') for the AOT path")

    # -- single layer pieces (weight side) ------------------------------
    def _w_qkv(self, lp, x, positions):
        """positions: (B,S) int32 — per-row RoPE phase (continuous batching
        admits rows at different depths, so the W side must rotate per-row)."""
        cfg, ctx = self.cfg, self.w_ctx
        h = common.apply_norm(cfg.norm, lp["ln1"], x, cfg.norm_eps)
        return qkv_project(lp["attn"], h, cfg, ctx, positions)

    def _w_post(self, lp, x, o):
        from repro.models.transformer import _mix_ffn
        cfg, ctx = self.cfg, self.w_ctx
        B, S = x.shape[0], x.shape[1]
        o = common.linear(lp["attn"]["wo"], o.reshape(B, S, -1))
        x = x + o
        h = common.apply_norm(cfg.norm, lp["ln2"], x, cfg.norm_eps)
        f, _ = _mix_ffn(lp, h, cfg, ctx, train=False)
        return x + f

    # -- attention side ---------------------------------------------------
    def _a_attend(self, kv_slices, q, k, v, pos, window=0):
        k_l, v_l, ks_l, vs_l = kv_slices[:4]
        k_l, v_l, ks_l, vs_l = layer_append(k_l, v_l, ks_l, vs_l,
                                            k[:, 0], v[:, 0], pos, window)
        kc, vc = layer_read(k_l, v_l, ks_l, vs_l, dtype=q.dtype)
        mask = slot_valid_mask(k_l.shape[2], window, pos)
        o = decode_attention(q[:, 0], kc, vc, mask, self.a_ctx)
        return (k_l, v_l, ks_l, vs_l) + tuple(kv_slices[4:]), o

    def _a_attend_slotted(self, kv_slices, q, k, v, positions, active,
                          window=0, kv_bucket=0):
        """Per-slot cursors live WITH the KV on the attention node — the
        weight node never tracks who occupies which slot (admission is an
        A-side state change, matching the paper's ownership split).
        ``kv_bucket`` > 0: the length-aware walk — read and attend only the
        first ``kv_bucket`` STORED positions (int8 caches dequantize just
        the bucket), exactly ``transformer.block_decode_slotted``'s slice.
        Tiered caches (6-entry ``kv_slices``) stage the append into both
        tiers and read the hot/cold-resolved image — the demotion boundary
        lives entirely in this A-side read (DESIGN.md §7)."""
        cfg = self.cfg
        k_l, v_l, ks_l, vs_l, hk_l, hv_l = kv_slices
        tiered = hk_l is not None
        if tiered:
            k_l, v_l, ks_l, vs_l, hk_l, hv_l = layer_append_tiered(
                k_l, v_l, ks_l, vs_l, hk_l, hv_l, k[:, 0], v[:, 0],
                positions, cfg.kv_cold_dtype, active)
            counts = positions + 1
        else:
            k_l, v_l, ks_l, vs_l = layer_append_slotted(
                k_l, v_l, ks_l, vs_l, k[:, 0], v[:, 0], positions, window,
                active)
        if window:
            kv_bucket = 0                   # ring order has no prefix to cut
        out = (k_l, v_l, ks_l, vs_l, hk_l, hv_l)
        if self.a_shards > 1 and not window:
            # split-KV flash decode: shard-major bucketed read (same stored
            # prefix, reshaped to a_shards contiguous blocks); the per-shard
            # partial softmax statistics reduce locally and ONE LSE merge
            # routes the combined output back toward W.
            # Pin the resident cache to the SAME kv_seq layout the chunk
            # program emits: GSPMD cannot back-propagate the shard-major
            # annotation through the reshape, and an unconstrained cache
            # input would compile replicated — mismatching the live buffers.
            ann = self.a_ctx.ann
            k_l = ann(k_l, "batch", "kv_heads", "kv_seq", "head_dim")
            v_l = ann(v_l, "batch", "kv_heads", "kv_seq", "head_dim")
            if ks_l is not None:
                ks_l = ann(ks_l, "batch", "kv_heads", "kv_seq", None)
                vs_l = ann(vs_l, "batch", "kv_heads", "kv_seq", None)
            if tiered:
                hk_l = ann(hk_l, "batch", "kv_heads", None, "head_dim")
                hv_l = ann(hv_l, "batch", "kv_heads", None, "head_dim")
                kc, vc = layer_read_tiered_shards(
                    k_l, v_l, ks_l, vs_l, hk_l, hv_l, counts, kv_bucket,
                    self.a_shards, cfg.hot_window, cfg.kv_cold_block,
                    cfg.kv_cold_dtype, dtype=q.dtype)
            else:
                kc, vc = layer_read_shards(k_l, v_l, ks_l, vs_l, kv_bucket,
                                           self.a_shards, dtype=q.dtype)
            mask = batch_valid_mask(kc.shape[2] * kc.shape[3], window,
                                    positions)
            o = decode_attention_split(q[:, 0], kc, vc, mask, self.a_ctx)
            return (k_l, v_l, ks_l, vs_l, hk_l, hv_l), o
        if tiered:
            kc, vc = layer_read_tiered(
                k_l, v_l, ks_l, vs_l, hk_l, hv_l, counts, kv_bucket,
                cfg.hot_window, cfg.kv_cold_block, cfg.kv_cold_dtype,
                dtype=q.dtype)
        else:
            kc, vc = layer_read_bucket(k_l, v_l, ks_l, vs_l, kv_bucket,
                                       dtype=q.dtype)
        mask = batch_valid_mask(kc.shape[2], window, positions)
        o = decode_attention(q[:, 0], kc, vc, mask, self.a_ctx)
        return out, o

    def _pin_cache_stacks(self, k_st, v_st, ks_st, vs_st,
                          hk_st=None, hv_st=None):
        """Pin the resident KV stacks to the A-domain layout at program
        ENTRY. GSPMD infers each program's cache placement independently —
        on a data-sharded mesh the chunk program used to compile its cache
        input batch-REPLICATED while the decode block compiled it
        batch-sharded, so the donated buffer resharded at every admission
        boundary (found by the repro.analysis residency pass; invisible on
        data=1 test meshes). The entry pin makes every WA program agree on
        the planned A-domain layout. Hot rings carry no kv_seq axis (the
        ring extent is H, not the shard-cut cache extent) — they pin
        batch/kv_heads only and replicate along the ring."""
        if self.routing != "sharding":
            return k_st, v_st, ks_st, vs_st, hk_st, hv_st
        ann = self.a_ctx.ann
        k_st = ann(k_st, None, "batch", "kv_heads", "kv_seq", "head_dim")
        v_st = ann(v_st, None, "batch", "kv_heads", "kv_seq", "head_dim")
        if ks_st is not None:
            ks_st = ann(ks_st, None, "batch", "kv_heads", "kv_seq", None)
            vs_st = ann(vs_st, None, "batch", "kv_heads", "kv_seq", None)
        if hk_st is not None:
            hk_st = ann(hk_st, None, "batch", "kv_heads", None, "head_dim")
            hv_st = ann(hv_st, None, "batch", "kv_heads", None, "head_dim")
        return k_st, v_st, ks_st, vs_st, hk_st, hv_st

    # -- preemption swap (A-domain slot state ops) -------------------------
    def swap_out_slot(self, cache: KVCache, slot):
        """Preemption export of one slot's stored KV ON the A domain: the
        resident stacks are pinned to the planned A layout first (same entry
        pin as every other WA cache program — the swap pair must not give
        GSPMD a program that disagrees on cache placement). The stored
        extent stays CONTIGUOUS under split-KV (a_shards > 1 is a read-time
        view, DESIGN.md §3), so the exported host buffer is shard-agnostic:
        it restores bit-identically under any shard width."""
        k, v, ks, vs, hk, hv = self._pin_cache_stacks(
            cache.k, cache.v, cache.k_scale, cache.v_scale,
            cache.hot_k, cache.hot_v)
        return export_slot_kv(
            cache._replace(k=k, v=v, k_scale=ks, v_scale=vs,
                           hot_k=hk, hot_v=hv), slot)

    def swap_in_slot(self, cache: KVCache, saved, slot, valid_len):
        """Preemption restore on the A domain: masked true-length write of
        an exported slot image (``import_slot_kv`` — the chunk lane's
        keep-past-valid semantics at full width), entry- and exit-pinned so
        the donated cache keeps the agreed A layout."""
        k, v, ks, vs, hk, hv = self._pin_cache_stacks(
            cache.k, cache.v, cache.k_scale, cache.v_scale,
            cache.hot_k, cache.hot_v)
        cache = import_slot_kv(
            cache._replace(k=k, v=v, k_scale=ks, v_scale=vs,
                           hot_k=hk, hot_v=hv), saved, slot, valid_len)
        k, v, ks, vs, hk, hv = self._pin_cache_stacks(
            cache.k, cache.v, cache.k_scale, cache.v_scale,
            cache.hot_k, cache.hot_v)
        return cache._replace(k=k, v=v, k_scale=ks, v_scale=vs,
                              hot_k=hk, hot_v=hv)

    # -- route helpers ------------------------------------------------------
    def _to_a(self, x):
        """W → A hop. Eager: a cross-submesh device_put (lowers to ICI).
        AOT: a sharding-constraint boundary — heads leave the W domain's
        model-axis shards and replicate onto the A domain, whose owned axis
        is the KV sequence ("only embeddings move", now inside the
        program)."""
        if self.routing == "device_put":
            return jax.device_put(x, NamedSharding(self.a_mesh,
                                                   P("data", None, None)))
        return _tagged_ann(_hop_to_a, self.a_ctx, x,
                           ("batch", "seq", "act_heads", "head_dim"))

    def _to_w(self, x):
        """A → W hop: the attention output re-shards onto the W domain's
        head axis before the output projection / FFN."""
        if self.routing == "device_put":
            return jax.device_put(x, NamedSharding(self.w_mesh,
                                                   P("data", None, None)))
        return _tagged_ann(_hop_to_w, self.w_ctx, x,
                           ("batch", "seq", "act_heads", "head_dim"))

    # -- decode step --------------------------------------------------------
    def _layer_loop(self, params, cache: KVCache, tokens, positions, attend):
        """Shared per-layer W→A→W routing. ``positions``: (B,1) per-row RoPE
        phase; ``attend(kv_slices, q, k, v)`` runs the A-side program and
        returns (updated slices, o). Returns (new k/v/scale stacks, logits)."""
        cfg = self.cfg
        x = common.embed(params["embed"], tokens[:, None], self.w_ctx)
        if cfg.pos == "learned":
            x = x + jnp.take(params["pos_embed"], positions[:, 0],
                             axis=0)[:, None].astype(x.dtype)
        stacks = list(self._pin_cache_stacks(
            cache.k, cache.v, cache.k_scale, cache.v_scale,
            cache.hot_k, cache.hot_v))
        for i in range(cfg.n_layers):
            lp = jax.tree.map(lambda a: a[i], params["blocks"])
            q, k, v = self._w_qkv(lp, x, positions)
            # W → A : route per-head activations (the "embeddings move" hop)
            q, k, v = self._to_a(q), self._to_a(k), self._to_a(v)
            kv_i = tuple(None if c is None else c[i] for c in stacks)
            kv_i, o = attend(kv_i, q, k, v)
            for n, piece in enumerate(kv_i):
                if piece is not None:
                    stacks[n] = stacks[n].at[i].set(piece)
            # A → W
            o = self._to_w(o[:, None])
            x = self._w_post(lp, x, o)
        x = common.apply_norm(cfg.norm, params["ln_f"], x, cfg.norm_eps)
        from repro.models.transformer import unembed_table
        logits = common.unembed_logits(unembed_table(params, cfg), x,
                                       self.w_ctx)
        return tuple(stacks), logits

    def _layer_loop_pipelined(self, params, cache: KVCache, tokens,
                              positions, attend):
        """Software-pipelined W→A→W layer loop (``overlap`` > 1, the
        paper's §3.2 sub-operator dependency relaxation applied to the WA
        boundary). The batch splits into ``overlap`` contiguous
        micro-batches; each runs the SAME chain of 2L+1 alternating ops
        (even = W: embed/QKV/FFN/unembed, odd = A: attention), skewed one
        tick per micro-batch (``core.pipeline.skewed_schedule``). At any
        tick the live micro-batches hold consecutive op indices — adjacent
        micro-batches always occupy OPPOSITE domains, so while A attends
        micro-batch m at layer l, W already runs QKV/FFN for micro-batch
        m+1 at the same layer, and m's layer l+1 W work starts the tick
        its A result lands. The routed q/k/v and attention outputs are
        held in per-micro-batch double buffers (``routed``/``backed``)
        whose producers and consumers sit one tick apart, so XLA's latency
        hiding can overlap the W-regime and A-regime collectives instead
        of serializing them at a per-layer barrier. The schedule is STATIC
        (python ints only): one compiled program per cell, same program
        names as depth 1.

        Token-exact by construction: every op is row-wise over the batch
        (per-slot KV, per-row cursors/masks), so splitting rows into
        micro-batches reorders no per-row reduction. ``attend(kv_slices,
        q, k, v, sl)`` must run the A-side program on micro-batch rows
        ``sl``. Returns (new k/v/scale stacks, logits) like
        ``_layer_loop``."""
        cfg, D = self.cfg, self.overlap
        L = cfg.n_layers
        from repro.models.transformer import unembed_table
        slices = micro_batch_slices(tokens.shape[0], D)
        stacks = self._pin_cache_stacks(
            cache.k, cache.v, cache.k_scale, cache.v_scale,
            cache.hot_k, cache.hot_v)
        lps = [jax.tree.map(lambda a, _i=i: a[_i], params["blocks"])
               for i in range(L)]
        xs = [None] * D          # per-micro-batch residual stream (W side)
        routed = [None] * D      # in-flight W→A (q,k,v) double buffers
        backed = [None] * D      # in-flight A→W attention-output buffers
        logits = [None] * D
        # per-(layer, micro-batch) updated KV pieces. The micro-batch
        # chains must stay INDEPENDENT dataflow: threading the stacks
        # through per-micro-batch scatter updates would version the whole
        # cache through every A op — a serial chain re-coupling the very
        # chains the schedule decoupled (and a full-stack copy per scatter
        # wherever XLA cannot prove slice disjointness). So all reads are
        # gathers from the ENTRY stacks (each micro-batch reads only its
        # own rows, no other micro-batch writes them — value-identical to
        # the sequential loop) and the updated stacks are assembled ONCE
        # at the end, concat over micro-batches, stack over layers.
        new_kv = [[None] * D for _ in range(L)]
        for _t, live in skewed_schedule(2 * L + 1, D):
            for m, op in live:
                sl = slices[m]
                j = op // 2
                if op % 2:
                    # -- A-domain op: attend layer j for micro-batch m ----
                    q, k, v = routed[m]
                    routed[m] = None
                    kv_i = tuple(None if c is None else c[j, sl]
                                 for c in stacks)
                    new_kv[j][m], o = attend(kv_i, q, k, v, sl)
                    # route toward W the tick it lands (A's send side)
                    backed[m] = self._to_w(o[:, None])
                    continue
                # -- W-domain op j: finish layer j-1, start layer j -------
                if j == 0:
                    x = common.embed(params["embed"], tokens[sl][:, None],
                                     self.w_ctx)
                    if cfg.pos == "learned":
                        x = x + jnp.take(params["pos_embed"],
                                         positions[sl, 0],
                                         axis=0)[:, None].astype(x.dtype)
                else:
                    o, backed[m] = backed[m], None
                    x = self._w_post(lps[j - 1], xs[m], o)
                if j < L:
                    q, k, v = self._w_qkv(lps[j], x, positions[sl])
                    routed[m] = (self._to_a(q), self._to_a(k), self._to_a(v))
                    xs[m] = x
                else:
                    xs[m] = None
                    x = common.apply_norm(cfg.norm, params["ln_f"], x,
                                          cfg.norm_eps)
                    logits[m] = common.unembed_logits(
                        unembed_table(params, cfg), x, self.w_ctx)

        def assemble(idx):
            if new_kv[0][0][idx] is None:
                return None
            return jnp.stack([jnp.concatenate([new_kv[j][m][idx]
                                               for m in range(D)], axis=0)
                              for j in range(L)])

        # re-pin: the assembled stacks are NEW buffers and must land on the
        # same A-domain layout the entry pin promised the donation chain
        out = self._pin_cache_stacks(*[assemble(i)
                                       for i in range(len(_STACK_FIELDS))])
        return out, jnp.concatenate(logits, axis=0)

    def decode_step(self, params, cache: KVCache, tokens):
        """Python-orchestrated per-layer routing. params live on W (weights
        resident, no KV there); KV lives on A. Used for correctness and
        for the Fig 11 breakdown; the analytical model covers scaling."""
        if cache.is_tiered:
            raise ValueError(
                "eager WA decode_step does not support tiered caches — the "
                "tiered read is a serving-lane (slotted) program")
        pos = cache.length
        B = tokens.shape[0]
        (k, v, ks, vs, _, _), logits = self._layer_loop(
            params, cache, tokens, jnp.full((B, 1), pos, jnp.int32),
            lambda kv_i, q, kk, vv: self._a_attend(kv_i, q, kk, vv, pos,
                                                   window=cache.window))
        return cache._replace(k=k, v=v, k_scale=ks, v_scale=vs,
                              length=pos + 1), logits

    def decode_step_slotted(self, params, cache: KVCache, tokens,
                            positions, active, kv_bucket: int = 0):
        """Continuous-batching decode in the WA-decoupled path: per-slot
        cursors + active mask (DESIGN.md §7). Slot admission itself is the
        same ``write_slot_kv`` the colocated engine uses — the A node owns
        the KV, so admission touches only A-side state. ``kv_bucket``
        (static) caps the attended extent — the serving engine's
        length-aware walk, applied at the A-side read. ``overlap`` > 1
        runs the software-pipelined schedule over micro-batch row slices
        (every A-side op is row-wise, so the split is token-exact)."""
        def attend(kv_i, q, kk, vv, sl=slice(None)):
            pos, act = (positions, active) if sl == slice(None)\
                else (positions[sl], active[sl])
            return self._a_attend_slotted(kv_i, q, kk, vv, pos, act,
                                          window=cache.window,
                                          kv_bucket=kv_bucket)

        loop = self._layer_loop_pipelined if self.overlap > 1\
            else self._layer_loop
        (k, v, ks, vs, hk, hv), logits = loop(
            params, cache, tokens, positions[:, None], attend)
        new_len = jnp.maximum(
            cache.length, jnp.max(jnp.where(active, positions, 0)) + 1)
        return cache._replace(k=k, v=v, k_scale=ks, v_scale=vs,
                              hot_k=hk, hot_v=hv, length=new_len), logits

    def _decode_slotted_api(self, params, caches, tokens, positions, active,
                            ctx, kv_bucket: int = 0):
        """ModelAPI.decode_slotted-shaped adapter for ``make_decode_block``:
        the WA engine carries its own W/A contexts, so the engine-supplied
        ctx is unused. Traced inside the block scan → AOT routing only."""
        del ctx
        self._require_aot("decode_block")
        return self.decode_step_slotted(params, caches, tokens, positions,
                                        active, kv_bucket=kv_bucket)

    # -- chunked prefill ----------------------------------------------------
    def prefill_chunk(self, params, cache: KVCache, tokens, slot, start,
                      valid_len):
        """WA-split chunked prefill: ONE fixed-(1,C) program per chunk width
        (DESIGN.md §7 chunked-prefill lane), the admission path of the WA
        serving backend. The W side runs embed/ln1/QKV and (after the route
        back) Wo/residual/ln2/FFN — unchanged weight-node work; the A side
        owns every piece of slot state: the chunk's K/V land at the slot's
        offset (``layer_write_chunk``; positions ≥ valid_len never touch the
        cache), the slot's stored prefix is read back (``layer_read_slot``;
        int8 dequantizes the same values decode will attend) and
        ``chunk_attention`` runs under the A-domain rules.
        slot/start/valid_len are traced scalars: zero retracing across
        chunks, prompts and slots. Returns (cache', logits (1,1,V)) at the
        chunk's last valid position."""
        self._require_aot("prefill_chunk")
        if cache.window:
            raise ValueError("chunked prefill requires a non-windowed cache "
                             "(ring order has no per-position write offset)")
        cfg = self.cfg
        x = common.embed(params["embed"], tokens, self.w_ctx)
        C = tokens.shape[1]
        positions = start + jnp.arange(C, dtype=jnp.int32)
        if cfg.pos == "learned":
            x = x + jnp.take(params["pos_embed"], positions,
                             axis=0)[None].astype(x.dtype)
        elif cfg.pos == "sinusoidal":
            table = common.sinusoidal_pos(cache.k.shape[3], cfg.d_model)
            x = x + jnp.take(table, positions, axis=0)[None].astype(x.dtype)
        stacks = list(self._pin_cache_stacks(
            cache.k, cache.v, cache.k_scale, cache.v_scale,
            cache.hot_k, cache.hot_v))
        tiered = cache.is_tiered
        S = cache.k.shape[3]
        # causal over absolute positions: query i attends cache slots
        # <= start+i (padding queries i >= valid_len attend zeros/stale
        # slots — their outputs are discarded)
        mask = jnp.arange(S, dtype=jnp.int32)[None, :]\
            <= positions[:, None]                                      # (C,S)
        if tiered:
            # per-QUERY demotion boundary: query i has start+i+1 tokens
            hot_mask = (jnp.arange(S, dtype=jnp.int32)[None, :] >=
                        cold_boundary(positions + 1, cfg.hot_window,
                                      cfg.kv_cold_block)[:, None])[None]
        for i in range(cfg.n_layers):
            lp = jax.tree.map(lambda a: a[i], params["blocks"])
            q, k, v = self._w_qkv(lp, x, positions[None])
            q, k, v = self._to_a(q), self._to_a(k), self._to_a(v)
            kv_i = tuple(None if c is None else c[i] for c in stacks)
            k_ch = jnp.swapaxes(k[0], 0, 1)
            v_ch = jnp.swapaxes(v[0], 0, 1)
            if tiered:
                # exact hot image from the PRE-write ring + incoming chunk
                # (the write below may overwrite exactly the ring slots
                # early queries' hot tails live in)
                kh, vh = chunk_hot_image(kv_i[4], kv_i[5], k_ch, v_ch,
                                         slot, start, valid_len, S,
                                         dtype=x.dtype)
                kv_i = layer_write_chunk_tiered(
                    kv_i[0], kv_i[1], kv_i[2], kv_i[3], kv_i[4], kv_i[5],
                    k_ch, v_ch, slot, start, valid_len, cfg.kv_cold_dtype)
                kc, vc = layer_read_slot_cold(
                    kv_i[0], kv_i[1], kv_i[2], kv_i[3], slot,
                    cfg.kv_cold_dtype, dtype=x.dtype)
                o = chunk_attention_tiered(q, kh, vh, kc, vc, hot_mask,
                                           mask, self.a_ctx)
            else:
                kv_i = layer_write_chunk(
                    kv_i[0], kv_i[1], kv_i[2], kv_i[3], k_ch, v_ch,
                    slot, start, valid_len) + (None, None)
                kc, vc = layer_read_slot(kv_i[0], kv_i[1], kv_i[2],
                                         kv_i[3], slot, dtype=x.dtype)
                o = chunk_attention(q, kc, vc, mask, self.a_ctx)
            for n, piece in enumerate(kv_i):
                if piece is not None:
                    stacks[n] = stacks[n].at[i].set(piece)
            o = self._to_w(o)
            x = self._w_post(lp, x, o)
        x = common.apply_norm(cfg.norm, params["ln_f"], x, cfg.norm_eps)
        from repro.models.transformer import unembed_table
        last = jax.lax.dynamic_slice_in_dim(x, valid_len - 1, 1, axis=1)
        logits = common.unembed_logits(unembed_table(params, cfg), last,
                                       self.w_ctx)
        new_len = jnp.maximum(cache.length, start + valid_len)
        # exit pin too: the donated stacks must leave in the layout they
        # entered with, or the donation degrades to a copy per dispatch
        stacks = self._pin_cache_stacks(*stacks)
        return cache._replace(k=stacks[0], v=stacks[1], k_scale=stacks[2],
                              v_scale=stacks[3], hot_k=stacks[4],
                              hot_v=stacks[5], length=new_len), logits
