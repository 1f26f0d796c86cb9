"""Mixture-of-Experts FFN with expert parallelism.

The paper names MoE as the natural extension of its execution model (§7.2):
"routing-dependent communication ... topology-aware expert placement to keep
sparse activation from turning into cross-socket traffic". Here that becomes:
experts sharded over the ``model`` axis (EP); token→expert dispatch is a
sort-based, capacity-bounded scatter (static shapes — the static-runtime
requirement) whose resharding the compiler lowers to all-to-all on the ICI.

Routing IS sub-operator scheduling: each token's expert assignment is an
independent dependency edge; there is no operator-boundary barrier between
router, dispatch, expert GEMMs and combine.

``moe_held`` is one chip's share of expert parallelism (``MoEConfig.
held_experts``): the router routes over every expert, the chip computes
only its own experts' part, dropless, and counts the picks they receive.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import common
from repro.models.sharding import ShardingCtx


def make_moe_params(key, cfg: ModelConfig) -> Dict:
    """Router over all ``num_experts``; expert weights for the experts this
    chip holds (all of them, or ``held_experts``). A held-expert router is
    stored in the model dtype, as the published checkpoints store it, and
    computes in float32 all the same."""
    m = cfg.moe
    d, f = cfg.d_model, m.expert_d_ff
    if m.held_experts:
        if not 0 <= m.held_offset <= m.num_experts - m.held_experts:
            raise ValueError(
                f"held experts [{m.held_offset}, "
                f"{m.held_offset + m.held_experts}) lie outside the "
                f"router's {m.num_experts}")
        e, router_dt = m.held_experts, common.dtype_of(cfg)
    else:
        e, router_dt = m.num_experts, jnp.dtype(jnp.float32)
    dt = common.dtype_of(cfg)
    ks = jax.random.split(key, 4)

    def einit(k, shape, fan_in):
        return common.dense_init(k, shape, dt, fan_in=fan_in)

    return {
        "router": common.make_linear(ks[0], d, m.num_experts, router_dt),
        "w_gate": einit(ks[1], (e, d, f), d),
        "w_up": einit(ks[2], (e, d, f), d),
        "w_down": einit(ks[3], (e, f, d), f),
    }


def moe_held(p: Dict, x: jax.Array, cfg: ModelConfig,
             valid: Optional[jax.Array] = None
             ) -> Tuple[jax.Array, jax.Array]:
    """One chip's share of an expert-parallel layer, dropless. x: (B,S,D);
    ``valid`` (B,S) bool marks the real tokens (None: all). Returns (out
    (B,S,D), picks (held,) int32).

    Every token is routed over all ``num_experts`` (float32 logits and
    softmax), takes its top ``experts_per_token`` and renormalises their
    weights over those; ``out`` is the weighted sum of the outputs of the
    picked experts that this chip holds, nothing for the others: the
    partial result that goes on to the next layer. No token is dropped:
    the held experts run as one SwiGLU of width held x expert_d_ff over
    every token, each expert's hidden part scaled by the token's weight for
    it (zero where the token did not pick it). ``picks`` counts the valid
    tokens that picked each held expert."""
    m = cfg.moe
    B, S, D = x.shape
    H = m.held_experts
    xf = x.reshape(B * S, D)
    with jax.named_scope("moe/router"):
        logits = jnp.einsum("td,de->te", xf.astype(jnp.float32),
                            p["router"]["w"].astype(jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, gate_idx = jax.lax.top_k(probs, m.experts_per_token)
        gate_vals = gate_vals / jnp.sum(gate_vals, -1, keepdims=True)
        # (T, K, H): which held expert each pick is; picks of experts held
        # elsewhere match none
        held = jax.nn.one_hot(gate_idx - m.held_offset, H, dtype=jnp.float32)
        weights = jnp.einsum("tk,tkh->th", gate_vals, held)
        mask = held if valid is None else \
            held * valid.reshape(B * S, 1, 1).astype(jnp.float32)
        picks = jnp.sum(mask, axis=(0, 1)).astype(jnp.int32)
    with jax.named_scope("moe/experts"):
        gate = jnp.einsum("td,hdf->thf", xf, p["w_gate"],
                          preferred_element_type=jnp.float32)
        up = jnp.einsum("td,hdf->thf", xf, p["w_up"],
                        preferred_element_type=jnp.float32)
        h = jax.nn.silu(gate) * up * weights[..., None]
        out = jnp.einsum("thf,hfd->td", h.astype(x.dtype), p["w_down"],
                         preferred_element_type=jnp.float32)
    return out.astype(x.dtype).reshape(B, S, D), picks


def capacity(tokens: int, cfg: ModelConfig) -> int:
    """Per-expert slot count. capacity_factor <= 0 → no-drop (worst case:
    every assignment lands on one expert) — exact but FLOP-wasteful; used by
    correctness tests. Production uses GShard-style bounded capacity (static
    shapes = the paper's static-runtime requirement; overflow drops)."""
    m = cfg.moe
    if m.capacity_factor <= 0:
        return tokens * m.experts_per_token
    c = int(math.ceil(tokens * m.experts_per_token * m.capacity_factor
                      / m.num_experts))
    return max(8, -(-c // 8) * 8)                      # pad to 8 for layout


def moe_ffn(p: Dict, x: jax.Array, cfg: ModelConfig, ctx: ShardingCtx,
            train: bool) -> Tuple[jax.Array, jax.Array]:
    """x: (B,S,D) → (out (B,S,D), load-balance aux loss).

    LOCALITY-AWARE dispatch (paper §7.2: "topology-aware expert placement to
    keep sparse activation from turning into cross-socket traffic"): when a
    data axis exists, the token→slot scatter and slot→token combine run
    SHARD-LOCALLY per data row (shard_map manual over "data", per-row
    capacity C/rows) — a data-dependent scatter across a sharded dim would
    otherwise make GSPMD materialize the full (E·C, D) dispatch tensor with
    a cross-row all-reduce per layer (measured: ~10 PB/step at qwen3-235B
    train_4k; see EXPERIMENTS.md §Perf cell 2)."""
    m = cfg.moe
    B, S, D = x.shape
    T = B * S
    K, E = m.experts_per_token, m.num_experts
    mesh = ctx.mesh
    mshape = dict(mesh.shape) if mesh is not None else {}
    batch_axes = ctx.rules.rules.get("batch") or ()
    dp_axes = tuple(a for a in ("pod", "data")
                    if a in mshape and a in batch_axes)
    data_rows = 1
    for a in dp_axes:
        data_rows *= mshape[a]
    # Gates (EXPERIMENTS §Perf cell 2):
    # - inference only: differentiating this shard_map at 512 simulated CPU
    #   devices trips an XLA-CPU check failure ("Invalid binary instruction
    #   opcode copy"); fwd+grad verified correct at 8 devices.
    # - per-row tokens ≥ 512: below that, the per-expert capacity floor
    #   (8-slot MXU alignment) pads ≥2× the expert GEMMs (measured at
    #   decode_32k: 3.0e13 → 8.6e13 flops) — tiny-batch decode keeps the
    #   GSPMD dispatch.
    t_local = T // max(data_rows, 1)
    if dp_axes and data_rows > 1 and B % data_rows == 0 and not train \
            and t_local >= 512:
        return _moe_ffn_sharded(p, x, cfg, ctx, train, dp_axes)
    C = capacity(T, cfg)
    xf = x.reshape(T, D)
    xf = ctx.ann(xf, "batch", "embed")

    # ---- router ------------------------------------------------------
    logits = common.linear(p["router"], xf.astype(jnp.float32))   # (T,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, K)                 # (T,K)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, -1, keepdims=True), 1e-9)              # renormalize

    # ---- load-balance loss (Switch-style) -----------------------------
    me = jnp.mean(probs, axis=0)                                  # (E,)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(gate_idx, E, dtype=jnp.float32), axis=1), axis=0)
    aux = E * jnp.sum(me * ce)

    # ---- sort-based capacity dispatch ---------------------------------
    flat_e = gate_idx.reshape(-1)                                 # (T*K,)
    flat_t = jnp.repeat(jnp.arange(T, dtype=jnp.int32), K)
    flat_g = gate_vals.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    se, st, sg = flat_e[order], flat_t[order], flat_g[order]
    # rank within expert segment (sorted ⇒ segment-contiguous)
    seg_start = jnp.searchsorted(se, jnp.arange(E, dtype=se.dtype))
    rank = jnp.arange(T * K, dtype=jnp.int32) - seg_start[se].astype(jnp.int32)
    keep = rank < C
    slot = jnp.where(keep, se.astype(jnp.int32) * C + rank, E * C)  # drop → OOB

    # gather tokens into expert buckets (E*C, D); OOB writes are dropped
    disp = jnp.zeros((E * C, D), x.dtype).at[slot].set(
        xf[st], mode="drop", unique_indices=True)
    disp = ctx.ann(disp.reshape(E, C, D), "experts", None, "embed")

    # ---- expert GEMMs (batched over the expert shard) ------------------
    gate = jnp.einsum("ecd,edf->ecf", disp, p["w_gate"].astype(disp.dtype))
    up = jnp.einsum("ecd,edf->ecf", disp, p["w_up"].astype(disp.dtype))
    h = common.gated_act(cfg.act if cfg.act != "gelu_mlp" else "swiglu", up, gate)
    h = ctx.ann(h, "experts", None, "mlp_shard")
    eo = jnp.einsum("ecf,efd->ecd", h, p["w_down"].astype(h.dtype))
    eo = ctx.ann(eo, "experts", None, "embed").reshape(E * C, D)

    # ---- combine: weighted scatter-add back to token order -------------
    contrib = jnp.take(eo, jnp.minimum(slot, E * C - 1), axis=0)
    contrib = contrib * (sg * keep).astype(contrib.dtype)[:, None]
    out = jnp.zeros((T, D), x.dtype).at[st].add(contrib)
    out = ctx.ann(out, "batch", "embed")
    return out.reshape(B, S, D), aux.astype(jnp.float32)


# ---------------------------------------------------------------------------
# Shard-local dispatch: manual over the batch axes, auto over "model".
# Per data row: local top-k → local capacity buckets → expert GEMMs (experts
# still sharded over "model" by GSPMD) → local combine. No cross-row
# collective is needed for routing at all; experts see per-row slot batches.
# ---------------------------------------------------------------------------

def _moe_ffn_sharded(p: Dict, x: jax.Array, cfg: ModelConfig,
                     ctx: ShardingCtx, train: bool, dp_axes) -> Tuple:
    from repro.models.sharding import ExecutionRules
    mesh = ctx.mesh
    B, S, D = x.shape
    # inner constraints may only use non-manual (auto) axes
    inner_rules = ExecutionRules(ctx.rules.name + "+local", {
        k: (tuple(a for a in (v or ()) if a not in dp_axes) or None)
        for k, v in ctx.rules.rules.items()})
    inner_ctx = ShardingCtx(mesh, inner_rules)

    def local(xl, pl):
        # xl: (B/rows, S, D) — this row's tokens; expert weights arrive via
        # their auto-axis sharding (model EP; FSDP gathers per layer in train)
        out, aux = _moe_core(pl, xl, cfg, inner_ctx, train)
        return out, jax.lax.pmean(aux, dp_axes)

    from jax.sharding import PartitionSpec as P
    x_spec = P(dp_axes if len(dp_axes) > 1 else dp_axes[0], None, None)
    f = jax.shard_map(local, mesh=mesh,
                      in_specs=(x_spec, P()),
                      out_specs=(x_spec, P()),
                      axis_names=frozenset(dp_axes), check_vma=False)
    return f(x, p)


def _moe_core(p: Dict, x: jax.Array, cfg: ModelConfig, ctx: ShardingCtx,
              train: bool) -> Tuple[jax.Array, jax.Array]:
    """The dispatch/compute/combine body on LOCAL tokens (original path)."""
    m = cfg.moe
    B, S, D = x.shape
    T = B * S
    K, E = m.experts_per_token, m.num_experts
    C = capacity(T, cfg)
    xf = x.reshape(T, D)

    logits = common.linear(p["router"], xf.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, K)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, -1, keepdims=True), 1e-9)

    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(gate_idx, E, dtype=jnp.float32), axis=1), axis=0)
    aux = E * jnp.sum(me * ce)

    flat_e = gate_idx.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(T, dtype=jnp.int32), K)
    flat_g = gate_vals.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    se, st, sg = flat_e[order], flat_t[order], flat_g[order]
    seg_start = jnp.searchsorted(se, jnp.arange(E, dtype=se.dtype))
    rank = jnp.arange(T * K, dtype=jnp.int32) - seg_start[se].astype(jnp.int32)
    keep = rank < C
    slot = jnp.where(keep, se.astype(jnp.int32) * C + rank, E * C)

    disp = jnp.zeros((E * C, D), x.dtype).at[slot].set(
        xf[st], mode="drop", unique_indices=True)
    disp = ctx.ann(disp.reshape(E, C, D), "experts", None, None)

    gate = jnp.einsum("ecd,edf->ecf", disp, p["w_gate"].astype(disp.dtype))
    up = jnp.einsum("ecd,edf->ecf", disp, p["w_up"].astype(disp.dtype))
    h = common.gated_act(cfg.act if cfg.act != "gelu_mlp" else "swiglu", up, gate)
    h = ctx.ann(h, "experts", None, "mlp_shard")
    eo = jnp.einsum("ecf,efd->ecd", h, p["w_down"].astype(h.dtype))
    eo = ctx.ann(eo, "experts", None, None).reshape(E * C, D)

    contrib = jnp.take(eo, jnp.minimum(slot, E * C - 1), axis=0)
    contrib = contrib * (sg * keep).astype(contrib.dtype)[:, None]
    out = jnp.zeros((T, D), x.dtype).at[st].add(contrib)
    return out.reshape(B, S, D), aux.astype(jnp.float32)
