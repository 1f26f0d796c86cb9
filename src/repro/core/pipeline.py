"""Pipeline parallelism over the ``pod`` axis (paper §4.1: "PP across rack
nodes ... only activation tensors are exchanged between rack nodes").

Token-pipelined DECODE for transformer-family archs: the pod axis carries
n_stages pipeline stages; each serve_step call advances every in-flight
request group by one stage and `ppermute`s the (B, 1, d_model) activation to
the next stage — per-call cross-pod traffic is exactly the paper's
"embeddings only" (B·d_model bytes per hop; KV and weights never move).
Steady state matches the paper's analytical model (§6.2):

    TPOT = n_stages × (stage_latency + hop_latency) + embed
    Throughput = one token-batch per call (1/stage_latency)

Training/prefill across pods use pod-DP with hierarchical gradient reduction
(core/collectives.py) — the paper applies PP to decoding, which "is the
long-running steady state"; a GPipe microbatch trainer is the documented
extension point.

State layout (stage dim leads, P("pod") on dim 0):
    KV:      (n_stages, L/n_stages, B, n_kv, S, hd)  int8 + scales
    lengths: (n_stages,)   — each in-flight group's decode position
    x_carry: (n_stages, B, 1, d_model) — activations in flight between calls
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.configs.shapes import ShapeConfig
from repro.models import common
from repro.models.param_specs import leaf_logical
from repro.models.registry import DECODE_SLACK, build_model
from repro.models.sharding import ShardingCtx, seq_sharded_kv, sub_operator
from repro.models.transformer import block_decode, unembed_table

_HEAD_KEYS = ("embed", "ln_f", "unembed", "pos_embed")


def _only_pod(spec: P) -> P:
    """shard_map manual-over-pod specs may reference only 'pod'; data/model
    placement comes from the outer jit in_shardings + inner constraints."""
    def keep(e):
        if e == "pod":
            return "pod"
        if isinstance(e, (tuple, list)) and "pod" in e:
            return "pod"
        return None
    return P(*[keep(e) for e in spec])


def _pod_specs(tree):
    return jax.tree.map(_only_pod, tree, is_leaf=lambda x: isinstance(x, P))


def _shard_map(f, mesh, in_specs, out_specs):
    """Partial-manual shard_map: manual over 'pod', auto over data/model —
    inner GSPMD rules keep working while we schedule the pipeline by hand."""
    return jax.shard_map(f, mesh=mesh, in_specs=_pod_specs(in_specs),
                         out_specs=_pod_specs(out_specs),
                         axis_names=frozenset({"pod"}), check_vma=False)


def stage_params(params: Dict[str, Any], n_stages: int) -> Dict[str, Any]:
    """(L, ...) block leaves → (n_stages, L/n_stages, ...)."""
    out = dict(params)
    out["blocks"] = jax.tree.map(
        lambda a: a.reshape(n_stages, a.shape[0] // n_stages, *a.shape[1:]),
        params["blocks"])
    return out


# ---------------------------------------------------------------------------
# Stage-skew schedule machinery
# ---------------------------------------------------------------------------
# The skew pattern of this module's token pipeline — participant m runs work
# item (t - m) at tick t — generalized so ``core/wa.py`` can software-
# pipeline its W/A layer loop over micro-batches (sub-operator overlap,
# DESIGN.md §3): the schedule is STATIC (pure python ints), so the unrolled
# trace compiles into one program per cell regardless of depth.

def skewed_schedule(n_ops: int, depth: int):
    """Static software-pipeline schedule: ``depth`` participants each run
    the same chain of ``n_ops`` ops, participant ``m`` skewed ``m`` ticks
    behind participant 0. Returns ``[(tick, [(m, op), ...]), ...]`` covering
    ``n_ops + depth - 1`` ticks; at each tick the live participants hold
    CONSECUTIVE op indices (op = tick - m), so for an alternating two-domain
    op chain adjacent participants always occupy opposite domains."""
    if n_ops < 1 or depth < 1:
        raise ValueError(f"need n_ops >= 1 and depth >= 1, got "
                         f"({n_ops}, {depth})")
    return [(t, [(m, t - m) for m in range(depth) if 0 <= t - m < n_ops])
            for t in range(n_ops + depth - 1)]


def wa_schedule_occupancy(n_layers: int, depth: int) -> Dict[str, Any]:
    """Per-domain occupancy of the skewed WA decode schedule: the op chain
    is 2L+1 alternating ops (even = W: QKV/FFN, odd = A: attention), so a
    tick is W-busy (A-busy) when any live micro-batch holds an even (odd)
    op. Depth 1 degenerates to the sequential loop — every tick runs
    exactly one domain and ``overlap_efficiency`` is ~0.5; depth >= 2 keeps
    both domains busy on every interior tick (efficiency → 1). Pure
    schedule arithmetic: the SAME numbers for the compiled program and for
    ``stats()['wa']``'s stall accounting, with no wall-clock noise."""
    sched = skewed_schedule(2 * n_layers + 1, depth)
    w_busy = sum(1 for _t, live in sched if any(op % 2 == 0 for _m, op in live))
    a_busy = sum(1 for _t, live in sched if any(op % 2 == 1 for _m, op in live))
    total = len(sched)
    return {
        "total_ticks": total,
        "w_busy_ticks": w_busy,
        "a_busy_ticks": a_busy,
        "w_idle_frac": (total - w_busy) / total,
        "a_idle_frac": (total - a_busy) / total,
        "overlap_efficiency": (w_busy + a_busy) / (2 * total),
    }


def make_pp_step(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh,
                 executor: str = "sub_operator", lr: float = 3e-4):
    from repro.core.execution import StepBundle
    if shape.mode != "decode":
        raise NotImplementedError(
            "PP is implemented for decode (the paper's scenario); train/"
            "prefill scale across pods with pod-DP + hierarchical reduction")
    if cfg.family not in ("dense", "vlm", "moe"):
        raise NotImplementedError("PP decode targets transformer-family archs")

    n_stages = mesh.shape["pod"]
    assert cfg.n_layers % n_stages == 0, (cfg.n_layers, n_stages)
    Lp = cfg.n_layers // n_stages
    B = shape.global_batch
    max_len = shape.seq_len + DECODE_SLACK
    cfg = cfg.replace(kv_dtype="int8")      # paper §5: fully INT8 serving

    rules = sub_operator(pod_is_dp=False)
    if executor.endswith("+seqkv"):
        rules = seq_sharded_kv(rules)
    ctx = ShardingCtx(mesh, rules)

    api = build_model(cfg)
    params_shape = jax.eval_shape(api.init, jax.random.key(0))
    staged_shape = jax.eval_shape(
        lambda: stage_params(
            jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), params_shape),
            n_stages))

    def spec_of(path, leaf):
        keys = [str(getattr(p, "key", getattr(p, "name", p))) for p in path]
        logical = leaf_logical(path, leaf)
        if "blocks" in keys:
            logical = ("stages",) + tuple(logical)[1:]
        return ctx.spec(tuple(logical), leaf.shape)

    p_specs = jax.tree_util.tree_map_with_path(spec_of, staged_shape)
    p_shard = jax.tree.map(lambda s: NamedSharding(mesh, s), p_specs,
                           is_leaf=lambda x: isinstance(x, P))

    kv_shape = (n_stages, Lp, B, cfg.n_kv_heads, max_len, cfg.head_dim)
    sc_shape = kv_shape[:-1] + (1,)
    caches_shape = {
        "k": jax.ShapeDtypeStruct(kv_shape, jnp.int8),
        "v": jax.ShapeDtypeStruct(kv_shape, jnp.int8),
        "k_scale": jax.ShapeDtypeStruct(sc_shape, jnp.float32),
        "v_scale": jax.ShapeDtypeStruct(sc_shape, jnp.float32),
        "lengths": jax.ShapeDtypeStruct((n_stages,), jnp.int32),
        "x_carry": jax.ShapeDtypeStruct((n_stages, B, 1, cfg.d_model),
                                        jnp.dtype(cfg.dtype)),
    }
    kv_spec = ctx.spec(("stages", None, "batch", "kv_heads", "kv_seq", None),
                       kv_shape)
    sc_spec = ctx.spec(("stages", None, "batch", "kv_heads", "kv_seq", None),
                       sc_shape)
    c_specs = {"k": kv_spec, "v": kv_spec, "k_scale": sc_spec,
               "v_scale": sc_spec, "lengths": P("pod"),
               # activations ride the wire model-scattered (embed_shard)
               "x_carry": ctx.spec(("stages", "batch", None, "embed_shard"),
                                   caches_shape["x_carry"].shape)}
    c_shard = jax.tree.map(lambda s: NamedSharding(mesh, s), c_specs,
                           is_leaf=lambda x: isinstance(x, P))
    tok_shape = jax.ShapeDtypeStruct((n_stages, B), jnp.int32)
    tok_spec = ctx.spec(("stages", "batch"), (n_stages, B))
    logit_spec = ctx.spec(("stages", "batch", None, "vocab"),
                          (n_stages, B, 1, cfg.vocab_size))

    # ------------------- per-stage body (manual over 'pod') ---------------
    # NOTE: the stage index arrives as an explicit P("pod")-sharded iota
    # instead of lax.axis_index("pod") — axis_index under partial-manual
    # shard_map lowers to a PartitionId instruction that SPMD partitioning
    # rejects on older JAX.
    def body(stage_ids, blocks, head, caches, tokens):
        blocks = jax.tree.map(lambda a: a[0], blocks)         # (Lp, ...)
        k = caches["k"][0]                                    # (Lp,B,kv,S,hd)
        v = caches["v"][0]
        ks = caches["k_scale"][0]
        vs = caches["v_scale"][0]
        pos = caches["lengths"][0]
        stage = stage_ids[0]
        emb = common.embed(head["embed"], tokens[0][:, None], ctx)
        x = jnp.where(stage == 0, emb.astype(caches["x_carry"].dtype),
                      caches["x_carry"][0])

        def layer(h, xs):
            lp, k_l, v_l, ks_l, vs_l = xs
            h, upd = block_decode(lp, h, cfg, ctx, (k_l, v_l, ks_l, vs_l), pos)
            return h, upd

        x, (k_n, v_n, ks_n, vs_n) = lax.scan(
            layer, x, (blocks, k, v, ks, vs), unroll=common.scan_unroll())
        xf = common.apply_norm(cfg.norm, head["ln_f"], x, cfg.norm_eps)
        logits = common.unembed_logits(unembed_table(head, cfg), xf, ctx)
        # paper's cross-node hop (embeddings only) happens OUTSIDE the manual
        # region — jnp.roll over the pod-sharded stage axis in `step` — since
        # CollectivePermute inside a manual subgroup crashes the SPMD
        # partitioner on older JAX; the roll lowers to the same permute.
        new_caches = {"k": k_n[None], "v": v_n[None],
                      "k_scale": ks_n[None], "v_scale": vs_n[None],
                      "lengths": (pos + 1)[None], "x_carry": x[None]}
        return new_caches, logits[None].astype(jnp.float32)

    head_keys = [k for k in _HEAD_KEYS if k in staged_shape]
    head_specs = {k: p_specs[k] for k in head_keys}
    f_sharded = _shard_map(
        body, mesh,
        (P("pod"), p_specs["blocks"], head_specs, c_specs, tok_spec),
        ({"k": kv_spec, "v": kv_spec, "k_scale": sc_spec, "v_scale": sc_spec,
          "lengths": P("pod"), "x_carry": c_specs["x_carry"]}, logit_spec))

    def step(params, caches, tokens):
        head = {k: params[k] for k in head_keys}
        stage_ids = jnp.arange(n_stages, dtype=jnp.int32)
        new_caches, logits = f_sharded(stage_ids, params["blocks"], head,
                                       caches, tokens)
        new_caches = dict(new_caches)
        new_caches["x_carry"] = jnp.roll(new_caches["x_carry"], 1, axis=0)
        return new_caches, logits

    name = f"{cfg.name}|{shape.name}|{executor}|pp{n_stages}"
    return StepBundle(
        name + "|decode", step,
        (staged_shape, caches_shape, tok_shape),
        (p_shard, c_shard, NamedSharding(mesh, tok_spec)),
        None, donate_argnums=(1,), ctx=ctx)
