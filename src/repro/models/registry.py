"""Uniform model API over all families + input_specs() for the dry-run.

``build_model(cfg)`` → ModelAPI with:
    init(key) -> params
    loss(params, batch, ctx) -> scalar
    prefill(params, batch, ctx) -> (caches, last_logits)
    decode(params, caches, tokens, ctx) -> (caches, logits)
    init_caches(batch, max_len) -> caches pytree
    input_specs(shape) -> dict of jax.ShapeDtypeStruct (weak-type-correct,
                          shardable, no device allocation)
"""
from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.configs.shapes import ShapeConfig

DECODE_SLACK = 128      # cache headroom beyond the shape's context length


class ModelAPI(NamedTuple):
    config: ModelConfig
    init: Callable
    loss: Callable
    prefill: Callable
    decode: Callable
    init_caches: Callable
    input_specs: Callable
    # -- continuous-batching extensions (None → family only serves in the
    #    drain-then-refill mode; see runtime/serving.py + DESIGN.md §7) -----
    # decode_slotted(params, caches, tokens, positions, active, ctx,
    #                kv_bucket=0[, kv_shards=1])
    #   → (caches, logits): per-slot cursors + active mask through decode;
    #   kv_bucket (static) caps the attended KV extent (length-aware walk);
    #   kv_shards (static, KV-cache families only) splits the walk into
    #   sequence shards combined by the partial-softmax LSE merge
    #   (split-KV flash decode — models/attention.py)
    decode_slotted: Optional[Callable] = None
    # write_slot(caches, single, slot) → caches: admit a batch-1 prefill
    #   into one batch slot (slot is traced — one program for all slots)
    write_slot: Optional[Callable] = None
    # reset_slot(caches, slot) → caches: zero a retired slot's state
    reset_slot: Optional[Callable] = None
    # decode_block(params, caches, tokens, positions, active, remaining,
    #              eos_ids, ctx, *, block_size, kv_bucket=0): T greedy
    #   micro-steps in ONE program with on-device per-slot halting — the
    #   macro-step decode path (DESIGN.md §7); see make_decode_block
    decode_block: Optional[Callable] = None
    # prefill_chunk(params, caches, tokens, slot, start, valid_len, ctx)
    #   → (caches, logits (1,1,V)): ONE fixed-(1,C) program that writes slot
    #   ``slot``'s prompt chunk [start, start+valid_len) at its per-slot
    #   offset and attends/advances over the prefix — the chunked-prefill
    #   lane (DESIGN.md §7). slot/start/valid_len traced: zero retracing
    #   across chunks, prompts and slots. None → monolithic admission only.
    prefill_chunk: Optional[Callable] = None
    # decode_kv_read(caches, ctx, positions, active, kv_bucket=0,
    #                kv_shards=1) → float: KV positions one decode_slotted
    #   micro-step reads at host cursors ``positions``, summed over rows,
    #   mean over layers (caches may be abstract: only its structure is
    #   read). None → the family's decode reads every slot's whole extent
    decode_kv_read: Optional[Callable] = None
    # wa_servable: the family can serve through the WA-disaggregated backend
    #   (ServingEngine(backend="wa") → core/wa.py). True only for prefix-
    #   ordered KV-cache transformers: attention-free families have no KV to
    #   decouple (DESIGN.md §6), windowed ring buffers have no stable
    #   per-position offsets, and VLM prompts interleave vision embeds the
    #   token-only WA chunk walk cannot cover.
    wa_servable: bool = False


def make_decode_block(decode_slotted: Callable) -> Callable:
    """Lift a family's ``decode_slotted`` into a macro-step ``decode_block``:
    ``block_size`` greedy micro-steps inside one ``lax.scan`` — caches,
    cursors, halt masks and sampled tokens all advance ON DEVICE, so the
    host syncs once per block instead of once per token (the step-axis
    analogue of the paper's sub-operator dependency relaxation, §5).

    Per-slot halting: ``remaining[b]`` is row b's token budget and
    ``eos_ids[b]`` an optional stop id (< 0 disables). A row that exhausts
    its budget or emits its EOS flips its own ``active`` bit mid-block and
    idles (no KV writes, token id 0) without host intervention.

    Returns ``(caches, toks (T,B) int32, emitted (T,B) bool, last_tok,
    positions, active, remaining)`` — ``emitted[t, b]`` marks micro-steps
    that really generated a token, so the host can unpack the block without
    guessing which zeros are padding. Where ``decode_slotted`` returns
    counts after its logits (a held-expert layer's picks), each is summed
    over the block's micro-steps and follows the seven."""

    def decode_block(params, caches, tokens, positions, active, remaining,
                     eos_ids, ctx, *, block_size: int, kv_bucket: int = 0,
                     kv_shards: int = 1):
        # kv_shards is forwarded only when split (> 1): attention-free
        # families' decode_slotted has no such axis and no such kwarg
        extra = {"kv_shards": kv_shards} if kv_shards != 1 else {}

        def micro(carry, _):
            caches, tok, pos, act, rem = carry
            caches, logits, *counts = decode_slotted(
                params, caches, tok, pos, act, ctx, kv_bucket=kv_bucket,
                **extra)
            nxt = jnp.argmax(logits[:, 0], -1).astype(jnp.int32)
            nxt = jnp.where(act, nxt, 0)
            emitted = act
            step = act.astype(jnp.int32)
            pos = pos + step
            rem = rem - step
            act = act & (rem > 0) & ((eos_ids < 0) | (nxt != eos_ids))
            return (caches, nxt, pos, act, rem), (nxt, emitted, *counts)

        (caches, tok, pos, act, rem), (toks, emitted, *counts) = \
            jax.lax.scan(micro, (caches, tokens, positions, active,
                                 remaining), None, length=block_size)
        return (caches, toks, emitted, tok, pos, act, rem) + \
            tuple(jnp.sum(c, axis=0) for c in counts)

    return decode_block


# ---------------------------------------------------------------------------
# family builders
# ---------------------------------------------------------------------------

def _build_transformer(cfg: ModelConfig) -> ModelAPI:
    from repro.models import transformer as T

    is_vlm = cfg.family == "vlm"

    def loss(params, batch, ctx):
        return T.loss_fn(params, batch, cfg, ctx)

    def prefill(params, batch, ctx):
        cache = T.make_cache(cfg, batch["tokens"].shape[0],
                             batch["tokens"].shape[1]
                             + (cfg.n_vision_tokens if is_vlm else 0)
                             + DECODE_SLACK)
        return T.prefill(params, batch["tokens"], cfg, ctx, cache,
                         vision_embeds=batch.get("vision_embeds"))

    def decode(params, caches, tokens, ctx):
        return T.decode_step(params, caches, tokens, cfg, ctx)

    def init_caches(batch, max_len):
        return T.make_cache(cfg, batch, max_len)

    def decode_slotted(params, caches, tokens, positions, active, ctx,
                       kv_bucket: int = 0, kv_shards: int = 1):
        return T.decode_step_slotted(params, caches, tokens, positions,
                                     active, cfg, ctx, kv_bucket=kv_bucket,
                                     kv_shards=kv_shards)

    from repro.kv.cache import reset_slot, write_slot_kv

    def prefill_chunk(params, caches, tokens, slot, start, valid_len, ctx):
        return T.prefill_chunk(params, caches, tokens, slot, start,
                               valid_len, cfg, ctx)

    def decode_kv_read(caches, ctx, positions, active, kv_bucket: int = 0,
                       kv_shards: int = 1):
        return T.decode_kv_read(cfg, caches, ctx, positions, active,
                                kv_bucket, kv_shards)

    return ModelAPI(cfg, lambda k: T.init_params(k, cfg), loss, prefill,
                    decode, init_caches, _lm_input_specs(cfg),
                    decode_slotted=decode_slotted,
                    write_slot=write_slot_kv,
                    reset_slot=reset_slot,
                    decode_block=make_decode_block(decode_slotted),
                    decode_kv_read=decode_kv_read,
                    # VLM prompts interleave vision embeds — the token-only
                    # chunk walk cannot cover them; monolithic admission only
                    prefill_chunk=None if is_vlm else prefill_chunk,
                    # the W/A layer loop has no per-layer windows
                    wa_servable=not is_vlm and not any(cfg.layer_windows))


def _build_ssm(cfg: ModelConfig) -> ModelAPI:
    from repro.models import ssm as S
    from repro.kv.state import reset_slot_tree, write_slot_tree

    def decode_slotted(params, state, tokens, positions, active, ctx,
                       kv_bucket: int = 0):
        return S.decode_step_slotted(params, state, tokens, positions,
                                     active, cfg, ctx, kv_bucket=kv_bucket)

    def prefill_chunk(params, state, tokens, slot, start, valid_len, ctx):
        return S.prefill_chunk(params, state, tokens, slot, start,
                               valid_len, cfg, ctx)

    return ModelAPI(
        cfg,
        lambda k: S.init_params(k, cfg),
        lambda p, b, ctx: S.loss_fn(p, b, cfg, ctx),
        lambda p, b, ctx: S.prefill(p, b["tokens"], cfg, ctx),
        lambda p, c, t, ctx: S.decode_step(p, c, t, cfg, ctx),
        lambda batch, max_len: S.make_state(cfg, batch),
        _lm_input_specs(cfg),
        decode_slotted=decode_slotted,
        write_slot=write_slot_tree,
        reset_slot=reset_slot_tree,
        decode_block=make_decode_block(decode_slotted),
        prefill_chunk=prefill_chunk)


def _build_hybrid(cfg: ModelConfig) -> ModelAPI:
    from repro.models import rglru as R

    return ModelAPI(
        cfg,
        lambda k: R.init_params(k, cfg),
        lambda p, b, ctx: R.loss_fn(p, b, cfg, ctx),
        lambda p, b, ctx: R.prefill(p, b["tokens"], cfg, ctx),
        lambda p, c, t, ctx: R.decode_step(p, c, t, cfg, ctx),
        lambda batch, max_len: R.make_caches(cfg, batch, max_len),
        _lm_input_specs(cfg))


def _build_encdec(cfg: ModelConfig) -> ModelAPI:
    from repro.models import encdec as E

    def prefill(p, b, ctx):
        return E.prefill(p, b["tokens"], b["frames"], cfg, ctx)

    return ModelAPI(
        cfg,
        lambda k: E.init_params(k, cfg),
        lambda p, b, ctx: E.loss_fn(p, b, cfg, ctx),
        prefill,
        lambda p, c, t, ctx: E.decode_step(p, c, t, cfg, ctx),
        lambda batch, max_len: E.make_caches(cfg, batch, max_len),
        _lm_input_specs(cfg))


def build_model(cfg: ModelConfig) -> ModelAPI:
    fam = cfg.family
    if fam in ("dense", "moe", "vlm"):
        return _build_transformer(cfg)
    if fam == "ssm":
        return _build_ssm(cfg)
    if fam == "hybrid":
        return _build_hybrid(cfg)
    if fam == "audio":
        return _build_encdec(cfg)
    raise ValueError(fam)


# ---------------------------------------------------------------------------
# input_specs — ShapeDtypeStruct stand-ins for every model input
# ---------------------------------------------------------------------------

def _lm_input_specs(cfg: ModelConfig):
    f32 = jnp.dtype(jnp.float32)
    i32 = jnp.dtype(jnp.int32)

    def specs(shape: ShapeConfig) -> Dict[str, Any]:
        B, S = shape.global_batch, shape.seq_len
        sd = jax.ShapeDtypeStruct
        if shape.mode == "decode":
            return {"tokens": sd((B,), i32)}
        out: Dict[str, Any] = {}
        if cfg.family == "audio":
            out["frames"] = sd((B, cfg.encoder.n_frames, cfg.d_model), f32)
        s_text = S
        if cfg.family == "vlm":
            out["vision_embeds"] = sd((B, cfg.n_vision_tokens, cfg.d_model), f32)
            s_text = S - cfg.n_vision_tokens
        out["tokens"] = sd((B, s_text), i32)
        if shape.mode == "train":
            out["labels"] = sd((B, s_text), i32)
        return out

    return specs


def decode_cache_len(shape: ShapeConfig) -> int:
    return shape.seq_len + DECODE_SLACK


# ---------------------------------------------------------------------------
# parameter counting (exact, via eval_shape — no allocation)
# ---------------------------------------------------------------------------

def count_params(cfg: ModelConfig, active_only: bool = False) -> int:
    api = build_model(cfg)
    shapes = jax.eval_shape(api.init, jax.random.key(0))
    leaves = jax.tree_util.tree_leaves_with_path(shapes)
    total = 0
    for path, leaf in leaves:
        keys = [getattr(p, "key", getattr(p, "name", str(p))) for p in path]
        n = int(np.prod(leaf.shape))
        if "scale" in keys and any(k in ("w", "table") for k in keys):
            continue                        # int8 quant scales aren't params
        if active_only and cfg.moe is not None and any(
                k in ("w_gate", "w_up", "w_down") for k in keys) and \
                "moe" in keys:
            n = n * cfg.moe.experts_per_token // cfg.moe.num_experts
        total += n
    return total
