"""Model / run configuration system.

Every architecture in the assigned pool is expressed as a ``ModelConfig``.
The config is a plain frozen dataclass (hashable → usable as an AOT compile-cache
key in runtime/static_runtime.py, mirroring the paper's static shard→core maps).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Optional, Tuple

# ---------------------------------------------------------------------------
# Block kinds for heterogeneous stacks (recurrentgemma interleaves RG-LRU and
# local attention; mamba2 is all-SSD; everything else is uniform attention).
# ---------------------------------------------------------------------------
ATTN = "attn"            # full (global) GQA attention
LOCAL_ATTN = "local"     # sliding-window GQA attention
RGLRU = "rglru"          # RG-LRU recurrent block (Griffin)
SSD = "ssd"              # Mamba-2 state-space-duality block


@dataclass(frozen=True)
class MoEConfig:
    num_experts: int
    experts_per_token: int          # top-k
    expert_d_ff: int                # per-expert hidden size
    # capacity factor for expert-parallel dispatch (tokens per expert slot)
    capacity_factor: float = 1.25
    # number of dense (shared) ffn units run for every token, 0 for pure MoE
    num_shared_experts: int = 0
    router_jitter: float = 0.0
    # expert parallelism seen from one chip: the router still routes over
    # all ``num_experts``, but this chip holds only experts
    # [held_offset, held_offset + held_experts) and computes their part of
    # the result, dropless (models/moe.py::moe_held). 0 → every expert is
    # held and dispatch is the capacity-bounded path (moe_ffn)
    held_experts: int = 0
    held_offset: int = 0


@dataclass(frozen=True)
class YaRNConfig:
    """YaRN scaling of a rotary table (``common.yarn_freqs``): low
    frequencies divided by ``factor``, high ones kept, a linear ramp between
    the dimensions that turn ``beta_fast`` and ``beta_slow`` times over
    ``original_max_position_embeddings``; cos and sin scaled by
    ``attention_factor``."""
    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0


@dataclass(frozen=True)
class SSMConfig:
    d_state: int = 128
    head_dim: int = 64
    expand: int = 2                 # d_inner = expand * d_model
    conv_width: int = 4
    n_groups: int = 1
    chunk: int = 256                # SSD chunk length for train/prefill

    def d_inner(self, d_model: int) -> int:
        return self.expand * d_model

    def n_heads(self, d_model: int) -> int:
        return self.d_inner(d_model) // self.head_dim


@dataclass(frozen=True)
class RGLRUConfig:
    lru_width: int = 0              # 0 → d_model
    conv_width: int = 4
    block_pattern: Tuple[str, ...] = (RGLRU, RGLRU, LOCAL_ATTN)
    window: int = 2048              # local attention sliding window


@dataclass(frozen=True)
class EncoderConfig:
    """Encoder stack for enc-dec (whisper) / frontend (vlm) archs."""
    n_layers: int = 0
    n_frames: int = 1500            # precomputed frame/patch embeddings (stub frontend)
    is_causal: bool = False


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                     # dense | moe | audio | vlm | hybrid | ssm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 128
    # --- normalization / activation / position ---
    norm: str = "rmsnorm"           # rmsnorm | layernorm
    act: str = "swiglu"             # swiglu | geglu | gelu_mlp
    rope_theta: float = 10000.0
    pos: str = "rope"               # rope | learned | sinusoidal
    qkv_bias: bool = False
    tie_embeddings: bool = False
    norm_eps: float = 1e-6
    # per-layer attention kind: layer l attends the ``layer_windows[l]``
    # positions ending at the query (itself included), 0 → every position.
    # Masked over the one full-extent KV stack. () → every layer full
    layer_windows: Tuple[int, ...] = ()
    # YaRN on the full-attention layers' rotary table (windowed layers keep
    # the plain table of rope_theta); None → plain everywhere
    yarn: Optional[YaRNConfig] = None
    # --- optional sub-configs ---
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    rglru: Optional[RGLRUConfig] = None
    encoder: Optional[EncoderConfig] = None
    # --- vlm stub ---
    n_vision_tokens: int = 0        # prepended precomputed patch embeddings
    # --- numerics ---
    dtype: str = "bfloat16"         # activation/weight compute dtype
    kv_dtype: str = "bfloat16"      # "int8" enables quantized KV (paper default)
    weight_int8: bool = False       # int8 weight storage (paper default INT8)
    # --- tiered KV cache (DESIGN.md §7): hot_window > 0 splits each slot's
    # KV into a hot ring (most recent tokens, compute dtype, exact) and a
    # cold tier (older tokens, kv_cold_dtype, demoted in kv_cold_block
    # chunks). Geometry is a build-time static — like a_shards, it is baked
    # into the compiled programs and never retraces.
    hot_window: int = 0             # 0 → flat (untiered) KV cache
    kv_cold_dtype: str = "int8"     # cold tier storage: bfloat16 | int8 | int4
    kv_cold_block: int = 16         # demotion granularity (tokens)
    # --- long-context capability flag (sub-quadratic decoding) ---
    subquadratic: bool = False
    # --- source provenance: [source; verified-tier] from the assignment ---
    source: str = ""

    # ------------------------------------------------------------------
    def block_kinds(self) -> Tuple[str, ...]:
        """Per-layer temporal-mixing kind for the decoder stack."""
        if self.family == "ssm":
            return tuple([SSD] * self.n_layers)
        if self.family == "hybrid":
            pat = self.rglru.block_pattern
            return tuple(pat[i % len(pat)] for i in range(self.n_layers))
        return tuple([ATTN] * self.n_layers)

    # --- parameter counting (exact, from shapes) -----------------------
    def param_count(self) -> int:
        from repro.models.registry import count_params
        return count_params(self)

    def active_param_count(self) -> int:
        from repro.models.registry import count_params
        return count_params(self, active_only=True)

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    # --- reduced config for CPU smoke tests ----------------------------
    def reduced(self) -> "ModelConfig":
        """Same family/shape *structure*, tiny sizes — for smoke tests."""
        kw = dict(
            n_layers=min(self.n_layers, 3 if self.family != "hybrid" else 3),
            d_model=128,
            n_heads=4,
            n_kv_heads=min(self.n_kv_heads, 2),
            head_dim=32,
            d_ff=256,
            vocab_size=512,
        )
        if self.moe is not None:
            kw["moe"] = dataclasses.replace(
                self.moe, num_experts=4,
                experts_per_token=min(self.moe.experts_per_token, 2),
                expert_d_ff=64)
        if self.ssm is not None:
            kw["ssm"] = dataclasses.replace(self.ssm, d_state=16, head_dim=32, chunk=16)
        if self.rglru is not None:
            kw["rglru"] = dataclasses.replace(self.rglru, lru_width=0, window=32)
        if self.encoder is not None:
            kw["encoder"] = dataclasses.replace(self.encoder, n_layers=2, n_frames=16)
        if self.n_vision_tokens:
            kw["n_vision_tokens"] = 4
        return self.replace(name=self.name + "-reduced", **kw)
