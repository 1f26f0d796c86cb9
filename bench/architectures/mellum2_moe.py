"""Mellum2 (JetBrains Mellum2-12B-A2.5B) as one chip of expert parallelism:
a decoder whose every feed-forward is a sparse layer of SwiGLU experts
routed top-k over a softmax and renormalised, and whose attention layers
are of two kinds, sliding-window and full, in a repeating pattern.

Per layer: RMSNorm, q/k/v projections without bias, rotary embedding
(the plain table of ``rope_theta`` on sliding layers, YaRN on full ones),
causal GQA over the last ``sliding_window`` positions or over all of them,
output projection and residual; RMSNorm, the expert layer, residual. A
final RMSNorm and an untied unembedding.

The configuration holds ``num_experts`` of each layer's experts (listed in
its ``reduced``); ``expert_parallel`` states the published count, the
router's width, and which share this chip holds. The program computes only
the held experts' part of each layer, and the needed work below counts
only what this chip does.

This module keeps the contract of ``dense_gqa.py``'s docstring:
``program_config``, the leaf layout (``LAYER_LEAVES``, ``GLOBAL_LEAVES``,
``layer_shapes``, ``global_shapes``, ``fan_in``, ``PROGRAM_PATHS``) and the
needed work (``weight_bytes``, ``micro_step_need``, ``prompt_flops``,
``decode_flops``), operations counted 2 per multiply-add.

Expert work is an expectation under uniform routing, so the needed work of
a micro-step is a rounded rational number: each of a micro-step's n rows
picks ``num_experts_per_tok`` of the router's E experts, so a held expert
is touched by some row with probability 1 - (1 - k/E)^n and its weights
are read that often; each token runs k x held / E held experts.
"""
from __future__ import annotations

from fractions import Fraction
from typing import Dict, Sequence, Tuple

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}
SLIDING, FULL = "sliding_attention", "full_attention"

# -- the program's configuration ------------------------------------------

# keys that describe a configuration file and set nothing in the program
DESCRIPTIVE = ("source", "paper", "reference", "architecture", "reduced",
               "assumed", "notes", "max_new_tokens", "model_type",
               # the published context; the cells serve at most 4096
               "max_position_embeddings",
               # superseded by the explicit layer_types
               "max_window_layers")
MAPPED = ("name", "family", "torch_dtype", "num_hidden_layers", "hidden_size",
          "intermediate_size", "num_attention_heads", "num_key_value_heads",
          "head_dim", "vocab_size", "hidden_act", "rms_norm_eps",
          "attention_bias", "tie_word_embeddings", "layer_types",
          "mlp_layer_types", "sliding_window", "use_sliding_window",
          "rope_parameters", "moe_intermediate_size", "num_experts",
          "num_experts_per_tok", "norm_topk_prob", "expert_parallel")
YARN_KEYS = ("rope_type", "rope_theta", "factor",
             "original_max_position_embeddings", "beta_fast", "beta_slow",
             "attention_factor")


def _refuse(cfg, what):
    raise ValueError(f"mellum2_moe cannot map {what} of configuration "
                     f"{cfg.get('name')!r}")


def layer_windows(cfg) -> Tuple[int, ...]:
    """Each layer's attention window, 0 for a full layer."""
    w = cfg["sliding_window"] if cfg["use_sliding_window"] else 0
    return tuple(w if t == SLIDING else 0 for t in cfg["layer_types"])


def router_experts(cfg) -> int:
    """The router's width: the published number of experts per layer."""
    return cfg["expert_parallel"]["num_experts"]


def program_config(cfg: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import ModelConfig, MoEConfig, YaRNConfig
    unknown = sorted(set(cfg) - set(DESCRIPTIVE) - set(MAPPED))
    if unknown:
        _refuse(cfg, f"the keys {unknown}")
    if cfg["hidden_act"] != "silu":
        _refuse(cfg, f"the activation {cfg['hidden_act']!r}")
    if not cfg["norm_topk_prob"]:
        _refuse(cfg, "unnormalised top-k weights")
    if set(cfg["mlp_layer_types"]) != {"sparse"}:
        _refuse(cfg, "dense feed-forward layers")
    kinds = cfg["layer_types"]
    if len(kinds) != cfg["num_hidden_layers"] or not set(kinds) <= {SLIDING,
                                                                     FULL}:
        _refuse(cfg, f"the layer types {sorted(set(kinds))}")
    rope = cfg["rope_parameters"]
    full, sliding = rope.get(FULL, {}), rope.get(SLIDING, {})
    if set(rope) - {FULL, SLIDING} or set(full) - set(YARN_KEYS) or \
            full.get("rope_type") != "yarn" or \
            sliding != {"rope_type": "default",
                        "rope_theta": full.get("rope_theta")}:
        _refuse(cfg, "rope_parameters other than YaRN on full layers and "
                "the same plain table on sliding ones")
    ep = cfg["expert_parallel"]
    held = cfg["num_experts"]
    if set(ep) != {"num_experts", "chips", "held_offset"} or \
            ep["num_experts"] != held * ep["chips"] or \
            not 0 <= ep["held_offset"] <= ep["num_experts"] - held:
        _refuse(cfg, f"the expert-parallel share {ep}")
    return ModelConfig(
        name=cfg["name"], family=cfg["family"],
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        # the published dense width; no layer here has a dense FFN
        d_ff=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"], head_dim=cfg["head_dim"],
        norm="rmsnorm", act="swiglu", rope_theta=float(full["rope_theta"]),
        qkv_bias=cfg["attention_bias"],
        tie_embeddings=cfg["tie_word_embeddings"],
        norm_eps=cfg["rms_norm_eps"], dtype=cfg["torch_dtype"],
        moe=MoEConfig(num_experts=ep["num_experts"],
                      experts_per_token=cfg["num_experts_per_tok"],
                      expert_d_ff=cfg["moe_intermediate_size"],
                      held_experts=held, held_offset=ep["held_offset"]),
        layer_windows=layer_windows(cfg),
        yarn=YaRNConfig(
            factor=float(full["factor"]),
            original_max_position_embeddings=full[
                "original_max_position_embeddings"],
            beta_fast=float(full["beta_fast"]),
            beta_slow=float(full["beta_slow"]),
            attention_factor=float(full["attention_factor"])))


# -- the weight layout ----------------------------------------------------
#
# ``embed`` (V, D); per layer ``ln1``/``ln2`` (D,), ``wq`` (D, Hq*hd),
# ``wk``/``wv`` (D, Hkv*hd), ``wo`` (Hq*hd, D), ``router`` (D, E) over all
# E experts, ``w_gate``/``w_up`` (held, D, F) and ``w_down`` (held, F, D)
# for the held experts; ``ln_f`` (D,); ``unembed`` (V, D).

LAYER_LEAVES = ("ln1", "wq", "wk", "wv", "wo", "ln2", "router", "w_gate",
                "w_up", "w_down")
GLOBAL_LEAVES = ("embed", "ln_f", "unembed")

PROGRAM_PATHS = {
    ("embed", "table"): "embed", ("unembed", "table"): "unembed",
    ("ln_f", "scale"): "ln_f",
    ("blocks", "ln1", "scale"): "ln1", ("blocks", "ln2", "scale"): "ln2",
    ("blocks", "attn", "wq", "w"): "wq", ("blocks", "attn", "wk", "w"): "wk",
    ("blocks", "attn", "wv", "w"): "wv", ("blocks", "attn", "wo", "w"): "wo",
    ("blocks", "moe", "router", "w"): "router",
    ("blocks", "moe", "w_gate"): "w_gate", ("blocks", "moe", "w_up"): "w_up",
    ("blocks", "moe", "w_down"): "w_down",
}


def layer_shapes(cfg) -> Dict[str, Tuple[int, ...]]:
    d, f, h = cfg["hidden_size"], cfg["moe_intermediate_size"], \
        cfg["num_experts"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return {"ln1": (d,), "wq": (d, q), "wk": (d, kv), "wv": (d, kv),
            "wo": (q, d), "ln2": (d,), "router": (d, router_experts(cfg)),
            "w_gate": (h, d, f), "w_up": (h, d, f), "w_down": (h, f, d)}


def global_shapes(cfg) -> Dict[str, Tuple[int, ...]]:
    v, d = cfg["vocab_size"], cfg["hidden_size"]
    return {"embed": (v, d), "ln_f": (d,), "unembed": (v, d)}


def fan_in(name: str, shape):
    if name.startswith("ln"):
        return "norm"
    if name in ("embed", "unembed"):
        return shape[-1]
    # an expert's matrix is (held, fan-in, fan-out)
    return shape[-2]


# -- needed work ----------------------------------------------------------

def dtype_bytes(cfg) -> int:
    return DTYPE_BYTES[cfg["torch_dtype"]]


def attention_params(cfg) -> int:
    """One layer's q, k, v and o projections."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return 2 * d * hq * hd + 2 * d * hkv * hd


def expert_params(cfg) -> int:
    """One expert's three SwiGLU matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def _shared_layer_params(cfg) -> int:
    """One layer's parameters outside its experts: attention, router,
    norms."""
    d = cfg["hidden_size"]
    return attention_params(cfg) + d * router_experts(cfg) + 2 * d


def param_count(cfg) -> int:
    """Every parameter this chip holds."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    per_layer = _shared_layer_params(cfg) + cfg["num_experts"] * \
        expert_params(cfg)
    return cfg["num_hidden_layers"] * per_layer + 2 * v * d + d


def weight_bytes(cfg) -> int:
    return param_count(cfg) * dtype_bytes(cfg)


def held_picks_per_token(cfg) -> Fraction:
    """Held experts a token runs, expected under uniform routing."""
    return Fraction(cfg["num_experts_per_tok"] * cfg["num_experts"],
                    router_experts(cfg))


def experts_touched(cfg, rows: int) -> Fraction:
    """Held experts of one layer that some of ``rows`` tokens picks,
    expected under uniform routing."""
    miss = 1 - Fraction(cfg["num_experts_per_tok"], router_experts(cfg))
    return cfg["num_experts"] * (1 - miss ** rows)


def _kinds(cfg) -> Tuple[int, int, int]:
    """(full layers, sliding layers, window)."""
    windows = layer_windows(cfg)
    sliding = sum(1 for w in windows if w)
    return len(windows) - sliding, sliding, max(windows)


def attended(cfg, context: int) -> int:
    """Positions a token attending ``context`` positions (itself included)
    reads, summed over the layers: all of them in a full layer, the last
    ``sliding_window`` in a sliding one."""
    full, sliding, window = _kinds(cfg)
    return full * context + sliding * min(context, window)


def _attended_prompt(cfg, n: int) -> int:
    """``attended`` summed over the tokens of an n-token prompt (token i
    attends i + 1 positions)."""
    full, sliding, w = _kinds(cfg)
    cut = min(n, w)
    return full * n * (n + 1) // 2 + \
        sliding * (cut * (cut + 1) // 2 + (n - cut) * w)


def kv_bytes_per_layer_token(cfg) -> int:
    """K and V of one token in one layer."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * \
        dtype_bytes(cfg)


def _token_flops(cfg, context: int, logits: bool) -> Fraction:
    """Operations of one token attending ``context`` positions: the
    projections and router of every layer, its held experts, QK and PV (4
    per attended position and query dimension), and the unembedding when
    its logits are needed."""
    n_layers, d = cfg["num_hidden_layers"], cfg["hidden_size"]
    shared = attention_params(cfg) + d * router_experts(cfg)
    flops = 2 * n_layers * (shared + held_picks_per_token(cfg)
                            * expert_params(cfg))
    flops += 4 * cfg["num_attention_heads"] * cfg["head_dim"] * \
        attended(cfg, context)
    if logits:
        flops += 2 * cfg["vocab_size"] * d
    return flops


def micro_step_need(cfg, contexts: Sequence[int]) -> Tuple[int, int]:
    """One decode micro-step: the weights outside the experts once (not
    the input embedding, of which it gathers one row per token), the held
    experts its rows touch, the KV each row attends, and each row's
    operations with its logits."""
    n_layers, d = cfg["num_hidden_layers"], cfg["hidden_size"]
    shared = n_layers * _shared_layer_params(cfg) + cfg["vocab_size"] * d + d
    experts = n_layers * experts_touched(cfg, len(contexts)) * \
        expert_params(cfg)
    kv = sum(attended(cfg, c) for c in contexts) * \
        kv_bytes_per_layer_token(cfg)
    nbytes = (shared + experts) * dtype_bytes(cfg) + kv
    flops = sum(_token_flops(cfg, c, True) for c in contexts)
    return round(nbytes), round(flops)


def prompt_flops(cfg, prompt_len: int) -> int:
    """A prompt of ``prompt_len`` tokens: token i attends i + 1 positions,
    and only the last position's logits are needed."""
    attention = 4 * cfg["num_attention_heads"] * cfg["head_dim"]
    total = prompt_len * _token_flops(cfg, 0, False) + \
        attention * _attended_prompt(cfg, prompt_len)
    return round(total + 2 * cfg["vocab_size"] * cfg["hidden_size"])


def decode_flops(cfg, prompt_len: int, n_generated: int) -> int:
    """The decode steps of one request: generated token j (j >= 1) comes
    from the token before it, which attends prompt_len + j positions."""
    return round(sum(_token_flops(cfg, prompt_len + j, True)
                     for j in range(1, n_generated)))
