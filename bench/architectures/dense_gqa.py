"""Dense decoder with grouped-query attention (Qwen2, InternLM2): RMSNorm,
q/k/v projections with optional biases, rotary embedding, causal GQA, a
SwiGLU feed-forward, and an unembedding tied to the embedding or not.

Every architecture-specific step of the benchmark goes through a module of
this kind, ``bench/architectures/<name>.py``, which a configuration names
with its ``"architecture"`` key and ``harness.architecture(cfg)`` finds. A
new architecture is a new such file; the harness, the weights, the metric
readers and the references reach what it defines only through it. Each
module defines:

- ``program_config(cfg)``: the program's ``repro.configs.base.ModelConfig``
  for a configuration file. It raises on a key it cannot map, so a setting
  that the program would silently drop never reaches a run.
- The weight layout. ``LAYER_LEAVES`` and ``GLOBAL_LEAVES`` name every leaf;
  a leaf's index in them is its fold-in id (``bench/weights.py``), so their
  order fixes the served weights of every seed and only grows at the end.
  ``layer_shapes(cfg)`` and ``global_shapes(cfg)`` give the shapes of the
  leaves a configuration has; ``fan_in(name, shape)`` says how a leaf is
  drawn: ``"norm"`` for a norm scale, ``"bias"`` for a bias, or the
  integer fan-in of a matrix, which sets its standard deviation.
  ``PROGRAM_PATHS`` maps each path of the program's parameter tree to the
  leaf that fills it.
- The work the algorithm needs, at the configuration's dtype, whatever a
  program happens to read or compute: ``weight_bytes(cfg)``;
  ``micro_step_need(cfg, contexts)``, the (bytes, operations) of one decode
  micro-step in which row i produces a token attending ``contexts[i]``
  positions, itself included; ``prompt_flops(cfg, n)``, a prompt of n
  tokens; and ``decode_flops(cfg, prompt_len, n_generated)``, the decode
  steps of one request. Operations count 2 per multiply-add.
"""
from __future__ import annotations

from typing import Dict, Sequence, Tuple

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}

# -- the program's configuration ------------------------------------------

# keys that describe a configuration file and set nothing in the program
# (``max_new_tokens`` is the serving engine's output cap, read by the harness)
DESCRIPTIVE = ("source", "paper", "reference", "architecture", "reduced",
               "assumed", "notes", "max_new_tokens")
MAPPED = ("name", "family", "torch_dtype", "num_hidden_layers", "hidden_size",
          "intermediate_size", "num_attention_heads", "num_key_value_heads",
          "head_dim", "vocab_size", "hidden_act", "rms_norm_eps",
          "rope_theta", "qkv_bias", "tie_word_embeddings")


def program_config(cfg: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import ModelConfig
    unknown = sorted(set(cfg) - set(DESCRIPTIVE) - set(MAPPED))
    if unknown:
        raise ValueError(f"dense_gqa cannot map the keys {unknown} of "
                         f"configuration {cfg.get('name')!r}")
    if cfg["hidden_act"] != "silu":
        raise ValueError(f"unsupported activation {cfg['hidden_act']!r}")
    return ModelConfig(
        name=cfg["name"], family=cfg["family"],
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], d_ff=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"], head_dim=cfg["head_dim"],
        norm="rmsnorm", act="swiglu", rope_theta=cfg["rope_theta"],
        qkv_bias=cfg["qkv_bias"], tie_embeddings=cfg["tie_word_embeddings"],
        norm_eps=cfg["rms_norm_eps"], dtype=cfg["torch_dtype"])


# -- the weight layout ----------------------------------------------------
#
# ``embed`` (V, D); per layer ``ln1``/``ln2`` (D,), ``wq`` (D, Hq*hd),
# ``wk``/``wv`` (D, Hkv*hd), ``bq``/``bk``/``bv`` when the configuration has
# QKV biases, ``wo`` (Hq*hd, D), ``w_gate``/``w_up`` (D, F), ``w_down``
# (F, D); ``ln_f`` (D,); ``unembed`` (V, D) when untied.

LAYER_LEAVES = ("ln1", "wq", "bq", "wk", "bk", "wv", "bv", "wo", "ln2",
                "w_gate", "w_up", "w_down")
GLOBAL_LEAVES = ("embed", "ln_f", "unembed")

PROGRAM_PATHS = {
    ("embed", "table"): "embed", ("unembed", "table"): "unembed",
    ("ln_f", "scale"): "ln_f",
    ("blocks", "ln1", "scale"): "ln1", ("blocks", "ln2", "scale"): "ln2",
    ("blocks", "attn", "wq", "w"): "wq", ("blocks", "attn", "wq", "b"): "bq",
    ("blocks", "attn", "wk", "w"): "wk", ("blocks", "attn", "wk", "b"): "bk",
    ("blocks", "attn", "wv", "w"): "wv", ("blocks", "attn", "wv", "b"): "bv",
    ("blocks", "attn", "wo", "w"): "wo",
    ("blocks", "ffn", "w_gate", "w"): "w_gate",
    ("blocks", "ffn", "w_up", "w"): "w_up",
    ("blocks", "ffn", "w_down", "w"): "w_down",
}


def layer_shapes(cfg) -> Dict[str, Tuple[int, ...]]:
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    shapes = {"ln1": (d,), "wq": (d, q), "wk": (d, kv), "wv": (d, kv),
              "wo": (q, d), "ln2": (d,), "w_gate": (d, f), "w_up": (d, f),
              "w_down": (f, d)}
    if cfg["qkv_bias"]:
        shapes.update(bq=(q,), bk=(kv,), bv=(kv,))
    return shapes


def global_shapes(cfg) -> Dict[str, Tuple[int, ...]]:
    v, d = cfg["vocab_size"], cfg["hidden_size"]
    shapes = {"embed": (v, d), "ln_f": (d,)}
    if not cfg["tie_word_embeddings"]:
        shapes["unembed"] = (v, d)
    return shapes


def fan_in(name: str, shape):
    if name.startswith("ln"):
        return "norm"
    if name.startswith("b"):
        return "bias"
    # the tables are (V, D): a row is one token's D inputs of the unembedding
    return shape[-1] if name in ("embed", "unembed") else shape[0]


# -- needed work ----------------------------------------------------------

def _dims(cfg):
    return (cfg["num_hidden_layers"], cfg["hidden_size"],
            cfg["intermediate_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"], cfg["vocab_size"])


def dtype_bytes(cfg) -> int:
    return DTYPE_BYTES[cfg["torch_dtype"]]


def layer_matmul_params(cfg) -> int:
    """Parameters of one layer's matrix multiplications (q, k, v, o and the
    three SwiGLU projections)."""
    _, d, f, hq, hkv, hd, _ = _dims(cfg)
    return d * hq * hd + 2 * d * hkv * hd + hq * hd * d + 3 * d * f


def param_count(cfg) -> int:
    """Every parameter, as the published model stores it."""
    n_layers, d, _, hq, hkv, hd, vocab = _dims(cfg)
    per_layer = layer_matmul_params(cfg) + 2 * d
    if cfg["qkv_bias"]:
        per_layer += (hq + 2 * hkv) * hd
    tables = vocab * d * (1 if cfg["tie_word_embeddings"] else 2)
    return n_layers * per_layer + tables + d


def weight_bytes(cfg) -> int:
    return param_count(cfg) * dtype_bytes(cfg)


def decode_weight_bytes(cfg) -> int:
    """Weight bytes one decode micro-step has to read: all of them but an
    untied input embedding table, of which it gathers one row per slot."""
    _, d, _, _, _, _, vocab = _dims(cfg)
    untied_embed = 0 if cfg["tie_word_embeddings"] else vocab * d
    return (param_count(cfg) - untied_embed) * dtype_bytes(cfg)


def kv_bytes_per_token(cfg) -> int:
    """K and V of one token in every layer."""
    n_layers, _, _, _, hkv, hd, _ = _dims(cfg)
    return 2 * n_layers * hkv * hd * dtype_bytes(cfg)


def token_flops(cfg, context: int, logits: bool) -> int:
    """Operations of one token that attends ``context`` positions (itself
    included): 2 per multiply-add of every layer's projections, 4 per
    attended position and query dimension (QK and PV), and the
    unembedding when the token's logits are needed."""
    n_layers, d, _, hq, _, hd, vocab = _dims(cfg)
    flops = 2 * n_layers * layer_matmul_params(cfg)
    flops += 4 * n_layers * hq * hd * context
    if logits:
        flops += 2 * vocab * d
    return flops


def micro_step_need(cfg, contexts: Sequence[int]) -> Tuple[int, int]:
    """One decode micro-step: the weights once, the KV of each row's true
    context, and each row's operations with its logits."""
    kv = kv_bytes_per_token(cfg)
    return (decode_weight_bytes(cfg) + sum(c * kv for c in contexts),
            sum(token_flops(cfg, c, True) for c in contexts))


def prompt_flops(cfg, prompt_len: int) -> int:
    """A prompt of ``prompt_len`` tokens: token i attends i + 1 positions,
    and only the last position's logits are needed."""
    n_layers, d, _, hq, _, hd, vocab = _dims(cfg)
    total = prompt_len * 2 * n_layers * layer_matmul_params(cfg)
    total += 4 * n_layers * hq * hd * prompt_len * (prompt_len + 1) // 2
    return total + 2 * vocab * d


def decode_flops(cfg, prompt_len: int, n_generated: int) -> int:
    """The decode steps of one request: generated token j (j >= 1, the
    first comes from the prompt's last position) is computed from the
    token before it, which attends prompt_len + j positions."""
    return sum(token_flops(cfg, prompt_len + j, logits=True)
               for j in range(1, n_generated))
