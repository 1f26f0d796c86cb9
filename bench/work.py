"""Work a dense GQA decoder needs, counted from a configuration's sizes.

These counts are the yardstick of the roofline and utilization metrics:
what the algorithm has to read and compute, at the configuration's stated
dtype, whatever a program happens to read or compute today. All take the
configuration dict of ``bench/configs/<name>.json``.
"""
from __future__ import annotations

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


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
    n_layers, d, _, _, _, _, vocab = _dims(cfg)
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
