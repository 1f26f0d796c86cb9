"""Work counts of the dense architecture module and the peaks of the
benchmark, pinned against the program's own parameter shapes
(``jax.eval_shape``: no weights are made)."""
import json
import math

import jax
import pytest

from bench import harness
from bench import weights as W

dense = harness.load_module("architectures", "dense_gqa")


def _cfg(name):
    return harness.load_json("configs", name)


def _program_param_bytes(cfg):
    from repro.models import build_model
    api = build_model(dense.program_config(cfg))
    shapes = jax.eval_shape(api.init, jax.random.key(0))
    return sum(math.prod(a.shape) * a.dtype.itemsize
               for a in jax.tree.leaves(shapes))


@pytest.mark.parametrize("name,weights,kv", [
    ("qwen2-0.5b", 988_065_536, 12_288),
    ("internlm2-1.8b", 3_778_220_032, 98_304),
])
def test_weight_and_kv_bytes(name, weights, kv):
    cfg = _cfg(name)
    assert dense.weight_bytes(cfg) == weights
    assert _program_param_bytes(cfg) == weights
    assert dense.kv_bytes_per_token(cfg) == kv


def test_decode_weight_bytes_skip_only_an_untied_input_table():
    q, i = _cfg("qwen2-0.5b"), _cfg("internlm2-1.8b")
    assert dense.decode_weight_bytes(q) == dense.weight_bytes(q)
    table = i["vocab_size"] * i["hidden_size"] * 2
    assert dense.decode_weight_bytes(i) == dense.weight_bytes(i) - table


def test_flops_add_up():
    cfg = _cfg("qwen2-0.5b")
    # a 3-token prompt is 3 tokens of context 1, 2, 3 with one unembedding
    by_token = sum(dense.token_flops(cfg, c, logits=False) for c in (1, 2, 3))
    unembed = 2 * cfg["vocab_size"] * cfg["hidden_size"]
    assert dense.prompt_flops(cfg, 3) == by_token + unembed
    # 4 generated tokens: 3 decode steps at contexts 11, 12, 13
    assert dense.decode_flops(cfg, 10, 4) == sum(
        dense.token_flops(cfg, c, logits=True) for c in (11, 12, 13))


def test_benchmark_weights_fill_the_program_tree():
    """The benchmark's weight layout covers every program parameter, shape
    for shape, for both configurations."""
    from repro.models import build_model
    for name in ("qwen2-0.5b", "internlm2-1.8b"):
        cfg = _cfg(name)
        arch = harness.architecture(cfg)
        api = build_model(arch.program_config(cfg))
        shapes = jax.eval_shape(api.init, jax.random.key(0))
        out = jax.eval_shape(
            lambda k: W.to_program_tree(
                arch, W.stacked(arch, cfg, k, jax.numpy.bfloat16), shapes),
            W.root_key(1))
        assert jax.tree.structure(out) == jax.tree.structure(shapes)


def test_peak_table():
    peaks = harness.peaks_for("TPU v5 lite")
    assert peaks["bf16_flops_per_s"] == 197e12
    assert peaks["hbm_bytes_per_s"] == 819e9
    assert peaks["hbm_bytes"] == 16e9
    assert "Google Cloud" in peaks["source"]
    with pytest.raises(KeyError):
        harness.peaks_for("TPU v9 imaginary")
    json.loads((harness.BENCH / "devices.json").read_text())
