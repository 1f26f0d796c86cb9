"""The plain float32 reference against the program's model code, at reduced
widths that keep both configurations' features: GQA 7:1 and 2:1, head size
64 and 128, QKV biases, tied and untied unembeddings; and the seeded
weights it shares with the served path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench import weights as W

REF = harness.load_module("references", "dense_gqa")
ARCH = harness.load_module("architectures", "dense_gqa")


def _small(name, **kw):
    cfg = harness.load_json("configs", name)
    cfg = dict(cfg, num_hidden_layers=2, intermediate_size=96,
               vocab_size=256, **kw)
    return cfg


CASES = {
    # qwen2's ratios: 14 query heads over 2 KV heads of 64, bias, tied
    "qwen2-like": _small("qwen2-0.5b", hidden_size=112),
    # internlm2's: 16 over 8 of 128, no bias, untied, eps 1e-5
    "internlm2-like": _small("internlm2-1.8b", hidden_size=128),
}


def _program_logits(cfg, seed, tokens):
    """All-position logits of the program's full-sequence forward, in f32
    at the highest precision, on the benchmark's weights."""
    from repro.models import NULL_CTX, build_model, transformer
    mcfg = ARCH.program_config(dict(cfg, torch_dtype="float32"))
    api = build_model(mcfg)
    shapes = jax.eval_shape(api.init, jax.random.key(0))
    params = W.to_program_tree(
        ARCH, W.stacked(ARCH, cfg, W.root_key(seed), jnp.float32), shapes)
    with jax.default_matmul_precision("highest"):
        h, _ = transformer.forward_hidden(params, jnp.asarray(tokens), mcfg,
                                          NULL_CTX, train=False)
        table = transformer.unembed_table(params, mcfg)
        return np.asarray(jnp.einsum("bsd,vd->bsv", h, table))


@pytest.mark.parametrize("case", sorted(CASES))
def test_reference_matches_program(case):
    cfg = CASES[case]
    tokens = np.random.default_rng(0).integers(0, cfg["vocab_size"], (2, 32),
                                               dtype=np.int32)
    pos = np.broadcast_to(np.arange(32), (2, 32))
    want = _program_logits(cfg, 5, tokens)
    got = np.asarray(REF.logits(cfg, W.root_key(5), tokens, pos))
    scale = np.abs(want).max()
    assert np.abs(got - want).max() <= 1e-4 * scale, case


def test_right_padding_changes_no_scored_position():
    cfg = CASES["qwen2-like"]
    rng = np.random.default_rng(1)
    row = rng.integers(0, cfg["vocab_size"], 20, dtype=np.int32)
    a = np.zeros((1, 32), np.int32)
    b = rng.integers(0, cfg["vocab_size"], (1, 32), dtype=np.int32)
    a[0, :20] = b[0, :20] = row
    pos = np.arange(20)[None]
    la = np.asarray(REF.logits(cfg, W.root_key(2), a, pos))
    lb = np.asarray(REF.logits(cfg, W.root_key(2), b, pos))
    np.testing.assert_allclose(la, lb, rtol=1e-5, atol=1e-5)


def test_weights_exact_in_bf16_and_equal_layer_by_layer():
    cfg = CASES["internlm2-like"]
    key = W.root_key(2**33 + 7)
    whole = W.stacked(ARCH, cfg, key, jnp.float32)
    for name, leaf in whole.items():
        assert jnp.array_equal(leaf.astype(jnp.bfloat16).astype(jnp.float32),
                               leaf), name
    one = jax.jit(lambda k, i: W.layer(ARCH, cfg, k, i))(key, jnp.uint32(1))
    for name, leaf in one.items():
        assert jnp.array_equal(leaf, whole[name][1]), name
    assert jnp.array_equal(W.global_leaf(ARCH, cfg, key, "unembed"),
                           whole["unembed"])


def test_root_key_uses_every_bit_of_a_large_seed():
    a = jax.random.key_data(W.root_key(2**31 + 5))
    b = jax.random.key_data(W.root_key(2**31 + 5 + 2**32))
    c = jax.random.key_data(W.root_key(5))
    assert not jnp.array_equal(a, b) and not jnp.array_equal(a, c)
