"""Every architecture-specific step goes through the module a configuration
names: the dense module gives the program the same configuration, the
served weights the same bits and the roofline the same needed work as the
code it replaced (numbers pinned from that code), and a configuration of a
new architecture runs on files in a fresh root alone."""
import dataclasses
import hashlib
import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, tiny
from bench import weights as W
from repro.configs.base import ModelConfig

DENSE = dict(family="dense", norm="rmsnorm", act="swiglu", dtype="bfloat16")
PROGRAM_CONFIGS = {
    "qwen2-0.5b": ModelConfig(
        name="qwen2-0.5b", n_layers=24, d_model=896, n_heads=14,
        n_kv_heads=2, d_ff=4864, vocab_size=151936, head_dim=64,
        rope_theta=1000000.0, qkv_bias=True, tie_embeddings=True,
        norm_eps=1e-06, **DENSE),
    "internlm2-1.8b": ModelConfig(
        name="internlm2-1.8b", n_layers=24, d_model=2048, n_heads=16,
        n_kv_heads=8, d_ff=8192, vocab_size=92544, head_dim=128,
        rope_theta=1000000.0, qkv_bias=False, tie_embeddings=False,
        norm_eps=1e-05, **DENSE),
    "tiny": ModelConfig(
        name="tiny", n_layers=4, d_model=128, n_heads=8, n_kv_heads=2,
        d_ff=256, vocab_size=2048, head_dim=32, rope_theta=10000.0,
        qkv_bias=True, tie_embeddings=False, norm_eps=1e-06, **DENSE),
}


def _cfg(name):
    return tiny.CONFIG if name == "tiny" else harness.load_json("configs",
                                                                name)


@pytest.mark.parametrize("name", sorted(PROGRAM_CONFIGS))
def test_dense_program_config_is_unchanged(name):
    cfg = _cfg(name)
    got = harness.architecture(cfg).program_config(cfg)
    want = PROGRAM_CONFIGS[name]
    for f in dataclasses.fields(ModelConfig):
        assert getattr(got, f.name) == getattr(want, f.name), f.name


@pytest.mark.parametrize("key,value", [("num_experts", 64),
                                       ("sliding_window", 1024),
                                       ("hidden_act", "gelu")])
def test_dense_program_config_refuses_what_it_cannot_map(key, value):
    arch = harness.architecture(tiny.CONFIG)
    with pytest.raises(ValueError):
        arch.program_config(dict(tiny.CONFIG, **{key: value}))


def _digest(a) -> str:
    return hashlib.sha256(np.asarray(a, np.float32).tobytes()).hexdigest()[:16]


# sha256 of the float32 bytes of each leaf of the tiny configuration at seed
# 7, recorded from the weights code before the architecture modules
LAYER0 = {"ln1": "62b06ddb2b5ad6be", "wq": "208c30936e9cd60a",
          "bq": "bcfa41ff2a933f8d", "wk": "e3e7a0ea78d2ac12",
          "bk": "a741c33cef42937d", "wv": "7699616def937219",
          "bv": "df33b003d27bb676", "wo": "91536e122cf4e8a7",
          "ln2": "9f9d0e72a3dffa2a", "w_gate": "36f9fd3423e53562",
          "w_up": "26a0ed49e5d80446", "w_down": "296031965861f4fb"}
LAYER3 = {"ln1": "dbb5119e9e3c310b", "wq": "941113761c34f3ac",
          "bq": "0d614780c0baefac", "wk": "d72e6bb3e7492b6a",
          "bk": "3e33b5cc9799314b", "wv": "049351dee5009d6f",
          "bv": "f4910f4d22170afc", "wo": "bb814f9e65caac29",
          "ln2": "c499a758a4126f02", "w_gate": "ab30ad06e095999c",
          "w_up": "dc147256428ee164", "w_down": "6ad99136516d5f2e"}
GLOBAL = {"embed": "00772cfd696686e6", "ln_f": "7182525abb468d62",
          "unembed": "417f9530d97eceee"}


def test_dense_weights_are_the_same_bits():
    arch = harness.architecture(tiny.CONFIG)
    key = W.root_key(7)
    got = W.layer(arch, tiny.CONFIG, key, 0)
    assert {k: _digest(v) for k, v in got.items()} == LAYER0
    got = jax.jit(lambda k, i: W.layer(arch, tiny.CONFIG, k, i))(
        key, jnp.uint32(3))
    assert {k: _digest(v) for k, v in got.items()} == LAYER3
    for name, want in GLOBAL.items():
        assert _digest(W.global_leaf(arch, tiny.CONFIG, key, name)) == want


class _Sched:
    """The slot cursors a decode block is dispatched with."""

    def __init__(self, rows):
        self.positions = np.array([p for p, _, _ in rows])
        self.remaining = np.array([r for _, r, _ in rows])
        self._live = np.array([a for _, _, a in rows])

    def decode_active(self):
        return self._live


# (cursor, tokens left, decoding) of each slot, per block; the parked slot
# of the first block counts for nothing
BLOCKS = [[(100, 8, True), (2000, 3, True), (55, 8, False), (3960, 128, True)],
          [(0, 1, True)],
          [(4000, 8, True), (17, 8, True), (511, 8, True), (1023, 2, True),
           (64, 5, True)]]


def _count(cfg, arch, blocks):
    rec = harness.Recorder(cfg, arch, 10.0, "unused")
    for rows in blocks:
        rec.engine = types.SimpleNamespace(_sched=_Sched(rows))
        rec._count_block()
    assert rec.error is None
    return rec.need


@pytest.mark.parametrize("name,nbytes,flops", [
    # the sums of the per-row count before the architecture modules
    ("qwen2-0.5b", 17_746_669_312, 57_030_930_432),
    ("internlm2-1.8b", 65_382_158_336, 188_539_797_504),
])
def test_dense_decode_need_is_unchanged(name, nbytes, flops):
    cfg = _cfg(name)
    need = _count(cfg, harness.architecture(cfg), BLOCKS)
    assert need == {"blocks": 3, "bytes": nbytes, "flops": flops}


@pytest.mark.parametrize("name,prompt,decode,weights", [
    ("qwen2-0.5b", 1_170_584_461_312, 13_410_066_432, 988_065_536),
    ("internlm2-1.8b", 4_751_558_836_224, 44_341_788_672, 3_778_220_032),
])
def test_dense_request_counts_are_unchanged(name, prompt, decode, weights):
    cfg = _cfg(name)
    arch = harness.architecture(cfg)
    assert arch.prompt_flops(cfg, 1500) == prompt
    assert arch.decode_flops(cfg, 1500, 13) == decode
    assert arch.weight_bytes(cfg) == weights


TOY_CONFIG = dict(tiny.CONFIG, name="toy", architecture="toy",
                  sliding_window=16)

# A dense variant: the first norm renamed, and every second layer attending
# only the last ``sliding_window`` positions in the needed work.
TOY_ARCHITECTURE = '''
from bench import harness

dense = harness.load_module("architectures", "dense_gqa")
RENAMED = {"ln1": "attn_norm"}
BACK = {v: k for k, v in RENAMED.items()}

LAYER_LEAVES = tuple(RENAMED.get(n, n) for n in dense.LAYER_LEAVES)
GLOBAL_LEAVES = dense.GLOBAL_LEAVES
PROGRAM_PATHS = {p: RENAMED.get(n, n) for p, n in dense.PROGRAM_PATHS.items()}


def program_config(cfg):
    return dense.program_config(
        {k: v for k, v in cfg.items() if k != "sliding_window"})


def layer_shapes(cfg):
    return {RENAMED.get(n, n): s for n, s in dense.layer_shapes(cfg).items()}


def global_shapes(cfg):
    return dense.global_shapes(cfg)


def fan_in(name, shape):
    return dense.fan_in(BACK.get(name, name), shape)


def weight_bytes(cfg):
    return dense.weight_bytes(cfg)


def micro_step_need(cfg, contexts):
    layers, w = cfg["num_hidden_layers"], cfg["sliding_window"]
    per_layer = dense.kv_bytes_per_token(cfg) // layers
    attended = sum(c if i % 2 == 0 else min(c, w)
                   for c in contexts for i in range(layers))
    return dense.decode_weight_bytes(cfg) + attended * per_layer, len(contexts)


def prompt_flops(cfg, n):
    return dense.prompt_flops(cfg, n)


def decode_flops(cfg, prompt_len, n_generated):
    return dense.decode_flops(cfg, prompt_len, n_generated)
'''


def _bench_files():
    return {p: (p.stat().st_size, p.stat().st_mtime_ns)
            for p in harness.BENCH.rglob("*")
            if p.is_file() and "__pycache__" not in p.parts}


def test_a_new_architecture_is_files_alone(tmp_path):
    before = _bench_files()
    (tmp_path / "configs").mkdir()
    (tmp_path / "architectures").mkdir()
    (tmp_path / "configs" / "toy.json").write_text(json.dumps(TOY_CONFIG))
    (tmp_path / "architectures" / "toy.py").write_text(TOY_ARCHITECTURE)

    cfg = harness.load_json("configs", "toy", tmp_path)
    arch = harness.architecture(cfg, tmp_path)
    with pytest.raises(FileNotFoundError):
        harness.architecture(cfg)              # not in the benchmark's root
    # mapped
    from repro.models import build_model
    api = build_model(arch.program_config(cfg))
    # weighted: the renamed leaf fills the program's first norm
    shapes = jax.eval_shape(api.init, jax.random.key(0))
    weights = W.stacked(arch, cfg, W.root_key(3), jnp.bfloat16)
    params = W.to_program_tree(arch, weights, shapes)
    assert "ln1" not in weights
    assert jnp.array_equal(params["blocks"]["ln1"]["scale"],
                           weights["attn_norm"])
    assert jax.tree.structure(params) == jax.tree.structure(shapes)
    # counted, one micro-step at a time: contexts (11, 3) then (12,) fit
    # the window of 16 in every layer; 40 and 41 are cut to 16 in layers 1
    # and 3 of the 4
    need = _count(cfg, arch, [[(10, 2, True), (2, 1, True)]])
    dense = harness.load_module("architectures", "dense_gqa")
    per_layer = dense.kv_bytes_per_token(cfg) // cfg["num_hidden_layers"]
    wb = dense.decode_weight_bytes(cfg)
    assert need == {"blocks": 1, "flops": 3,
                    "bytes": 2 * wb + 4 * (11 + 3 + 12) * per_layer}
    need = _count(cfg, arch, [[(39, 2, True)]])
    assert need["bytes"] == 2 * wb + (2 * (40 + 41) + 4 * 16) * per_layer
    # a program leaf without a benchmark weight is still refused
    del weights["attn_norm"]
    with pytest.raises(ValueError, match="blocks/ln1/scale"):
        W.to_program_tree(arch, weights, shapes)
    assert _bench_files() == before
