"""Mellum2 as one chip of expert parallelism, at a tiny size of the same
architecture (4 layers sliding, sliding, sliding, full; window 8; 16
experts top-4, 4 held) on the CPU: the program's chunk lane and decode
against the plain reference, the held-expert layer dropless and adding up
over the shares, the YaRN table against hand-computed values, the engine's
pick counters, and the architecture module's mapping, weights and needed
work."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench import weights as W
from repro.configs.base import ModelConfig, MoEConfig, YaRNConfig
from repro.models import NULL_CTX, build_model, common
from repro.models.moe import moe_held

ARCH = harness.load_module("architectures", "mellum2_moe")
REF = harness.load_module("references", "mellum2_moe")
S, F = "sliding_attention", "full_attention"
YARN = {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
        "original_max_position_embeddings": 64, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.2772588722239782}
TINY = {
    "name": "tiny-mellum", "architecture": "mellum2_moe",
    "reference": "mellum2_moe", "family": "moe", "torch_dtype": "bfloat16",
    "model_type": "mellum", "attention_bias": False, "head_dim": 16,
    "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 128,
    "layer_types": [S, S, S, F], "mlp_layer_types": ["sparse"] * 4,
    "max_position_embeddings": 512, "max_window_layers": 0,
    "moe_intermediate_size": 32, "norm_topk_prob": True,
    "num_attention_heads": 4, "num_experts": 4, "num_experts_per_tok": 4,
    "num_hidden_layers": 4, "num_key_value_heads": 2, "rms_norm_eps": 1e-6,
    "rope_parameters": {F: YARN, S: {"rope_type": "default",
                                     "rope_theta": 500000}},
    "sliding_window": 8, "tie_word_embeddings": False,
    "use_sliding_window": True, "vocab_size": 256,
    "expert_parallel": {"num_experts": 16, "chips": 4, "held_offset": 4},
    "max_new_tokens": 16,
}
# every expert held: the uncut layer
WHOLE = dict(TINY, num_experts=16,
             expert_parallel={"num_experts": 16, "chips": 1,
                              "held_offset": 0})


def _program(cfg, seed, dtype=jnp.float32):
    mcfg = ARCH.program_config(dict(cfg, torch_dtype=jnp.dtype(dtype).name))
    api = build_model(mcfg)
    shapes = jax.eval_shape(api.init, jax.random.key(0))
    weights = W.stacked(ARCH, cfg, W.root_key(seed), dtype)
    return mcfg, api, W.to_program_tree(ARCH, weights, shapes)


def test_chunks_across_the_window_then_decode_agree_with_the_reference():
    """Slot 0's prompt of 21 tokens goes in chunks of 8 (so its last chunk
    attends past the sliding window), slot 1's of 13; then six decode
    steps through the cache. Every logit the program produces matches the
    reference's full forward at that position."""
    seed, C, extent, steps = 3, 8, 32, 6
    _, api, params = _program(TINY, seed)
    rng = np.random.default_rng(0)
    prompts = (21, 13)
    rows = rng.integers(0, TINY["vocab_size"], (2, extent), dtype=np.int32)
    chunk = jax.jit(lambda *a: api.prefill_chunk(*a, NULL_CTX))
    decode = jax.jit(lambda *a: api.decode_slotted(*a, NULL_CTX))
    got = np.zeros((2, steps + 1, TINY["vocab_size"]), np.float32)
    with jax.default_matmul_precision("highest"):
        caches = api.init_caches(2, extent)
        for slot, n in enumerate(prompts):
            for start in range(0, n, C):
                valid = min(C, n - start)
                toks = np.zeros((1, C), np.int32)
                toks[0, :valid] = rows[slot, start:start + valid]
                caches, logits, picks = chunk(
                    params, caches, jnp.asarray(toks), jnp.int32(slot),
                    jnp.int32(start), jnp.int32(valid))
                assert picks.shape == (4, 4)
            got[slot, 0] = logits[0, 0]
        pos = np.array(prompts, np.int32)
        for t in range(steps):
            caches, logits, picks = decode(
                params, caches, jnp.asarray(rows[np.arange(2), pos + t]),
                jnp.asarray(pos + t), jnp.ones(2, bool))
            got[:, t + 1] = logits[:, 0]
    score = np.stack([np.arange(n - 1, n + steps) for n in prompts])
    want = np.asarray(REF.logits(TINY, W.root_key(seed), rows, score))
    assert np.abs(got - want).max() <= 1e-4 * np.abs(want).max()


def _layer0(cfg, seed=5):
    return W.layer(ARCH, cfg, W.root_key(seed), 0)


def _moe_params(w, lo=0, hi=None):
    return {"router": {"w": w["router"]}, "w_gate": w["w_gate"][lo:hi],
            "w_up": w["w_up"][lo:hi], "w_down": w["w_down"][lo:hi]}


def test_every_token_to_one_held_expert_drops_nothing():
    """All 64 tokens pick held expert 5 (the capacity-bounded dispatch
    would keep 24 of them there); the layer still gives the reference's
    output and counts 64 picks of it."""
    w = _layer0(TINY)
    w["router"] = w["router"].at[:, 5].set(4.0)
    x = jnp.abs(jax.random.normal(jax.random.key(1), (2, 32, 64))) + 0.5
    mcfg = ARCH.program_config(dict(TINY, torch_dtype="float32"))
    with jax.default_matmul_precision("highest"):
        out, picks = moe_held(_moe_params(w), x, mcfg)
        want = REF.experts(TINY, w, x, None)
    assert int(picks[1]) == 64
    assert int(picks.sum()) <= 64 * 4
    np.testing.assert_allclose(out, want, rtol=1e-4,
                               atol=1e-4 * float(jnp.abs(want).max()))


def test_the_four_shares_add_up_to_the_uncut_layer():
    w = _layer0(WHOLE)
    x = jax.random.normal(jax.random.key(2), (2, 16, 64))
    whole = ARCH.program_config(dict(WHOLE, torch_dtype="float32"))
    with jax.default_matmul_precision("highest"):
        parts, counts = [], []
        for share in range(4):
            cfg = dict(TINY, torch_dtype="float32", expert_parallel=dict(
                TINY["expert_parallel"], held_offset=4 * share))
            out, picks = moe_held(
                _moe_params(w, 4 * share, 4 * share + 4), x,
                ARCH.program_config(cfg))
            parts.append(out)
            counts.append(picks)
        uncut, picks = moe_held(_moe_params(w), x, whole)
        want = REF.experts(WHOLE, w, x, None)
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(sum(parts), uncut, atol=1e-5 * scale)
    np.testing.assert_allclose(uncut, want, atol=1e-4 * scale)
    np.testing.assert_array_equal(jnp.concatenate(counts), picks)
    assert int(picks.sum()) == 2 * 16 * 4          # every token's top-4


def test_yarn_table_is_the_published_formula():
    """head_dim 128, theta 500000, factor 16 over 8192 positions, betas 32
    and 1: the ramp runs from dimension pair 18 (floor of 18.08) to 35
    (ceiling of 34.98). Values computed by hand from the formula."""
    yarn = YaRNConfig(factor=16, original_max_position_embeddings=8192,
                      beta_fast=32, beta_slow=1,
                      attention_factor=1.2772588722239782)
    inv, scale = common.yarn_freqs(128, 500000.0, yarn)
    want = {0: 1.0, 10: 0.12868737343265052, 18: 0.024955408670558694,
            26: 0.0027043825167258223, 34: 0.00011040869063028003,
            35: 4.7781061769823416e-05, 40: 1.7140510979762956e-05,
            63: 1.5344629944572555e-07}
    for i, v in want.items():
        assert inv[i] == pytest.approx(v, rel=1e-6), i
    assert scale == pytest.approx(0.1 * np.log(16) + 1)
    ref_inv, ref_scale = REF.rope_table(128, dict(
        YARN, original_max_position_embeddings=8192))
    np.testing.assert_allclose(inv, ref_inv, rtol=1e-6)
    assert ref_scale == scale


def _engine_run(cfg, seed=4):
    from repro.runtime.serving import Request, ServingEngine
    _, api, params = _program(cfg, seed)
    eng = ServingEngine(api, NULL_CTX, 3, 40, mode="continuous",
                        backend="colocated", max_new_cap=16, block_size=4,
                        kv_bucket_chunk=0, prefill_chunk=8)
    rng = np.random.default_rng(seed)
    shape = [(20, 5), (33, 9), (5, 3), (12, 16)]
    reqs = [Request(rid=i, prompt=rng.integers(0, 256, n, dtype=np.int32),
                    max_new_tokens=m, eos_id=-1)
            for i, (n, m) in enumerate(shape)]
    stats = eng.run(params, reqs, max_steps=1 << 30)
    assert [len(r.generated) for r in reqs] == [m for _, m in shape]
    return stats, shape


def test_engine_counts_every_pick_of_every_token():
    """With every expert held, each layer counts K picks per prompt token
    (chunk lane) and per decoded token (decode blocks); with a share held,
    the counts split by expert as the program routed them."""
    stats, shape = _engine_run(WHOLE)
    moe = stats["moe"]
    dec, pre = np.array(moe["decode_picks"]), np.array(moe["prefill_picks"])
    assert dec.shape == pre.shape == (4, 16)
    np.testing.assert_array_equal(dec.sum(1), 4 * stats["decode_tokens"])
    np.testing.assert_array_equal(pre.sum(1), 4 * sum(n for n, _ in shape))
    assert moe["picks"] == dec.sum() + pre.sum()
    # the decode tokens (all but each request's first) come from at least
    # max(output) - 1 micro-steps and at most their sum
    assert 15 <= moe["decode_micro_steps"] <= stats["decode_tokens"]
    share, _ = _engine_run(TINY)
    assert np.array(share["moe"]["decode_picks"]).shape == (4, 4)


def test_expert_metrics_read_the_counters():
    picks = [[2, 4, 6, 8], [5, 5, 5, 5]]
    w = harness.Window(cfg=TINY, arch=ARCH, peaks={}, slots=4, block_size=4,
                       stats={"moe": {"decode_picks": picks,
                                      "decode_micro_steps": 5}},
                       window_s=1.0, completed=[])
    ratio = harness.load_module("metrics", "expert_load_ratio").read(w)
    per_step = harness.load_module("metrics", "expert_tokens_per_step").read(w)
    assert ratio == pytest.approx((8 / 5 + 1) / 2)
    assert per_step == pytest.approx(40 / (8 * 5))
    dense = dataclasses.replace(w, stats={"macro_steps": 1})
    for name in ("expert_load_ratio", "expert_tokens_per_step"):
        assert harness.load_module("metrics", name).read(dense) is None


# -- the architecture module ------------------------------------------------

PUBLISHED = harness.load_json("configs", "mellum2-12b-a2.5b-ep8")


def test_program_config_of_the_published_file():
    got = ARCH.program_config(PUBLISHED)
    want = ModelConfig(
        name="mellum2-12b-a2.5b-ep8", family="moe", n_layers=28,
        d_model=2304, n_heads=32, n_kv_heads=4, d_ff=7168, vocab_size=98304,
        head_dim=128, norm="rmsnorm", act="swiglu", rope_theta=500000.0,
        qkv_bias=False, tie_embeddings=False, norm_eps=1e-6,
        dtype="bfloat16",
        moe=MoEConfig(num_experts=64, experts_per_token=8, expert_d_ff=896,
                      held_experts=8, held_offset=0),
        layer_windows=(1024, 1024, 1024, 0) * 7,
        yarn=YaRNConfig(factor=16.0, original_max_position_embeddings=8192,
                        beta_fast=32.0, beta_slow=1.0,
                        attention_factor=1.2772588722239782))
    for f in dataclasses.fields(ModelConfig):
        assert getattr(got, f.name) == getattr(want, f.name), f.name


@pytest.mark.parametrize("change", [
    {"qk_norm": True},
    {"hidden_act": "gelu"},
    {"mlp_layer_types": ["dense"] + ["sparse"] * 27},
    {"norm_topk_prob": False},
    {"layer_types": ["chunked_attention"] * 28},
    {"rope_parameters": {F: YARN, S: dict(YARN, rope_theta=10000)}},
    {"expert_parallel": {"num_experts": 64, "chips": 4, "held_offset": 0}},
    {"expert_parallel": {"num_experts": 64, "chips": 8, "held_offset": 60}},
], ids=["unknown-key", "activation", "dense-mlp", "unnormalised",
        "layer-kind", "rope", "share-count", "share-range"])
def test_program_config_refuses_what_it_cannot_map(change):
    with pytest.raises(ValueError):
        ARCH.program_config(dict(PUBLISHED, **change))


def test_weight_tree_fills_the_program_tree():
    mcfg, api, params = _program(TINY, 9, jnp.bfloat16)
    shapes = jax.eval_shape(api.init, jax.random.key(0))
    assert jax.tree.structure(params) == jax.tree.structure(shapes)
    weights = W.stacked(ARCH, TINY, W.root_key(9), jnp.bfloat16)
    assert params["blocks"]["moe"]["router"]["w"].shape == (4, 64, 16)
    assert jnp.array_equal(params["blocks"]["moe"]["w_down"],
                           weights["w_down"])
    del weights["router"]
    with pytest.raises(ValueError, match="blocks/moe/router/w"):
        W.to_program_tree(ARCH, weights, shapes)


def test_published_share_sizes():
    """Per layer 21,233,664 attention parameters, a 2304 x 64 router and
    49,545,216 in the 8 held experts; 4,878,107,136 bytes in bf16."""
    assert ARCH.attention_params(PUBLISHED) == 21_233_664
    assert 8 * ARCH.expert_params(PUBLISHED) == 49_545_216
    assert ARCH.weight_bytes(PUBLISHED) == 4_878_107_136
    assert 28 * ARCH.kv_bytes_per_layer_token(PUBLISHED) == 57_344


def test_micro_step_need_by_hand():
    """Two rows at contexts 100 and 2000: the shared weights once, 8 x (1 -
    (7/8)^2) = 15/8 held experts per layer, KV of 100 positions in every
    layer and of 2000 in the 7 full layers and 1024 in the 21 sliding."""
    shared = 28 * (21_233_664 + 2304 * 64 + 2 * 2304) + 98304 * 2304 + 2304
    experts = 28 * 15 * 6_193_152 // 8
    kv = (28 * 100 + 7 * 2000 + 21 * 1024) * 2048
    per_token = 2 * 28 * (21_233_664 + 2304 * 64 + 6_193_152) \
        + 2 * 98304 * 2304
    flops = 2 * per_token + 4 * 4096 * (28 * 100 + 7 * 2000 + 21 * 1024)
    assert ARCH.micro_step_need(PUBLISHED, [100, 2000]) == \
        (2 * (shared + experts) + kv, flops)


@pytest.mark.parametrize("contexts,nbytes,flops", [
    ([], 1_650_590_208, 0),
    ([1], 1_997_464_064, 1_997_602_816),
    ([1024, 1025, 4000, 17], 3_018_556_416, 9_747_087_360),
    (list(range(3000, 3032)), 7_179_098_579, 86_249_832_448),
])
def test_micro_step_need_pinned(contexts, nbytes, flops):
    assert ARCH.micro_step_need(PUBLISHED, contexts) == (nbytes, flops)


def test_request_counts_pinned():
    assert ARCH.prompt_flops(PUBLISHED, 1500) == 2_794_071_687_168
    assert ARCH.decode_flops(PUBLISHED, 1500, 13) == 30_266_916_864
