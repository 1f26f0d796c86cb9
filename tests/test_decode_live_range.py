"""The slotted decode's live-range read: with the flash-decode kernel's
path forced (interpret mode on the CPU), the serving engine produces the
same greedy streams as the jnp read for a dense configuration (bf16 and
int8 KV) and for a held-expert one with sliding-window layers; the
engine's ``kv_read`` counter equals the positions of the tiles the kernel
fetches; and the jnp read reports that it reads every slot's whole
extent."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig, MoEConfig, YaRNConfig
from repro.configs.registry import ASSIGNED
from repro.kernels.flash_decode.flash_decode import (flash_decode_pallas,
                                                     live_tiles)
from repro.kernels.flash_decode.ref import flash_decode_ref
from repro.models import NULL_CTX, build_model
from repro.models import transformer as T
from repro.runtime.serving import Request, ServingEngine

# f32 weights and activations, so the two reads' roundings cannot flip a
# greedy pick
DENSE = ASSIGNED["qwen2-0.5b"].reduced()
HELD = ModelConfig(
    name="tiny-held-windowed", family="moe", n_layers=4, d_model=64,
    n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=256, head_dim=16,
    rope_theta=500000.0,
    moe=MoEConfig(num_experts=16, experts_per_token=4, expert_d_ff=32,
                  held_experts=4, held_offset=4),
    layer_windows=(24, 24, 24, 0),
    yarn=YaRNConfig(factor=16.0, original_max_position_embeddings=64,
                    attention_factor=1.2772588722239782))
PROMPT_LEN, MAX_NEW = 240, 16            # an extent of 256 positions


@pytest.fixture
def kernel_path(monkeypatch):
    """The decode's path as a TPU takes it, with the kernel interpreted,
    and tiles of 128 positions, so a row's range spans two tiles."""
    monkeypatch.setattr(T, "_kernel_interpret", lambda: True)
    monkeypatch.setattr(T, "LIVE_TILE_BYTES", 1)


def _serve(cfg, seed=3):
    cfg = dataclasses.replace(cfg, dtype="float32")
    api = build_model(cfg)
    params = api.init(jax.random.key(seed))
    rng = np.random.default_rng(seed)
    shape = [(200, 12), (37, 16), (130, 5), (90, 16), (9, 9)]
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, n,
                                               dtype=np.int32),
                    max_new_tokens=m, eos_id=-1)
            for i, (n, m) in enumerate(shape)]
    eng = ServingEngine(api, NULL_CTX, 3, PROMPT_LEN, mode="continuous",
                        max_new_cap=MAX_NEW, block_size=4, prefill_chunk=64)
    stats = eng.run(params, reqs, max_steps=1 << 30)
    assert [len(r.generated) for r in reqs] == [m for _, m in shape]
    return [r.generated for r in reqs], stats


@pytest.mark.parametrize("cfg", [DENSE, DENSE.replace(kv_dtype="int8"),
                                 HELD], ids=["dense", "dense_int8", "held"])
def test_kernel_read_serves_the_jnp_streams(cfg, monkeypatch):
    want, jnp_stats = _serve(cfg)
    assert jnp_stats["kv"]["read_share_mean"] == 1.0
    monkeypatch.setattr(T, "_kernel_interpret", lambda: True)
    monkeypatch.setattr(T, "LIVE_TILE_BYTES", 1)
    got, stats = _serve(cfg)
    assert got == want
    # the decoding rows' tiles alone: some, and not the whole extent
    assert 0 < stats["kv"]["read_share_mean"] < 1.0


def test_jnp_read_reports_the_whole_extent():
    _, stats = _serve(DENSE)
    assert stats["kv"]["read_share_mean"] == 1.0
    assert stats["kv"]["in_use_share_mean"] < 1.0


def test_kv_read_counts_the_tiles_the_kernel_fetches(kernel_path):
    """Every K/V position outside the tiles that ``decode_kv_read`` counts
    is NaN: had the kernel fetched and attended any other tile, a NaN
    would reach its output. It matches the oracle over each row's range,
    and the count is the tiles' positions, a windowed layer from its
    lower bound."""
    L, B, n_kv, S, hd, bs = 4, 5, 2, 512, 16, 128
    windows = (24, 24, 24, 0)
    cfg = HELD
    cache = T.make_cache(cfg, B, S)
    assert T.live_range_block(cache, NULL_CTX) == bs
    positions = np.array([0, 127, 300, 511, 260], np.int32)
    active = np.array([True, True, False, True, True])
    want_read = 0
    k = np.full((L, B, n_kv, S, hd), np.nan, np.float32)
    v = k.copy()
    rng = np.random.default_rng(0)
    hi = np.where(active, positions + 1, 0)
    for layer, w in enumerate(windows):
        lo = np.maximum(positions + 1 - w, 0) if w else np.zeros(B, int)
        for b in range(B):
            tiles = [t for t in range(S // bs)
                     if hi[b] > lo[b] and t * bs < hi[b]
                     and (t + 1) * bs > lo[b]]
            want_read += len(tiles) * bs
            for t in tiles:
                sl = np.s_[layer, b, :, t * bs:(t + 1) * bs]
                k[sl] = rng.normal(size=k[sl].shape)
                v[sl] = rng.normal(size=v[sl].shape)
    got_read = T.decode_kv_read(cfg, cache, NULL_CTX, positions, active)
    assert got_read == want_read / L
    q = jnp.asarray(rng.normal(size=(B, cfg.n_heads, hd)), jnp.float32)
    for layer, w in enumerate(windows):
        lo = np.maximum(positions + 1 - w, 0) if w else np.zeros(B, int)
        got = np.asarray(flash_decode_pallas(
            q, jnp.asarray(k), jnp.asarray(v), None, None, None, block_s=bs,
            interpret=True, lo=jnp.asarray(lo, jnp.int32),
            hi=jnp.asarray(hi, jnp.int32), layer=jnp.int32(layer)))
        assert np.isfinite(got).all(), layer
        pos = np.arange(S)[None]
        mask = (pos >= lo[:, None]) & (pos < hi[:, None])
        ref = np.asarray(flash_decode_ref(
            q, jnp.nan_to_num(jnp.asarray(k[layer])),
            jnp.nan_to_num(jnp.asarray(v[layer])), jnp.asarray(mask)))
        np.testing.assert_allclose(got[active], ref[active], rtol=2e-5,
                                   atol=2e-5)
        assert not got[~active].any()
        first, count = live_tiles(lo, hi, bs)
        assert ((first >= 0) & (first + count <= S // bs)).all()
