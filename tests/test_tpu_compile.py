"""Compile for a described TPU v5e, without a chip.

The TPU compiler is installed with JAX, so programs lower and compile for a
chip that is described but not attached. This catches what interpret mode
and the CPU backend cannot: Mosaic's tiling rules for a Pallas kernel
(the last two block dims divisible by 8 and 128, or equal to the array's)
and whether a full-width step program fits one chip.

The topology is described inside a module fixture, never at import time:
only one process may load the TPU library, and under several test workers
only the worker given this file may try.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import get_config
from repro.kernels.flash_decode.flash_decode import flash_decode_pallas
from repro.models import NULL_CTX, build_model

# qwen2-0.5b decode widths: 14 query heads over 2 KV heads (G=7), hd 64
B, N_KV, G, HD, S, BLOCK_S = 8, 2, 7, 64, 4096, 512


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # can never be read back without one; keep the cache off meanwhile
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("partial", [False, True], ids=["full", "partial"])
@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
def test_flash_decode_compiles_for_v5e(one_chip, kv_dtype, partial):
    quant = kv_dtype == "int8"
    q = _spec((B, N_KV * G, HD), jnp.bfloat16, one_chip)
    kv = _spec((B, N_KV, S, HD), jnp.dtype(kv_dtype), one_chip)
    sc = _spec((B, N_KV, S, 1), jnp.float32, one_chip) if quant else None
    mask = _spec((B, S), jnp.bool_, one_chip)
    lim = _spec((1, 1), jnp.int32, one_chip)

    def call(q, k, v, ks, vs, mask, lim):
        return flash_decode_pallas(q, k, v, ks, vs, mask, block_s=BLOCK_S,
                                   kv_limit=lim, partial_stats=partial)

    compiled = jax.jit(call).lower(q, kv, kv, sc, sc, mask, lim).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_qwen2_decode_block_compiles_for_v5e(one_chip):
    """The serving engine's macro-step program (T=8, 8 slots, KV extent
    256) at the full published qwen2-0.5b widths, from eval_shape shapes."""
    cfg = get_config("qwen2-0.5b")
    assert (cfg.n_layers, cfg.d_model, cfg.vocab_size) == (24, 896, 151936)
    api = build_model(cfg)
    slots, extent, T = 8, 256, 8

    def place(tree):
        return jax.tree.map(lambda a: _spec(a.shape, a.dtype, one_chip),
                            tree)

    params = place(jax.eval_shape(api.init, jax.random.key(0)))
    caches = place(jax.eval_shape(lambda: api.init_caches(slots, extent)))
    i32 = _spec((slots,), jnp.int32, one_chip)
    act = _spec((slots,), jnp.bool_, one_chip)

    def block(p, c, tok, pos, act, rem, eos):
        return api.decode_block(p, c, tok, pos, act, rem, eos, NULL_CTX,
                                block_size=T)

    compiled = jax.jit(block, donate_argnums=(1,)).lower(
        params, caches, i32, i32, act, i32, i32).compile()
    param_bytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                      for a in jax.tree.leaves(params))
    assert param_bytes == 988_065_536
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes >= param_bytes
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16 * 2**30


def test_mellum2_share_programs_compile_for_v5e(one_chip):
    """One chip of Mellum2-12B-A2.5B's eight-way expert parallelism at its
    published widths (8 of 64 experts held, 21 sliding-window and 7 YaRN
    layers): the serving engine's decode block (T=8, 32 slots, extent 4096)
    and its 512-token chunk program, each with the picks per held expert
    beside its outputs, fit one chip."""
    api = build_model(_mellum2())
    slots, extent = 32, 4096
    params, caches = _placed(api, slots, extent, one_chip)
    scalar = _spec((), jnp.int32, one_chip)

    def chunk(p, c, toks, slot, start, valid):
        return api.prefill_chunk(p, c, toks, slot, start, valid, NULL_CTX)

    for compiled in (
            _compile_block(api, params, caches, slots, one_chip),
            jax.jit(chunk, donate_argnums=(1,)).lower(
                params, caches, _spec((1, 512), jnp.int32, one_chip),
                scalar, scalar, scalar).compile()):
        assert compiled.out_info[-1].shape == (28, 8)     # the picks
        mem = compiled.memory_analysis()
        assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
            < 16 * 2**30


def _mellum2():
    """One chip of Mellum2-12B-A2.5B's eight-way expert parallelism at its
    published widths."""
    from repro.configs.base import ModelConfig, MoEConfig, YaRNConfig
    return ModelConfig(
        name="mellum2-12b-a2.5b-ep8", family="moe", n_layers=28,
        d_model=2304, n_heads=32, n_kv_heads=4, d_ff=7168, vocab_size=98304,
        head_dim=128, rope_theta=500000.0,
        moe=MoEConfig(num_experts=64, experts_per_token=8, expert_d_ff=896,
                      held_experts=8),
        layer_windows=(1024, 1024, 1024, 0) * 7,
        yarn=YaRNConfig(factor=16.0, original_max_position_embeddings=8192,
                        attention_factor=1.2772588722239782))


def _placed(api, slots, extent, sharding):
    """The abstract weights and slot caches, on ``sharding``."""
    def place(tree):
        return jax.tree.map(lambda a: _spec(a.shape, a.dtype, sharding),
                            tree)

    return (place(jax.eval_shape(api.init, jax.random.key(0))),
            place(jax.eval_shape(lambda: api.init_caches(slots, extent))))


def _compile_block(api, params, caches, slots, sharding, T=8):
    """The serving engine's macro-step decode program, compiled."""
    i32 = _spec((slots,), jnp.int32, sharding)
    act = _spec((slots,), jnp.bool_, sharding)

    def block(p, c, tok, pos, act, rem, eos):
        return api.decode_block(p, c, tok, pos, act, rem, eos, NULL_CTX,
                                block_size=T)

    return jax.jit(block, donate_argnums=(1,)).lower(
        params, caches, i32, i32, act, i32, i32).compile()


# a copy or a transpose, as an instruction or as a fusion that XLA names so
_COPY = re.compile(r"%((?:copy|transpose)[\w.-]*) = \(?(\w+)\[([\d,]*)\]")


@pytest.mark.parametrize("name,slots", [("qwen2-0.5b", 32),
                                        ("internlm2-1.8b", 8),
                                        ("mellum2-12b-a2.5b-ep8", 32)])
def test_decode_block_reads_the_carried_stacks_for_v5e(one_chip, monkeypatch,
                                                       name, slots):
    """Each benchmark configuration's decode block (T=8, its slot count,
    extent 4096) as a TPU takes it: decode attention is the flash-decode
    kernel reading each row's live range from the carried KV stacks, and
    the optimized program copies neither a stack nor a layer's slice of
    one (a relayout or a materialized slice would read the whole extent
    again), and still fits one chip."""
    from repro.models import transformer
    monkeypatch.setattr(transformer, "_kernel_interpret", lambda: False)
    cfg = _mellum2() if name.startswith("mellum2") else get_config(name)
    api = build_model(cfg)
    params, caches = _placed(api, slots, 4096, one_chip)
    assert transformer.live_range_block(caches, NULL_CTX) > 0
    compiled = _compile_block(api, params, caches, slots, one_chip)
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    L, B, n_kv, S, hd = caches.k.shape
    whole = {tuple(sorted(d)) for d in ((L, B, n_kv, S, hd),
                                        (B, n_kv, S, hd))}
    copies = [m.group(0) for m in _COPY.finditer(text)
              if tuple(sorted(int(d) for d in m.group(3).split(",") if d))
              in whole]
    assert not copies, copies[:4]
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16 * 2**30
