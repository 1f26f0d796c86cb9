"""End-to-end behaviour tests for the paper's system."""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import ASSIGNED, get_config
from repro.configs.shapes import SHAPES, applicable
from repro.models import NULL_CTX, build_model


def test_train_driver_checkpoint_resume(tmp_path):
    """Train a tiny model, checkpoint, 'crash', resume — the restarted job
    continues from the saved step (fault-tolerance loop)."""
    from repro.launch.train import train
    from repro.checkpoint.checkpointer import latest_step
    ck = str(tmp_path / "ckpt")
    train("qwen2-0.5b", steps=12, batch=4, seq=64, reduced=True,
          ckpt_dir=ck, ckpt_every=6, log_every=6)
    assert latest_step(ck) == 12
    # resume: as if the job restarted; must pick up at step 12, not 0
    _, opt, _ = train("qwen2-0.5b", steps=16, batch=4, seq=64, reduced=True,
                      ckpt_dir=ck, ckpt_every=100, log_every=4)
    assert int(opt.step) == 16


def test_training_reduces_loss():
    from repro.launch.train import train
    _, _, losses = train("internlm2-1.8b", steps=60, batch=8, seq=64,
                         reduced=True, log_every=10)
    first, last = losses[0][1], losses[-1][1]
    assert last < first, (first, last)


def test_serve_driver_end_to_end():
    from repro.launch.serve import serve
    stats = serve("qwen2-0.5b", n_requests=4, batch_slots=2, prompt_len=8,
                  max_new=4)
    assert stats["completed"] == 4
    assert stats["throughput_tok_s"] > 0


def _run_fresh(code: str, **env_over) -> str:
    """``code`` in a fresh interpreter (the compile-cache settings are
    process-wide, so they are probed outside this test process)."""
    root = Path(__file__).resolve().parents[1]
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(PYTHONPATH=str(root / "src"), JAX_PLATFORMS="cpu", **env_over)
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=120)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_compile_cache_uses_environment_dir(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, compiled programs are cached
    there and the helper sets no directory of its own."""
    cache = tmp_path / "cache"
    out = _run_fresh("""
        import os, jax, jax.numpy as jnp
        from repro.launch.serve import enable_compile_cache
        d = enable_compile_cache()
        assert d == os.environ["JAX_COMPILATION_CACHE_DIR"], d
        assert jax.config.jax_compilation_cache_dir == d
        jax.jit(lambda x: x * 2 + 1).lower(jnp.ones(8)).compile()
        print(len(os.listdir(d)))
        """, JAX_COMPILATION_CACHE_DIR=str(cache),
        JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    assert int(out.split()[-1]) > 0


def test_compile_cache_defaults_to_fixed_repo_dir():
    """Without the variable, the cache sits at <repo root>/.jax_cache: a
    fixed path (part of each entry's key), ignored by git."""
    root = Path(__file__).resolve().parents[1]
    out = _run_fresh("""
        import jax
        from repro.launch.serve import enable_compile_cache
        d = enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == d
        print(d)
        """)
    assert out.split()[-1] == str(root / ".jax_cache")
    assert ".jax_cache/" in (root / ".gitignore").read_text().split()


def test_greedy_decode_is_deterministic():
    cfg = ASSIGNED["internlm2-1.8b"].reduced()
    api = build_model(cfg)
    params = api.init(jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (2, 8), 0, cfg.vocab_size)

    def gen():
        caches, logits = api.prefill(params, {"tokens": toks}, NULL_CTX)
        out = []
        cur = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
        for _ in range(5):
            out.append(np.asarray(cur).copy())
            caches, logits = api.decode(params, caches, cur, NULL_CTX)
            cur = jnp.argmax(logits[:, 0], -1).astype(jnp.int32)
        return np.stack(out)

    np.testing.assert_array_equal(gen(), gen())


def test_shape_applicability_policy():
    """long_500k runs ONLY for sub-quadratic archs; everything else is a
    documented skip (DESIGN.md §6)."""
    runnable = {a for a in ASSIGNED
                if applicable(ASSIGNED[a], SHAPES["long_500k"])[0]}
    assert runnable == {"mamba2-1.3b", "recurrentgemma-9b"}
    for a in ASSIGNED:
        ok, why = applicable(ASSIGNED[a], SHAPES["long_500k"])
        assert ok or "quadratic" in why


def test_wa_plan_policy_matches_paper_fig9():
    """WA separation: inapplicable for attention-free archs; profitable for
    the high-pressure 70B regime (paper Fig 9)."""
    from jax.sharding import Mesh
    from repro.core.wa import wa_plan
    devs = np.array([jax.devices()[0]] * 4).reshape(4, 1)
    mesh = Mesh(devs, ("data", "model"))
    assert not wa_plan(ASSIGNED["mamba2-1.3b"], SHAPES["decode_32k"],
                       mesh).separate
    big = wa_plan(get_config("llama2-70b"), SHAPES["decode_32k"], mesh)
    assert big.separate
