"""A whole benchmark run, past the look for a chip, with the timed path
broken underneath: ``correct`` has to come out false for every fault a
one-chip serving cell can have, and true for the unbroken path."""
import time

import jax
import numpy as np
import pytest

from bench import harness, tiny
from repro.kv import cache as kv_cache
from repro.runtime import serving

PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def _run(seed=2**31 + 11):
    return harness.measure(
        tiny.CONFIG, tiny.MIX, tiny.LIMITS,
        harness.load_module("references", "dense_gqa"), seed, 0.1, False,
        harness.benchmark()["end_to_end"], [], time.monotonic(),
        jax.devices(), PEAKS)


def _altered_token(monkeypatch):
    emit = serving.ServingEngine._emit_token

    def altered(self, r, tok):
        if len(r.generated) == 3:
            tok = (int(tok) + 1) % tiny.CONFIG["vocab_size"]
        emit(self, r, tok)
    monkeypatch.setattr(serving.ServingEngine, "_emit_token", altered)


def _state_unchanged(monkeypatch):
    """Decode steps leave the KV cache as they found it."""
    monkeypatch.setattr(kv_cache, "layer_append_slotted",
                        lambda k, v, ks, vs, *a, **kw: (k, v, ks, vs))


def _half_batch(monkeypatch):
    """Decode writes the KV of the first half of the slots only."""
    append = kv_cache.layer_append_slotted

    def half(k, v, ks, vs, kn, vn, pos, window, active):
        rows = np.arange(active.shape[0]) < active.shape[0] // 2
        return append(k, v, ks, vs, kn, vn, pos, window, active & rows)
    monkeypatch.setattr(kv_cache, "layer_append_slotted", half)


def test_unbroken_run_is_correct():
    out = _run()
    assert out["correct"], out["checks"]
    assert out["failed"] == 0
    assert set(out["metrics"]) == {
        m["name"] for m in harness.benchmark()["end_to_end"]}
    assert list(out["checks"]) == ["widest_logit_gap", "failed_requests",
                                   "window_compiles"]


@pytest.mark.parametrize("fault", [_altered_token, _state_unchanged,
                                   _half_batch])
def test_fault_makes_run_incorrect(monkeypatch, fault):
    fault(monkeypatch)
    out = _run()
    assert not out["correct"]
    gap = out["checks"]["widest_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_a_compile_in_the_window_makes_run_incorrect(monkeypatch):
    """A program first lowered inside the window counts, even one the
    engine's own compile counters do not see."""
    import jax.numpy as jnp
    emit = serving.ServingEngine._emit_token

    def lowering(self, r, tok):
        if len(r.generated) == 2 and r.rid == 0:
            jax.jit(lambda x: x + len(r.prompt))(jnp.ones(3))
        emit(self, r, tok)
    monkeypatch.setattr(serving.ServingEngine, "_emit_token", lowering)
    out = _run()
    assert not out["correct"]
    assert out["checks"]["window_compiles"]["value"] >= 1
