"""The ``kv_read_share`` reader of the engine's KV read counter: a known
answer on a fabricated window, nothing where the engine keeps no such
counter (the commit before it), a share of the extent from a whole traced
run of the tiny cell on the CPU, and its benchmark entry resolving in
every cell."""
import time

import jax
import pytest

from bench import harness, tiny

PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def _read(stats):
    w = harness.Window(cfg=tiny.CONFIG,
                       arch=harness.architecture(tiny.CONFIG), peaks=PEAKS,
                       slots=2, block_size=8, stats=stats, window_s=1.0,
                       completed=[])
    return harness.load_module("metrics", "kv_read_share").read(w)


@pytest.mark.parametrize("kv,want", [
    ({"reserved_tokens": 8192, "in_use_share_mean": 0.125,
      "read_share_mean": 0.1875, "lane_depth_mean": 3.0}, 18.75),
    # an engine whose KV block has no read counter, and one with no block
    ({"reserved_tokens": 8192, "in_use_share_mean": 0.125,
      "lane_depth_mean": 3.0}, None),
    (None, None),
], ids=["counted", "no_counter", "no_kv"])
def test_reader_on_a_fabricated_window(kv, want):
    stats = {"per_request": [], "macro_steps": 3}
    if kv is not None:
        stats["kv"] = kv
    got = _read(stats)
    assert got == (None if want is None else pytest.approx(want))


def test_traced_tiny_run_reads_the_whole_extent_off_the_chip():
    """Off a TPU the decode attends with the jnp form, which reads every
    slot's whole extent on every dispatch: 100%."""
    bm = harness.benchmark()
    per_layer = [m for m in bm["per_layer"] if m["name"] == "kv_read_share"]
    out = harness.measure(
        tiny.CONFIG, tiny.MIX, tiny.LIMITS,
        harness.load_module("references", "dense_gqa"), 2**31 + 37, 0.1,
        True, [], per_layer, time.monotonic(), jax.devices(), PEAKS)
    assert out["correct"], out["checks"]
    assert out["metrics"]["kv_read_share"] == {"value": pytest.approx(100.0),
                                               "unit": "%"}


def test_every_cell_lists_and_resolves_it():
    bm = harness.benchmark()
    for wl in bm["workloads"]:
        listed = {m["name"] for m in harness.per_layer_for(bm, wl["name"])}
        assert "kv_read_share" in listed, wl["name"]
    assert callable(harness.load_module("metrics", "kv_read_share").read)
