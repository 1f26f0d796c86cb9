"""The readers of the engine's span table and counters (``lane_wait_ms``,
``boundary_host_ms``, ``kv_in_use_share``): known answers on a fabricated
window, nothing where the engine keeps no such table, finite values from a
whole traced run of the tiny cell on the CPU, and the benchmark's entries
for them resolve in both cells."""
import math
import time

import jax
import pytest

from bench import harness, tiny

NAMES = ("lane_wait_ms", "boundary_host_ms", "kv_in_use_share")
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def _window(stats):
    return harness.Window(cfg=tiny.CONFIG,
                          arch=harness.architecture(tiny.CONFIG),
                          peaks=PEAKS, slots=2, block_size=8, stats=stats,
                          window_s=1.0, completed=[])


def _read(name, w):
    return harness.load_module("metrics", name).read(w)


def test_readers_on_a_fabricated_window():
    w = _window({
        "per_request": [{"lane_wait_ms": v} for v in (30.0, 10.0, 20.0, 50.0)],
        "boundary": {"n": 7, "host_p50_ms": 9.5, "host_p90_ms": 12.0},
        "kv": {"reserved_tokens": 8192, "in_use_share_mean": 0.125,
               "lane_depth_mean": 3.0}})
    assert _read("lane_wait_ms", w) == pytest.approx(25.0)
    assert _read("boundary_host_ms", w) == pytest.approx(9.5)
    assert _read("kv_in_use_share", w) == pytest.approx(12.5)


@pytest.mark.parametrize("name", NAMES)
def test_readers_return_nothing_without_the_engine_block(name):
    """A program without the span table (the commit before it) reports
    nothing rather than failing the run."""
    older = {"per_request": [{"ttft_ms": 5.0, "queue_delay_ms": 1.0}],
             "macro_steps": 3, "prefill_time_ms": 2.0}
    assert _read(name, _window(older)) is None
    assert _read(name, _window({"boundary": {"n": 0, "host_p50_ms": 0.0,
                                             "host_p90_ms": 0.0},
                                "per_request": []})) is None


def test_traced_tiny_run_reads_all_three():
    bm = harness.benchmark()
    per_layer = [m for m in bm["per_layer"] if m["name"] in NAMES]
    out = harness.measure(
        tiny.CONFIG, tiny.MIX, tiny.LIMITS,
        harness.load_module("references", "dense_gqa"), 2**31 + 29, 0.1,
        True, [], per_layer, time.monotonic(), jax.devices(), PEAKS)
    assert out["correct"], out["checks"]
    for name in NAMES:
        assert math.isfinite(out["metrics"][name]["value"]), name
    assert out["metrics"]["lane_wait_ms"]["unit"] == "ms"
    assert 0 < out["metrics"]["kv_in_use_share"]["value"] <= 100
    assert out["metrics"]["boundary_host_ms"]["value"] > 0


def test_both_cells_list_and_resolve_the_new_metrics():
    bm = harness.benchmark()
    for wl in bm["workloads"]:
        listed = {m["name"] for m in harness.per_layer_for(bm, wl["name"])}
        assert set(NAMES) <= listed
        for name in NAMES:
            assert callable(harness.load_module("metrics", name).read)
