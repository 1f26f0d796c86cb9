"""Each per-layer metric reader on a synthetic window whose answers are
known, and each returns nothing where it finds nothing to read."""
import pytest

from bench import harness
from bench import trace_reduce as T

MS = 1_000_000
PEAKS = {"bf16_flops_per_s": 100e12, "hbm_bytes_per_s": 1e12}


def _window(trace=True, need=None):
    cfg = harness.load_json("configs", "qwen2-0.5b")
    tr = {"window": [0, 100 * MS],
          "device": [[0, T.MODULES, "jit_block_step(1)", 0, 40 * MS],
                     [0, T.OPS, "fusion", 0, 40 * MS],
                     [0, T.MODULES, "jit_chunk_fn(2)", 50 * MS, 10 * MS],
                     [0, T.OPS, "fusion", 50 * MS, 10 * MS]],
          "host": []}
    return harness.Window(
        cfg=cfg, arch=harness.architecture(cfg), peaks=PEAKS, slots=4, block_size=8,
        stats={"macro_steps": 10, "decode_tokens": 80,
               "prefill_time_ms": 250.0},
        window_s=2.0, completed=[(100, 10), (50, 20)],
        modules={"serve_decode_block": "jit_block_step",
                 "serve_prefill_chunk": "jit_chunk_fn"},
        trace=tr if trace else None, decode_need=need)


def _read(name, w):
    return harness.load_module("metrics", name).read(w)


def test_counter_readers():
    w = _window()
    assert _read("decode_occupancy", w) == pytest.approx(25.0)  # 80/320
    assert _read("prefill_share", w) == pytest.approx(12.5)     # 0.25/2
    a = w.arch
    flops = (a.prompt_flops(w.cfg, 100) + a.decode_flops(w.cfg, 100, 10)
             + a.prompt_flops(w.cfg, 50) + a.decode_flops(w.cfg, 50, 20))
    assert _read("mfu", w) == pytest.approx(100 * flops / 2.0 / 100e12)


def test_trace_readers():
    w = _window(need={"blocks": 1, "bytes": 20e9, "flops": 1e12})
    assert _read("decode_step_ms", w) == pytest.approx(40 / 8)
    assert _read("prefill_chunk_ms", w) == pytest.approx(10.0)
    assert _read("device_idle_share", w) == pytest.approx(50.0)
    # least time max(20 GB / 1 TB/s, 1 TFLOP / 100 TFLOP/s) = 20 ms of 40
    assert _read("decode_roofline", w) == pytest.approx(50.0)


def test_readers_return_nothing_without_a_trace():
    w = _window(trace=False)
    for name in ("decode_step_ms", "prefill_chunk_ms", "device_idle_share",
                 "decode_roofline"):
        assert _read(name, w) is None


def test_roofline_needs_the_blocks_it_timed():
    """Work counted for other blocks than the trace holds is an error, not
    a silent gap in the result."""
    with pytest.raises(ValueError, match="decode blocks"):
        _read("decode_roofline",
              _window(need={"blocks": 2, "bytes": 1.0, "flops": 1.0}))
    with pytest.raises(ValueError, match="decode blocks"):
        _read("decode_roofline", _window(need=None))


@pytest.mark.parametrize("name", ["decode_step_ms", "prefill_chunk_ms",
                                  "decode_roofline"])
def test_trace_readers_fail_loudly_without_their_program(name):
    """A refactor that renames a program, or hides it from the trace,
    fails the traced run instead of silencing the metric."""
    w = _window(need={"blocks": 1, "bytes": 1.0, "flops": 1.0})
    w.modules = {}
    with pytest.raises(LookupError, match="no program"):
        _read(name, w)
    w = _window(need={"blocks": 1, "bytes": 1.0, "flops": 1.0})
    w.trace["device"] = [e for e in w.trace["device"]
                         if e[1] != T.MODULES]
    with pytest.raises(LookupError, match="no execution"):
        _read(name, w)
