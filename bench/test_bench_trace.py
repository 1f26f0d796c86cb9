"""Trace reduction: device busy union, idle share, device time per program,
top operations and the longest idle gaps with the host events in them, on
a synthetic trace whose answers are known and on a small trace recorded on
the chip (``bench/testdata/record_trace.py``)."""
import json
from pathlib import Path

import pytest

from bench import trace_reduce as T

RECORDED = Path(__file__).resolve().parent / "testdata" / "trace_small.json"


def _synthetic():
    ms = 1_000_000
    return {
        "window": [0, 100 * ms],
        "device": [
            [0, T.MODULES, "jit_block_step(7)", 10 * ms, 30 * ms],
            [0, T.OPS, "fusion.1", 10 * ms, 20 * ms],
            [0, T.OPS, "fusion.2", 25 * ms, 15 * ms],    # overlaps fusion.1
            [0, T.MODULES, "jit_chunk_fn(9)", 60 * ms, 10 * ms],
            [0, T.OPS, "convolution.3", 60 * ms, 10 * ms],
            [0, T.OPS, "outside", 150 * ms, 5 * ms],      # after the window
        ],
        "host": [
            [T.BEGIN, 0, 0],
            ["dispatch:serve_decode_block", 9 * ms, 0],
            ["np.asarray", 41 * ms, 15 * ms],
            ["dispatch:serve_prefill_chunk", 59 * ms, 0],
            [T.END, 100 * ms, 0],
        ],
    }


def test_union_merges_overlaps():
    assert T.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def test_busy_and_window():
    tr = _synthetic()
    assert T.window_s(tr) == pytest.approx(0.1)
    assert T.busy_s(tr) == pytest.approx(0.04)           # 30 + 10 ms


def test_module_times_by_stable_name():
    mt = T.module_times(_synthetic())
    assert mt["jit_block_step"] == (1, pytest.approx(0.03))
    assert mt["jit_chunk_fn"] == (1, pytest.approx(0.01))


def test_top_ops():
    top = T.top_ops(_synthetic(), k=2)
    assert [n for n, _ in top] == ["jit_block_step/fusion.1",
                                   "jit_block_step/fusion.2"]
    assert top[0][1] == pytest.approx(0.02)


def test_idle_gaps_name_overlapping_host_events():
    gaps = T.idle_gaps(_synthetic())
    # gaps: 30 ms at +70, 20 ms at +40, 10 ms at +0
    assert [round(s, 6) for _, s in gaps] == [0.03, 0.02, 0.01]
    assert "np.asarray" in gaps[1][0]
    assert "dispatch:serve_decode_block" in gaps[2][0]
    assert gaps[0][0].endswith("host: no event")


def test_op_name_is_the_hlo_name():
    assert T.op_name("%while.23 = (s32[], bf16[2,4]) while(%t), "
                     "body=%b") == "while.23"
    assert T.op_name("fusion.1") == "fusion.1"


def test_recorded_chip_trace():
    tr = json.loads(RECORDED.read_text())
    assert T.chips(tr) == [0]
    mt = T.module_times(tr)
    assert mt["jit_matmul_step"][0] == 3 and mt["jit_reduce_step"][0] == 3
    busy, window = T.busy_s(tr), T.window_s(tr)
    assert 0 < busy < window
    # every program execution lies inside the busy union
    assert sum(t for _, t in mt.values()) <= busy * 1.0001
    # the host sleeps 2 ms between the two programs, three times
    gaps = T.idle_gaps(tr)
    assert len([g for g in gaps if g[1] >= 0.0015]) >= 3
    top = T.top_ops(tr)
    assert top and all(n.split("/")[0] in mt for n, _ in top)
