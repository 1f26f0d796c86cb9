"""End-to-end metric arithmetic on a synthetic closed-loop timeline."""
import pytest

from bench import e2e


def _timeline():
    # concurrency 2; four requests. Admission order: 0, 1 at the start,
    # 2 after the first completion (t=3), 3 after the second (t=4).
    return [
        e2e.Served(t_admitted=0.0, t_first_token=1.0, t_done=3.0, n_tokens=5),
        e2e.Served(t_admitted=0.1, t_first_token=1.5, t_done=4.0, n_tokens=6),
        e2e.Served(t_admitted=3.1, t_first_token=3.5, t_done=6.0, n_tokens=6),
        e2e.Served(t_admitted=4.2, t_first_token=5.0, t_done=7.0, n_tokens=3),
    ]


def test_closed_loop_send_times():
    assert e2e.send_times(_timeline(), 2, 0.0) == [0.0, 0.0, 3.0, 4.0]


def test_send_times_follow_admission_order_not_list_order():
    tl = _timeline()
    shuffled = [tl[2], tl[0], tl[3], tl[1]]
    assert e2e.send_times(shuffled, 2, 0.0) == [3.0, 0.0, 4.0, 0.0]


def test_metrics():
    m = e2e.metrics(_timeline(), 2, 0.0, 7.0)
    assert m["output_tokens_per_s"] == pytest.approx(20 / 7)
    # TTFTs 1.0, 1.5, 0.5, 1.0 s -> p50 and p90 by linear interpolation
    assert m["ttft_p50_ms"] == pytest.approx(1000.0)
    assert m["ttft_p90_ms"] == pytest.approx(1350.0)
    # TPOTs 0.5, 0.5, 0.5, 1.0 s
    assert m["tpot_p50_ms"] == pytest.approx(500.0)
    assert m["tpot_p90_ms"] == pytest.approx(850.0)
