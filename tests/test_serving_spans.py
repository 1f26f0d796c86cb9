"""The serving loop's span table (``runtime/spans.py``): every engine timing
comes from it, its spans count the work the engine's counters count, TTFT
splits exactly into queue delay, lane wait and prefill, the KV counters are
exact on a serve worked by hand, and a profiler session records the
``serve:`` spans with their request ids and slots."""
import glob
import math

import jax
import numpy as np
import pytest

from repro.configs.registry import ASSIGNED
from repro.models import NULL_CTX, build_model
from repro.runtime.serving import Request, ServingEngine
from repro.runtime.spans import SpanTable

PROMPT_LEN = 8
DECODE_PHASES = ("decode_dispatch", "decode_wait", "unpack", "boundary")


@pytest.fixture(scope="module")
def dense():
    cfg = ASSIGNED["qwen2-0.5b"].reduced()
    api = build_model(cfg)
    return cfg, api, api.init(jax.random.key(0))


def _requests(cfg, lengths, max_new=6, every=2, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, n,
                                               dtype=np.int32),
                    max_new_tokens=max_new, arrival_step=i * every)
            for i, n in enumerate(lengths)]


LENGTHS = [5, 8, 3, 7, 6]
ENGINES = {
    "chunked_block": dict(mode="continuous", block_size=4, prefill_chunk=3),
    "chunked_step": dict(mode="continuous", block_size=1, prefill_chunk=4),
    "monolithic_block": dict(mode="continuous", block_size=4),
    "drain": dict(mode="drain"),
}


def _serve(dense, kind, lengths=LENGTHS, **kw):
    cfg, api, params = dense
    eng = ServingEngine(api, NULL_CTX, 2, PROMPT_LEN, max_new_cap=16,
                        **ENGINES[kind], **kw)
    reqs = _requests(cfg, lengths)
    stats = eng.run(params, reqs, max_steps=400)
    assert stats["completed"] == len(lengths)
    return eng, reqs, stats


@pytest.mark.parametrize("kind", sorted(ENGINES))
def test_decode_phase_spans_count_macro_steps(dense, kind):
    eng, _, stats = _serve(dense, kind)
    assert stats["macro_steps"] > 0
    for phase in DECODE_PHASES:
        assert eng.spans.count(phase) == stats["macro_steps"], phase
        assert stats["spans"][phase]["n"] == stats["macro_steps"], phase
    assert stats["boundary"]["n"] == stats["macro_steps"]
    assert 0 < stats["boundary"]["host_p50_ms"] <= \
        stats["spans"]["boundary"]["max_ms"]


@pytest.mark.parametrize("kind", ["chunked_block", "chunked_step"])
def test_chunk_spans_count_prefill_chunks(dense, kind):
    eng, _, stats = _serve(dense, kind)
    chunk = ENGINES[kind]["prefill_chunk"]
    assert stats["prefill_chunks"] == sum(math.ceil(n / chunk)
                                          for n in LENGTHS)
    for phase in ("chunk_dispatch", "chunk_wait"):
        assert eng.spans.count(phase) == stats["prefill_chunks"]
    assert eng.spans.count("prefill") == 0


@pytest.mark.parametrize("kind,phases", [
    ("chunked_block", ("chunk_dispatch", "chunk_wait")),
    ("monolithic_block", ("prefill",)),
    ("drain", ("prefill",))])
def test_prefill_time_is_the_prefill_spans_sum(dense, kind, phases):
    eng, _, stats = _serve(dense, kind)
    secs = sum(sum(eng.spans.seconds(p)) for p in phases)
    assert secs > 0
    assert stats["prefill_time_ms"] == pytest.approx(secs * 1e3, rel=1e-12)
    assert stats["prefill_time_ms"] == pytest.approx(
        sum(stats["spans"][p]["total_ms"] for p in phases), rel=1e-12)


def test_decode_timings_come_from_the_decode_spans(dense):
    eng, _, stats = _serve(dense, "chunked_block")
    rounds = [(d + w) / 4 for d, w in zip(eng.spans.seconds("decode_dispatch"),
                                          eng.spans.seconds("decode_wait"))]
    assert stats["tpot_mean_ms"] == pytest.approx(
        np.mean(rounds[1:]) * 1e3, rel=1e-12)
    assert stats["throughput_tok_s"] == pytest.approx(
        stats["decode_tokens"] / (sum(rounds) * 4), rel=1e-12)


@pytest.mark.parametrize("kind", sorted(ENGINES))
def test_ttft_splits_into_queue_lane_and_prefill(dense, kind):
    _, reqs, stats = _serve(dense, kind)
    for m, r in zip(stats["per_request"], reqs):
        assert r.t_admitted <= r.t_first_chunk < r.t_first_token
        assert m["prefill_ms"] > 0
        assert m["queue_delay_ms"] + m["lane_wait_ms"] + m["prefill_ms"] \
            == pytest.approx(m["ttft_ms"], abs=1e-6)


def test_chunked_lane_wait_is_the_time_before_the_first_chunk(dense):
    """With two slots and one chunk per boundary, a request admitted beside
    a prefilling one waits for the lane; its first chunk is its first
    ``chunk_dispatch`` span's start."""
    _, reqs, stats = _serve(dense, "chunked_block")
    assert max(m["lane_wait_ms"] for m in stats["per_request"]) > 0
    lanes = [r.t_first_chunk - r.t_admitted for r in reqs]
    assert [m["lane_wait_ms"] for m in stats["per_request"]] == \
        pytest.approx([x * 1e3 for x in lanes], abs=1e-9)


def test_kv_in_use_share_is_exact_on_a_hand_worked_serve(dense):
    """Two slots, prompts of 5 and 7 tokens, chunks of 8, blocks of 4,
    9 tokens each (the first from the prefill, 8 from two blocks).

    dispatch 1: slot 0 decodes at cursor 5, slot 1 waits for the lane
                (admitted, 0 written, lane depth 1)          -> 5
    dispatch 2: slot 0 at 9, slot 1's one chunk ran, at 7   -> 16
    dispatch 3: slot 0 retired, slot 1 at 11                -> 11
    """
    cfg, api, params = dense
    eng = ServingEngine(api, NULL_CTX, 2, PROMPT_LEN, mode="continuous",
                        max_new_cap=16, block_size=4, prefill_chunk=8)
    reqs = _requests(cfg, [5, 7], max_new=9, every=0)
    stats = eng.run(params, reqs, max_steps=400)
    assert stats["macro_steps"] == 3
    assert eng.spans.samples["kv_in_use"] == [5, 16, 11]
    assert eng.spans.samples["lane_depth"] == [1, 0, 0]
    extent = PROMPT_LEN + 16
    assert eng._kv_extent == extent
    # off a TPU the decode attends with the jnp form: every dispatch reads
    # both slots' whole extent
    assert eng.spans.samples["kv_read"] == [2 * extent] * 3
    assert stats["kv"] == {"reserved_tokens": 2 * extent,
                           "in_use_share_mean": (32 / 3) / (2 * extent),
                           "read_share_mean": 1.0,
                           "lane_depth_mean": 1 / 3}


def test_watchdog_reads_the_dispatch_spans(dense):
    """A decode dispatch held past ``watchdog_s`` counts once per slow
    dispatch; chunk dispatches, quick, do not."""
    import time

    class Slow:
        def on_dispatch(self, name):
            if name.endswith("decode_block"):
                time.sleep(0.15)

    eng, _, stats = _serve(dense, "chunked_block", watchdog_s=0.1,
                           fault_injector=Slow())
    assert stats["watchdog_timeouts"] == stats["macro_steps"]
    assert eng.spans.count("dispatch") == \
        stats["macro_steps"] + stats["prefill_chunks"]


def test_span_records_only_a_normal_exit():
    table = SpanTable()
    with table.span("a", emit=False) as sp:
        pass
    assert table.count("a") == 1 and sp.seconds >= 0
    with pytest.raises(KeyError):
        with table.span("a", emit=False) as sp:
            raise KeyError
    assert table.count("a") == 1 and sp.seconds is None


def test_profiler_records_leaf_serve_spans(dense, tmp_path):
    """On a traced serve the host plane holds ``serve:<phase>`` events of
    the emitted phases only (no enclosing boundary, no in-memory dispatch),
    each chunk event with its request id and slot as stats."""
    from jax.profiler import ProfileData
    cfg, api, params = dense
    eng = ServingEngine(api, NULL_CTX, 2, PROMPT_LEN, mode="continuous",
                        max_new_cap=16, block_size=4, prefill_chunk=3)
    eng.run(params, _requests(cfg, LENGTHS), max_steps=400)   # compile
    reqs = _requests(cfg, LENGTHS)
    with jax.profiler.trace(str(tmp_path)):
        stats = eng.run(params, reqs, max_steps=400)
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    data = ProfileData.from_file(path[0])
    events = [e for plane in data.planes if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events
              if e.name.startswith("serve:")]
    names = {e.name for e in events}
    assert names == {f"serve:{p}" for p in
                     ("policies", "admit", "chunk_dispatch", "chunk_wait",
                      "decode_dispatch", "decode_wait", "unpack")}
    chunks = [dict(e.stats) for e in events if e.name == "serve:chunk_dispatch"]
    assert len(chunks) == stats["prefill_chunks"]
    rids = {r.rid for r in reqs}
    for st in chunks:
        assert st["rid"] in rids and st["slot"] in (0, 1)
