"""The traffic generator: deterministic from the seed, inside its clips,
and the same multiset of lengths for every seed."""
import numpy as np
import pytest

from bench import harness, traffic_gen


@pytest.mark.parametrize("mix_name", ["chat", "code"])
def test_draws_are_deterministic_and_clipped(mix_name):
    mix = harness.load_json("traffic", mix_name)
    seed = 2**31 + 977
    a = traffic_gen.draw(mix, 50, seed, traffic_gen.MEASURED, 1000)
    b = traffic_gen.draw(mix, 50, seed, traffic_gen.MEASURED, 1000)
    for x, y in zip(a, b):
        assert np.array_equal(x.prompt, y.prompt)
        assert x.max_new_tokens == y.max_new_tokens
    p, o = mix["prompt_tokens"], mix["output_tokens"]
    assert all(p["min"] <= len(d.prompt) <= p["max"] for d in a)
    assert all(o["min"] <= d.max_new_tokens <= o["max"] for d in a)
    assert all(d.prompt.dtype == np.int32 and d.prompt.min() >= 0
               and d.prompt.max() < 1000 for d in a)
    # every request fits the cache extent the engine is built with
    ext = traffic_gen.kv_extent(mix, 128)
    assert all(len(d.prompt) + d.max_new_tokens <= ext for d in a)


def test_every_seed_serves_the_same_lengths_with_its_own_tokens():
    mix = harness.load_json("traffic", "chat")
    a = traffic_gen.draw(mix, 40, 1, traffic_gen.MEASURED, 500)
    b = traffic_gen.draw(mix, 40, 2, traffic_gen.MEASURED, 500)
    assert [(len(d.prompt), d.max_new_tokens) for d in a] == \
        [(len(d.prompt), d.max_new_tokens) for d in b]
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_spread_order_mixes_lengths_evenly():
    n = 50
    for step in (traffic_gen.PROMPT_STEP, traffic_gen.OUTPUT_STEP):
        order = traffic_gen.spread_order(n, step)
        assert sorted(order) == list(range(n))
        # every 5 consecutive requests take ranks from at least 3 of the
        # 5 fifths of the sorted lengths
        for i in range(n - 5):
            assert len({r * 5 // n for r in order[i:i + 5]}) >= 3
    # prompt and output ranks are nearly uncorrelated
    p = traffic_gen.spread_order(n, traffic_gen.PROMPT_STEP)
    o = traffic_gen.spread_order(n, traffic_gen.OUTPUT_STEP)
    assert abs(np.corrcoef(p, o)[0, 1]) < 0.2


def test_streams_differ():
    mix = harness.load_json("traffic", "code")
    m = traffic_gen.draw(mix, 8, 3, traffic_gen.MEASURED, 500)
    w = traffic_gen.draw(mix, 8, 3, traffic_gen.WARMUP, 500)
    assert any(not np.array_equal(x.prompt[:8], y.prompt[:8])
               for x, y in zip(m, w))


def test_stratified_lengths_follow_the_distribution():
    spec = {"dist": "lognormal", "median": 256, "sigma": 0.7, "min": 32,
            "max": 896}
    lengths = traffic_gen.stratified_lengths(spec, 1001)
    assert lengths == sorted(lengths)
    assert lengths[500] == 256
    assert lengths[0] >= 32 and lengths[-1] == 896


@pytest.mark.parametrize("mix_name", ["chat", "code"])
def test_every_mix_names_its_source(mix_name):
    """A mix's lengths come from a public trace, named in its file."""
    mix = harness.load_json("traffic", mix_name)
    assert "arXiv:" in mix["source"] or "github.com/" in mix["source"]
    assert str(mix["prompt_tokens"]["median"]) in mix["source"]
    assert str(mix["output_tokens"]["median"]) in mix["source"]
