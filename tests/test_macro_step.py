"""Macro-step decode tests (DESIGN.md §7 "macro-step scheduling").

Covers the invariants the macro-step ISSUE demands:
- ``decode_block(T)`` is token-EXACT against T sequential ``decode_slotted``
  steps (transformer + ssm families, int8 KV on/off),
- per-slot on-device halting stops exactly at the token budget / EOS id,
- the chunk-bucketed (length-aware) decode matches full-extent numerics,
- the block program compiles exactly once across staggered admissions,
- host syncs per generated token drop from 1 to 1/T (counted hook),
- engine reuse across ``run()`` calls starts from clean state,
- ``debug_reset_slots`` zeroes retired slots.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import ASSIGNED
from repro.models import NULL_CTX, build_model
from repro.models.attention import (bucket_for, decode_attention,
                                    decode_attention_bucketed, kv_buckets)
from repro.runtime.serving import Request, ServingEngine
from repro.runtime.static_runtime import StaticRuntime

PROMPT_LEN = 8
T = 8


@pytest.fixture(scope="module")
def dense():
    cfg = ASSIGNED["qwen2-0.5b"].reduced()
    api = build_model(cfg)
    return cfg, api, api.init(jax.random.key(0))


@pytest.fixture(scope="module")
def dense_f32():
    # split-KV einsums take bf16 operands into an f32 accumulator, a dot the
    # CPU backend does not implement; f32 keeps the programs compilable here
    cfg = ASSIGNED["qwen2-0.5b"].reduced().replace(dtype="float32")
    api = build_model(cfg)
    return cfg, api, api.init(jax.random.key(0))


@pytest.fixture(scope="module")
def dense_int8():
    cfg = ASSIGNED["qwen2-0.5b"].reduced().replace(kv_dtype="int8")
    api = build_model(cfg)
    return cfg, api, api.init(jax.random.key(0))


@pytest.fixture(scope="module")
def ssm():
    cfg = ASSIGNED["mamba2-1.3b"].reduced()
    api = build_model(cfg)
    return cfg, api, api.init(jax.random.key(0))


def _requests(cfg, plan, seed=0):
    """plan: list of (max_new, arrival_step). Seeded per call so identical
    plans produce identical prompts across engines."""
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size, PROMPT_LEN,
                                        dtype=np.int32),
                    max_new_tokens=new, arrival_step=arr)
            for i, (new, arr) in enumerate(plan)]


def _sequential_reference(api, params, caches, cur, pos, act, rem, steps):
    """T single slotted steps with the SAME halt logic the block runs on
    device — the oracle decode_block must match token-for-token."""
    toks, emits = [], []
    for _ in range(steps):
        caches, logits = api.decode_slotted(params, caches, cur, pos, act,
                                            NULL_CTX)
        nxt = jnp.argmax(logits[:, 0], -1).astype(jnp.int32)
        nxt = jnp.where(act, nxt, 0)
        toks.append(np.asarray(nxt))
        emits.append(np.asarray(act))
        pos = pos + act.astype(jnp.int32)
        rem = rem - act.astype(jnp.int32)
        act = act & (rem > 0)
        cur = nxt
    return caches, np.stack(toks), np.stack(emits)


# ---------------------------------------------------------------------------
# decode_block == T sequential slotted steps (token-exact)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fixture", ["dense", "dense_int8", "ssm"])
def test_decode_block_token_exact(fixture, request):
    cfg, api, params = request.getfixturevalue(fixture)
    toks = jax.random.randint(jax.random.key(1), (2, PROMPT_LEN), 0,
                              cfg.vocab_size)
    c0, logits = api.prefill(params, {"tokens": toks}, NULL_CTX)
    cur = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
    pos = jnp.full((2,), PROMPT_LEN, jnp.int32)
    act = jnp.array([True, True])
    rem = jnp.array([T, T - 3], jnp.int32)       # row 1 halts mid-block
    eos = jnp.full((2,), -1, jnp.int32)
    c_ref, want_toks, want_emit = _sequential_reference(
        api, params, c0, cur, pos, act, rem, T)
    c1, logits1 = api.prefill(params, {"tokens": toks}, NULL_CTX)
    c_blk, blk_toks, emitted, last, pos_o, act_o, rem_o = jax.jit(
        lambda *xs: api.decode_block(*xs, NULL_CTX, block_size=T))(
        params, c1, cur, pos, act, rem, eos)
    np.testing.assert_array_equal(np.asarray(blk_toks), want_toks)
    np.testing.assert_array_equal(np.asarray(emitted), want_emit)
    assert np.asarray(pos_o).tolist() == [PROMPT_LEN + T,
                                          PROMPT_LEN + T - 3]
    assert np.asarray(rem_o).tolist() == [0, 0]
    assert np.asarray(act_o).tolist() == [False, False]
    # cache state equal too (KV families: byte-identical stored buffers)
    ref_leaves = jax.tree_util.tree_leaves(c_ref)
    blk_leaves = jax.tree_util.tree_leaves(c_blk)
    for a, b in zip(ref_leaves, blk_leaves):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_decode_block_halts_exactly_at_budget(dense):
    """remaining=k emits exactly k tokens then idles: token id 0, no
    position advance, no emission bit — regardless of how many micro-steps
    the block still runs."""
    cfg, api, params = dense
    toks = jnp.ones((2, PROMPT_LEN), jnp.int32)
    c0, logits = api.prefill(params, {"tokens": toks}, NULL_CTX)
    cur = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
    rem = jnp.array([2, 5], jnp.int32)
    _, toks_o, emitted, _, pos_o, act_o, _ = jax.jit(
        lambda *xs: api.decode_block(*xs, NULL_CTX, block_size=T))(
        params, c0, cur, jnp.full((2,), PROMPT_LEN, jnp.int32),
        jnp.array([True, True]), rem, jnp.full((2,), -1, jnp.int32))
    emitted = np.asarray(emitted)
    assert emitted[:, 0].sum() == 2 and emitted[:, 1].sum() == 5
    assert emitted[:2, 0].all() and not emitted[2:, 0].any()
    assert np.asarray(toks_o)[2:, 0].tolist() == [0] * (T - 2)
    assert np.asarray(pos_o).tolist() == [PROMPT_LEN + 2, PROMPT_LEN + 5]
    assert not np.asarray(act_o).any()


def _first_new_at(stream, step):
    """True when stream[step] occurs nowhere before it — an EOS id taken
    from that step then halts exactly there (a random model often repeats
    one token, which would halt at an earlier step)."""
    return stream[step] not in list(stream[:step])


def test_decode_block_eos_halts_on_device(dense):
    """Generate without EOS, pick the token emitted at micro-step 3, rerun
    with that id as the slot's EOS operand: the slot must emit it and halt
    — entirely on device, no host intervention."""
    cfg, api, params = dense
    blk = jax.jit(lambda *xs: api.decode_block(*xs, NULL_CTX, block_size=T))
    for seed in range(2, 34):          # a prompt whose step-3 token is new
        toks = jax.random.randint(jax.random.key(seed), (2, PROMPT_LEN), 0,
                                  cfg.vocab_size)
        c0, logits = api.prefill(params, {"tokens": toks}, NULL_CTX)
        cur = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
        args = (cur, jnp.full((2,), PROMPT_LEN, jnp.int32),
                jnp.array([True, True]), jnp.full((2,), T, jnp.int32))
        _, toks_free, _, _, _, _, _ = blk(params, c0, *args,
                                          jnp.full((2,), -1, jnp.int32))
        if _first_new_at(np.asarray(toks_free)[:, 0].tolist(), 3):
            break
    else:
        pytest.fail("no prompt seed gives a new token at micro-step 3")
    stop = int(np.asarray(toks_free)[3, 0])
    c1, _ = api.prefill(params, {"tokens": toks}, NULL_CTX)
    _, toks_eos, emitted, _, _, act_o, _ = blk(
        params, c1, *args, jnp.array([stop, -1], jnp.int32))
    emitted = np.asarray(emitted)
    assert emitted[:, 0].sum() == 4                 # halted after the EOS
    assert int(np.asarray(toks_eos)[3, 0]) == stop
    assert not np.asarray(act_o)[0]
    assert emitted[:, 1].all()                      # row 1 unaffected


# ---------------------------------------------------------------------------
# length-aware (chunk-bucketed) KV walking
# ---------------------------------------------------------------------------

def test_kv_bucket_helpers():
    assert kv_buckets(136, 64) == (64, 128, 136)
    assert kv_buckets(128, 64) == (64, 128)
    assert kv_buckets(64, 0) == (64,)
    assert kv_buckets(32, 64) == (32,)
    assert bucket_for(10, (64, 128, 136)) == 64
    assert bucket_for(65, (64, 128, 136)) == 128
    assert bucket_for(999, (64, 128, 136)) == 136
    # split-KV (shards > 1): every bucket must cut into equal shard blocks
    # — the chunk stride rounds UP to a shard multiple, never truncates
    assert kv_buckets(128, 64, shards=2) == (64, 128)
    assert kv_buckets(128, 24, shards=8) == (24, 48, 72, 96, 120, 128)
    assert kv_buckets(128, 20, shards=8) == (24, 48, 72, 96, 120, 128)
    assert kv_buckets(40, 16, shards=4) == (16, 32, 40)
    assert kv_buckets(64, 60, shards=8) == (64,)
    with pytest.raises(ValueError, match="not divisible"):
        kv_buckets(130, 64, shards=4)


def test_decode_attention_bucketed_matches_full():
    key = jax.random.key(0)
    B, Hq, n_kv, S, hd = 2, 8, 4, 96, 32
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, Hq, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, n_kv, S, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, n_kv, S, hd), jnp.float32)
    mask = jnp.arange(S)[None, :] < jnp.array([[20], [31]])
    want = decode_attention(q, k, v, mask, NULL_CTX)
    got = decode_attention_bucketed(q, k, v, mask, NULL_CTX, kv_bucket=32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    # identity buckets
    for b in (0, S, S + 32):
        same = decode_attention_bucketed(q, k, v, mask, NULL_CTX, kv_bucket=b)
        np.testing.assert_array_equal(np.asarray(same), np.asarray(want))


@pytest.mark.parametrize("fixture", ["dense", "dense_int8"])
def test_bucketed_slotted_decode_matches_full_extent(fixture, request):
    """decode_slotted under a covering kv_bucket equals the full-extent
    walk bit-for-bit on logits AND stored cache (the bucket only trims the
    attended read, never the append)."""
    cfg, api, params = request.getfixturevalue(fixture)
    toks = jax.random.randint(jax.random.key(3), (2, PROMPT_LEN), 0,
                              cfg.vocab_size)
    c0, logits = api.prefill(params, {"tokens": toks}, NULL_CTX)
    cur = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
    pos = jnp.full((2,), PROMPT_LEN, jnp.int32)
    act = jnp.array([True, True])
    c_full, lg_full = jax.jit(lambda *xs: api.decode_slotted(*xs, NULL_CTX))(
        params, c0, cur, pos, act)
    c1, _ = api.prefill(params, {"tokens": toks}, NULL_CTX)
    c_bkt, lg_bkt = jax.jit(lambda *xs: api.decode_slotted(
        *xs, NULL_CTX, kv_bucket=16))(params, c1, cur, pos, act)
    np.testing.assert_allclose(np.asarray(lg_bkt), np.asarray(lg_full),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(c_bkt.k), np.asarray(c_full.k))
    np.testing.assert_array_equal(np.asarray(c_bkt.v), np.asarray(c_full.v))


# ---------------------------------------------------------------------------
# engine: macro-step loop
# ---------------------------------------------------------------------------

PLAN = [(9, 0), (13, 0), (5, 2), (9, 6)]


def test_engine_block_tokens_equal_per_step_engine(dense):
    cfg, api, params = dense
    r1 = _requests(cfg, PLAN)
    ServingEngine(api, NULL_CTX, 2, PROMPT_LEN, mode="continuous",
                  max_new_cap=32).run(params, r1, max_steps=400)
    rT = _requests(cfg, PLAN)
    stats = ServingEngine(api, NULL_CTX, 2, PROMPT_LEN, mode="continuous",
                          max_new_cap=32, block_size=4,
                          kv_bucket_chunk=16).run(params, rT, max_steps=400)
    assert stats["completed"] == len(PLAN)
    for a, b in zip(r1, rT):
        assert a.generated == b.generated, a.rid


@pytest.mark.parametrize("a_shards", [1, 2])
def test_block_programs_compile_once_across_admissions(dense_f32, a_shards):
    """Zero retracing (§4.3 invariant) extends to the macro-step regime:
    prefill1, admit, and EVERY decode-block bucket compile exactly once
    while calls grow across staggered admissions. Split-KV decode
    (a_shards > 1) keeps the SAME program names and the same bucket set —
    the shard count is a build-time static baked into each program, so the
    invariant (and this assertion set) cannot drift with the width."""
    cfg, api, params = dense_f32
    rt = StaticRuntime()
    eng = ServingEngine(api, NULL_CTX, 2, PROMPT_LEN, runtime=rt,
                        mode="continuous", max_new_cap=32, block_size=4,
                        kv_bucket_chunk=16, a_shards=a_shards)
    stats = eng.run(params, _requests(cfg, PLAN), max_steps=400)
    assert stats["completed"] == len(PLAN)
    rs = stats["runtime"]
    # buckets fixed at prepare: s_max = 8 + 32 = 40, chunk 16 → 16/32/40
    # (every bucket divides by a_shards=2, so the set is width-invariant)
    assert {"serve_prefill1", "serve_admit", "serve_decode_block_s16",
            "serve_decode_block_s32", "serve_decode_block_s40"} <= set(rs)
    for name, rec in rs.items():
        assert rec["compiles"] == 1, (name, rec)
    assert sum(rec["calls"] for n, rec in rs.items()
               if n.startswith("serve_decode_block")) == stats["macro_steps"]


def test_block_programs_compile_once_across_shard_resident_lengths(
        dense_f32):
    """Cursor positions that land inside different shard blocks (shard 0
    only, mid-shard 1, the full extent) must all route through the SAME
    per-bucket programs — shard-resident length is traced state, never a
    compile key. Two runs with different length mixes: still one compile
    per program."""
    cfg, api, params = dense_f32
    rt = StaticRuntime()
    eng = ServingEngine(api, NULL_CTX, 2, PROMPT_LEN, runtime=rt,
                        mode="continuous", max_new_cap=32, block_size=4,
                        kv_bucket_chunk=16, a_shards=2)
    # short run: cursors stay inside shard 0 (extent 40 → blocks of 20)
    s1 = eng.run(params, _requests(cfg, [(4, 0), (4, 0)]), max_steps=400)
    # long run: cursors cross into shard 1 (8 + 24 = 32 > 20)
    s2 = eng.run(params, _requests(cfg, [(24, 0), (13, 2)]), max_steps=400)
    assert s1["completed"] == 2 and s2["completed"] == 2
    for name, rec in s2["runtime"].items():
        assert rec["compiles"] == 1, (name, rec)


def test_host_syncs_drop_by_block_size(dense):
    """The counted hook: syncs per generated token fall from 1/batch (per
    decode step) to 1/(T·batch) — exactly a T× reduction on an aligned
    workload."""
    cfg, api, params = dense
    plan = [(9, 0), (9, 0)]                      # 8 decode tokens each
    r1 = _requests(cfg, plan)
    e1 = ServingEngine(api, NULL_CTX, 2, PROMPT_LEN, mode="continuous",
                      max_new_cap=32)
    s1 = e1.run(params, r1, max_steps=100)
    rT = _requests(cfg, plan)
    eT = ServingEngine(api, NULL_CTX, 2, PROMPT_LEN, mode="continuous",
                       max_new_cap=32, block_size=4)
    sT = eT.run(params, rT, max_steps=100)
    assert s1["decode_tokens"] == sT["decode_tokens"] == 16
    assert e1.host_syncs == 8                    # one per decode step
    assert eT.host_syncs == 2                    # one per block of T=4
    assert eT.host_syncs * 4 == e1.host_syncs
    assert sT["syncs_per_token"] == pytest.approx(s1["syncs_per_token"] / 4)


def test_engine_reuse_starts_clean(dense):
    """Satellite: ``run()`` on a used engine must not leak tpot samples,
    sync counts or cache state from the previous run."""
    cfg, api, params = dense
    eng = ServingEngine(api, NULL_CTX, 2, PROMPT_LEN, mode="continuous",
                        max_new_cap=32, block_size=4)
    ra = _requests(cfg, PLAN)
    sa = eng.run(params, ra, max_steps=400)
    rb = _requests(cfg, PLAN)
    sb = eng.run(params, rb, max_steps=400)
    assert sb["completed"] == sa["completed"]
    assert sb["host_syncs"] == sa["host_syncs"]          # not accumulated
    assert sb["decode_tokens"] == sa["decode_tokens"]
    assert eng.spans.count("decode_dispatch") == sa["macro_steps"]
    for a, b in zip(ra, rb):
        assert a.generated == b.generated                # fresh caches


def test_throughput_counts_only_decode_tokens(dense):
    """Satellite: prefill-produced first tokens are excluded from the
    decode-throughput numerator (their cost is not in the denominator)."""
    cfg, api, params = dense
    reqs = _requests(cfg, PLAN)
    stats = ServingEngine(api, NULL_CTX, 2, PROMPT_LEN, mode="continuous",
                          max_new_cap=32, block_size=4).run(
        params, reqs, max_steps=400)
    n_dec = sum(len(r.generated) - 1 for r in reqs)      # minus prefill token
    assert stats["decode_tokens"] == n_dec
    assert stats["tokens_per_macro_step_mean"] == pytest.approx(
        n_dec / stats["macro_steps"])
    assert stats["throughput_tok_s"] > 0


def test_debug_reset_slots_zeroes_retired(dense):
    cfg, api, params = dense
    # include a 1-token request: it retires AT admission (prefill-only) but
    # its prompt KV was written — reset must cover that path too
    plan = PLAN + [(1, 4)]
    eng = ServingEngine(api, NULL_CTX, 2, PROMPT_LEN, mode="continuous",
                        max_new_cap=32, block_size=4, debug_reset_slots=True)
    stats = eng.run(params, _requests(cfg, plan), max_steps=400)
    assert stats["completed"] == len(plan)
    assert stats["runtime"]["serve_reset"]["compiles"] == 1
    assert stats["runtime"]["serve_reset"]["calls"] == len(plan)
    # every request retired → every slot zeroed (clean dumps)
    assert not np.asarray(eng._caches.k).any()
    assert not np.asarray(eng._caches.v).any()


def test_ssm_family_serves_in_block_mode(ssm):
    """Attention-free families run the same macro-step loop (single
    full-extent block program — no KV length axis to bucket)."""
    cfg, api, params = ssm
    plan = [(6, 0), (10, 0), (6, 2)]
    r1 = _requests(cfg, plan)
    ServingEngine(api, NULL_CTX, 2, PROMPT_LEN, mode="continuous",
                  max_new_cap=32).run(params, r1, max_steps=200)
    rT = _requests(cfg, plan)
    rt = StaticRuntime()
    stats = ServingEngine(api, NULL_CTX, 2, PROMPT_LEN, runtime=rt,
                          mode="continuous", max_new_cap=32, block_size=4,
                          kv_bucket_chunk=16).run(params, rT, max_steps=200)
    assert stats["completed"] == 3
    assert stats["runtime"]["serve_decode_block"]["compiles"] == 1
    for a, b in zip(r1, rT):
        assert a.generated == b.generated, a.rid


def test_engine_eos_request_halts_early(dense):
    cfg, api, params = dense
    eng = ServingEngine(api, NULL_CTX, 2, PROMPT_LEN, mode="continuous",
                        max_new_cap=32)
    for seed in range(32):             # a prompt whose 4th token is new
        probe = _requests(cfg, [(9, 0)], seed=seed)
        eng.run(params, probe, max_steps=100)
        if _first_new_at(probe[0].generated, 3):
            break
    else:
        pytest.fail("no prompt seed gives a new token at index 3")
    stop = probe[0].generated[3]
    reqs = _requests(cfg, [(9, 0)], seed=seed)
    reqs[0].eos_id = stop
    ServingEngine(api, NULL_CTX, 2, PROMPT_LEN, mode="continuous",
                  max_new_cap=32, block_size=4).run(params, reqs,
                                                    max_steps=100)
    assert reqs[0].generated == probe[0].generated[:4]


def test_one_token_requests_do_not_idle_the_slot(dense):
    """A request that completes at its first (prefill) token must not park
    the slot until the next block boundary: admission retries the same slot
    within the boundary."""
    cfg, api, params = dense
    reqs = _requests(cfg, [(1, 0), (1, 0), (5, 0)])
    stats = ServingEngine(api, NULL_CTX, 1, PROMPT_LEN, mode="continuous",
                          max_new_cap=32, block_size=4).run(
        params, reqs, max_steps=100)
    assert stats["completed"] == 3
    assert [r.admit_step for r in reqs] == [0, 0, 0]
    assert len(reqs[2].generated) == 5


def test_invalid_block_size_rejected(dense):
    cfg, api, params = dense
    with pytest.raises(ValueError):
        ServingEngine(api, NULL_CTX, 2, PROMPT_LEN, block_size=0)
    with pytest.raises(ValueError):
        ServingEngine(api, NULL_CTX, 2, PROMPT_LEN, prefill_chunk=-1)
