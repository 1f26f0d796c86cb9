"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref.py oracle.

The partial-softmax combine tests run twice when ``hypothesis`` is
installed (CI — requirements-dev.txt): once property-based over generated
shard statistics, once over a fixed seeded sweep. Without hypothesis the
seeded sweep alone keeps the coverage (no skips)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_decode.combine import (NEG_INF, combine_partial_stats,
                                                merge_partial_stats)
from repro.kernels.flash_decode.flash_decode import flash_decode_pallas
from repro.kernels.flash_decode.ops import flash_decode, flash_decode_partial
from repro.kernels.flash_decode.ref import (flash_decode_ref,
                                            flash_decode_ref_partial)
from repro.kernels.fused_ffn.ops import fused_ffn
from repro.kernels.fused_ffn.ref import fused_ffn_ref
from repro.kernels.gemv.gemv import gemv_int8_pallas
from repro.kernels.gemv.ops import gemv_int8
from repro.kernels.gemv.ref import gemv_int8_ref
from repro.quant.int8 import quantize_int8, quantize_kv

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                       # CI installs it; local runs may not
    HAVE_HYPOTHESIS = False


@pytest.mark.parametrize("B,K,N,bn,bk", [
    (1, 256, 256, 128, 128),
    (4, 1024, 512, 256, 512),
    (8, 512, 1024, 256, 256),
    (16, 2048, 256, 256, 1024),
])
def test_gemv_int8_sweep(B, K, N, bn, bk):
    x = jax.random.normal(jax.random.key(1), (B, K), jnp.float32)
    w = jax.random.normal(jax.random.key(2), (K, N), jnp.float32) * 0.05
    wq = quantize_int8(w, axis=0)
    xq = quantize_int8(x, axis=-1)
    got = gemv_int8_pallas(xq.values, xq.scale, wq.values,
                           wq.scale.reshape(1, -1), block_n=bn, block_k=bk,
                           interpret=True)
    want = gemv_int8_ref(xq.values, xq.scale, wq.values, wq.scale.reshape(1, -1))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,Hq,n_kv,S,hd,bs", [
    (1, 4, 4, 128, 32, 64),     # MHA
    (2, 8, 2, 256, 64, 64),     # GQA
    (3, 16, 1, 192, 32, 64),    # MQA, non-pow2 batch
])
def test_flash_decode_sweep(B, Hq, n_kv, S, hd, bs, dtype):
    q = jax.random.normal(jax.random.key(1), (B, Hq, hd), dtype)
    k = jax.random.normal(jax.random.key(2), (B, n_kv, S, hd), dtype)
    v = jax.random.normal(jax.random.key(3), (B, n_kv, S, hd), dtype)
    lens = jnp.arange(B) * (S // (B + 1)) + S // 2
    mask = jnp.arange(S)[None, :] < lens[:, None]
    got = flash_decode(q, k, v, mask, interpret=True, block_s=bs)
    want = flash_decode_ref(q, k, v, mask)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_flash_decode_kv_limit_matches_full_walk():
    """A kv_limit covering every masked position is a pure fast path — the
    tile early-out must not change numerics; a CUTTING limit equals the ref
    with the limit folded into the mask."""
    B, Hq, n_kv, S, hd = 2, 8, 4, 256, 32
    q = jax.random.normal(jax.random.key(1), (B, Hq, hd), jnp.float32)
    k = jax.random.normal(jax.random.key(2), (B, n_kv, S, hd), jnp.float32)
    v = jax.random.normal(jax.random.key(3), (B, n_kv, S, hd), jnp.float32)
    lens = jnp.array([70, 100])
    mask = jnp.arange(S)[None, :] < lens[:, None]
    want = flash_decode_ref(q, k, v, mask)
    got = flash_decode(q, k, v, mask, interpret=True, block_s=64,
                       kv_limit=jnp.asarray(100))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    got_cut = flash_decode(q, k, v, mask, interpret=True, block_s=64,
                           kv_limit=jnp.asarray(64))
    want_cut = flash_decode_ref(q, k, v, mask, kv_limit=64)
    np.testing.assert_allclose(np.asarray(got_cut), np.asarray(want_cut),
                               rtol=2e-5, atol=2e-5)


def test_flash_decode_kv_limit_is_traced_not_static():
    """Advancing cursors must NOT retrace: the same jitted kernel serves
    every limit value (limit is an operand, not a static arg)."""
    B, Hq, n_kv, S, hd = 1, 4, 4, 128, 32
    q = jax.random.normal(jax.random.key(1), (B, Hq, hd), jnp.float32)
    k = jax.random.normal(jax.random.key(2), (B, n_kv, S, hd), jnp.float32)
    v = jax.random.normal(jax.random.key(3), (B, n_kv, S, hd), jnp.float32)
    traces = []

    def fn(q, k, v, mask, lim):
        traces.append(1)
        return flash_decode(q, k, v, mask, interpret=True, block_s=32,
                            kv_limit=lim)

    jfn = jax.jit(fn)
    for lim in (32, 64, 96):
        mask = jnp.arange(S)[None, :] < lim
        got = jfn(q, k, v, mask, jnp.asarray(lim))
        want = flash_decode_ref(q, k, v, mask)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
    assert len(traces) == 1, "kv_limit change retraced the kernel"


def test_flash_decode_int8_kv():
    B, Hq, n_kv, S, hd = 2, 8, 2, 256, 64
    q = jax.random.normal(jax.random.key(1), (B, Hq, hd), jnp.float32)
    k = jax.random.normal(jax.random.key(2), (B, n_kv, S, hd), jnp.float32)
    v = jax.random.normal(jax.random.key(3), (B, n_kv, S, hd), jnp.float32)
    mask = jnp.ones((B, S), bool)
    kq, ks = quantize_kv(k)
    vq, vs = quantize_kv(v)
    got = flash_decode(q, kq, vq, mask, ks, vs, interpret=True, block_s=64)
    want = flash_decode_ref(q, kq.astype(jnp.float32) * ks,
                            vq.astype(jnp.float32) * vs, mask)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# split-KV partial statistics: Pallas partial mode vs the ref oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode_partial_matches_ref(dtype):
    B, Hq, n_kv, S, hd = 2, 8, 2, 128, 32
    q = jax.random.normal(jax.random.key(1), (B, Hq, hd), dtype)
    k = jax.random.normal(jax.random.key(2), (B, n_kv, S, hd), dtype)
    v = jax.random.normal(jax.random.key(3), (B, n_kv, S, hd), dtype)
    mask = jnp.arange(S)[None, :] < jnp.array([[70], [128]])
    got = flash_decode_partial(q, k, v, mask, interpret=True, block_s=32,
                               kv_limit=jnp.asarray(128))
    want = flash_decode_ref_partial(q, k, v, mask, kv_limit=128)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    for g, w in zip(got, want):
        assert g.dtype == jnp.float32                # stats always f32
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=tol, atol=tol)


def test_flash_decode_partial_limit_empty_is_exact_identity():
    """A shard whose kv_limit skips every tile must return the merge
    identity (0, NEG_INF, 0) BIT-exactly on both paths — appending it to a
    combine cannot perturb a single bit (test_combine_* prove the merge
    side; this pins the producer side)."""
    B, Hq, n_kv, S, hd = 2, 4, 2, 64, 16
    q = jax.random.normal(jax.random.key(1), (B, Hq, hd), jnp.float32)
    k = jax.random.normal(jax.random.key(2), (B, n_kv, S, hd), jnp.float32)
    v = jax.random.normal(jax.random.key(3), (B, n_kv, S, hd), jnp.float32)
    mask = jnp.ones((B, S), bool)
    for impl in (dict(interpret=True, block_s=32), dict(use_pallas=False)):
        o, m, l = flash_decode_partial(q, k, v, mask,
                                       kv_limit=jnp.asarray(0), **impl)
        assert np.array_equal(np.asarray(o), np.zeros_like(np.asarray(o)))
        assert np.array_equal(np.asarray(m),
                              np.full((B, Hq), NEG_INF, np.float32))
        assert np.array_equal(np.asarray(l), np.zeros((B, Hq), np.float32))


def test_flash_decode_sharded_partials_combine_to_full_walk():
    """Four shard-local partial passes (shard-local clamped limits, ragged
    true lengths → one shard ends mid-tile, two are wholly empty) merged by
    combine_partial_stats equal the sequential full-extent walk."""
    B, Hq, n_kv, S, hd, n = 2, 8, 4, 256, 32, 4
    Sb = S // n
    q = jax.random.normal(jax.random.key(1), (B, Hq, hd), jnp.float32)
    k = jax.random.normal(jax.random.key(2), (B, n_kv, S, hd), jnp.float32)
    v = jax.random.normal(jax.random.key(3), (B, n_kv, S, hd), jnp.float32)
    lens = jnp.array([70, 100])
    mask = jnp.arange(S)[None, :] < lens[:, None]
    want = flash_decode_ref(q, k, v, mask)
    parts = []
    for s in range(n):
        lim = int(np.clip(int(lens.max()) - s * Sb, 0, Sb))
        parts.append(flash_decode_partial(
            q, k[:, :, s * Sb:(s + 1) * Sb], v[:, :, s * Sb:(s + 1) * Sb],
            mask[:, s * Sb:(s + 1) * Sb], interpret=True, block_s=32,
            kv_limit=jnp.asarray(lim)))
    got = combine_partial_stats(jnp.stack([p[0] for p in parts]),
                                jnp.stack([p[1] for p in parts]),
                                jnp.stack([p[2] for p in parts]), axis=0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# per-row live ranges [lo, hi): only the tiles a row's range touches
# ---------------------------------------------------------------------------

# (lo, hi) per row over S = 256 in tiles of 64: nothing (hi = 0), one
# position, hi on a tile boundary, the whole extent, a range inside one
# tile, and an empty range past a window (hi <= lo)
RANGES = [(0, 0), (0, 1), (0, 128), (0, 256), (70, 100), (200, 150)]
# a sliding-window lower bound: each row attends the 48 positions ending
# at its cursor
WINDOWED = [(0, 0), (0, 1), (80, 128), (208, 256), (17, 65), (0, 30)]


@pytest.mark.parametrize("hd", [32, 128], ids=["hd32", "hd128"])
@pytest.mark.parametrize("case", ["ragged", "window", "int8", "stacked"])
def test_flash_decode_live_ranges(case, hd):
    """Each row attends exactly [lo, hi), against the oracle with the range
    as its mask; a row with hi <= lo returns 0. hd 32 reads the stacks
    through their positions-minor view, hd 128 as they are."""
    S, bs, n_kv, G, L = 256, 64, 2, 3, 3
    lo, hi = map(jnp.asarray, zip(*(WINDOWED if case == "window"
                                     else RANGES)))
    B = lo.shape[0]
    keys = jax.random.split(jax.random.key(7), 3)
    q = jax.random.normal(keys[0], (B, n_kv * G, hd), jnp.float32)
    k = jax.random.normal(keys[1], (L, B, n_kv, S, hd), jnp.float32)
    v = jax.random.normal(keys[2], (L, B, n_kv, S, hd), jnp.float32)
    layer = 1
    ks = vs = None
    if case == "int8":
        (k, ks), (v, vs) = quantize_kv(k), quantize_kv(v)
    kw = dict(block_s=bs, interpret=True, lo=lo, hi=hi)
    if case == "stacked":
        # a traced layer index into the whole stack: one compiled call
        # serves every layer
        call = jax.jit(lambda i: flash_decode_pallas(q, k, v, None, None,
                                                     None, layer=i, **kw))
        got = call(jnp.int32(layer))
    else:
        got = flash_decode_pallas(
            q, k[layer], v[layer], None if ks is None else ks[layer],
            None if vs is None else vs[layer], None, **kw)
    kl, vl = k[layer], v[layer]
    if case == "int8":
        kl = kl.astype(jnp.float32) * ks[layer]
        vl = vl.astype(jnp.float32) * vs[layer]
    pos = jnp.arange(S)[None]
    mask = (pos >= lo[:, None]) & (pos < hi[:, None])
    want = flash_decode_ref(q, kl, vl, mask)
    live = np.asarray(hi > lo)
    np.testing.assert_allclose(np.asarray(got)[live],
                               np.asarray(want)[live], rtol=2e-5,
                               atol=2e-5)
    assert not np.asarray(got)[~live].any()


# ---------------------------------------------------------------------------
# partial-softmax combine: property-based (hypothesis when available) +
# seeded sweep vs a single-pass float64 reference
# ---------------------------------------------------------------------------

def _check_combine(shard_spec, dtype, seed):
    """shard_spec: [(n_keys, score_offset)] — one entry per shard; n_keys
    of 0 models a shard fully masked out by its kv_limit (the exact merge
    identity), extreme offsets model pathological running maxes. The
    combined output must match a single-pass float64 softmax over the
    concatenated live keys, and appending identity shards must not flip a
    single output bit."""
    hd = 8
    rng = np.random.default_rng(seed)
    scores, values = [], []
    for n_keys, off in shard_spec:
        s = (rng.standard_normal(n_keys) + off).astype(np.float32)
        scores.append(np.asarray(jnp.asarray(s, dtype), np.float32))
        values.append(np.asarray(
            jnp.asarray(rng.standard_normal((n_keys, hd)), dtype),
            np.float32))
    os, ms, ls = [], [], []
    for s, val in zip(scores, values):
        if len(s) == 0:
            os.append(np.zeros(hd, np.float32))
            ms.append(np.float32(NEG_INF))
            ls.append(np.float32(0.0))
        else:
            m = s.max()
            p = np.exp(s - m, dtype=np.float32)
            os.append(p @ val)
            ms.append(np.float32(m))
            ls.append(p.sum(dtype=np.float32))
    o = jnp.asarray(np.stack(os), dtype)
    m = jnp.asarray(np.stack(ms), dtype)
    l = jnp.asarray(np.stack(ls), dtype)
    got = np.asarray(combine_partial_stats(o, m, l, axis=0))
    assert np.isfinite(got).all(), got
    live = np.concatenate([s for s in scores if len(s)] or
                          [np.zeros(0, np.float32)])
    if len(live) == 0:
        np.testing.assert_array_equal(got, np.zeros(hd, np.float32))
    else:
        vals = np.concatenate([v for v in values if len(v)])
        p = np.exp(live.astype(np.float64) - live.max())
        want = (p[:, None] * vals).sum(0) / p.sum()
        tol = 1e-5 if dtype == jnp.float32 else 4e-2
        np.testing.assert_allclose(got, want, rtol=tol, atol=tol)
    # bit-stability: identity shards (empty via kv_limit) are free to append
    o2 = jnp.concatenate([o, jnp.zeros((2, hd), dtype)])
    m2 = jnp.concatenate([m, jnp.full((2,), NEG_INF, dtype)])
    l2 = jnp.concatenate([l, jnp.zeros((2,), dtype)])
    assert np.array_equal(np.asarray(combine_partial_stats(o2, m2, l2)), got)
    # ...and the merge is associative: left-fold == flat combine (bitwise
    # would over-promise across regrouping; the LSE algebra is exact)
    o12, m12, l12 = merge_partial_stats(o[:1 + len(shard_spec) // 2],
                                        m[:1 + len(shard_spec) // 2],
                                        l[:1 + len(shard_spec) // 2])
    ot = jnp.concatenate([o12[None].astype(dtype),
                          o[1 + len(shard_spec) // 2:]])
    mt = jnp.concatenate([m12[None].astype(dtype),
                          m[1 + len(shard_spec) // 2:]])
    lt = jnp.concatenate([l12[None].astype(dtype),
                          l[1 + len(shard_spec) // 2:]])
    tree = np.asarray(combine_partial_stats(ot, mt, lt))
    tol = 1e-6 if dtype == jnp.float32 else 4e-2
    np.testing.assert_allclose(tree, got, rtol=tol, atol=tol)


# a fixed sweep covering the hypothesis search space's corners: empty
# shards first/last/everywhere, extreme maxes both directions, singletons
_COMBINE_CASES = [
    [(4, 0.0), (4, 0.0)],
    [(0, 0.0), (5, 0.0), (3, 0.0)],
    [(6, 1e4), (6, -1e4)],
    [(1, 300.0), (8, 0.0), (0, 0.0), (2, -300.0)],
    [(0, 0.0), (0, 0.0)],
    [(8, -1e4), (0, 0.0), (1, 1e4)],
    [(2, 50.0), (2, 49.0), (2, 48.0)],
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", range(len(_COMBINE_CASES)))
@pytest.mark.parametrize("seed", [0, 1])
def test_combine_seeded_sweep(case, dtype, seed):
    _check_combine(_COMBINE_CASES[case], dtype, seed)


if HAVE_HYPOTHESIS:
    @settings(max_examples=50, deadline=None)
    @given(spec=st.lists(st.tuples(st.integers(0, 8),
                                   st.floats(-1e4, 1e4, allow_nan=False)),
                         min_size=1, max_size=6),
           seed=st.integers(0, 2**31 - 1),
           dtype_idx=st.integers(0, 1))
    def test_combine_property(spec, seed, dtype_idx):
        _check_combine(spec, (jnp.float32, jnp.bfloat16)[dtype_idx], seed)


@pytest.mark.parametrize("act", ["silu", "gelu"])
@pytest.mark.parametrize("B,D,F,bf", [
    (2, 64, 256, 128),
    (4, 128, 512, 512),
    (8, 256, 384, 128),
])
def test_fused_ffn_sweep(B, D, F, bf, act):
    x = jax.random.normal(jax.random.key(4), (B, D), jnp.float32)
    wg = jax.random.normal(jax.random.key(5), (D, F), jnp.float32) * 0.1
    wu = jax.random.normal(jax.random.key(6), (D, F), jnp.float32) * 0.1
    wd = jax.random.normal(jax.random.key(7), (F, D), jnp.float32) * 0.1
    got = fused_ffn(x, wg, wu, wd, act=act, interpret=True, block_f=bf,
                    out_dtype=jnp.float32)
    want = fused_ffn_ref(x, wg, wu, wd, act=act)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("op", ["flash_decode", "fused_ffn", "gemv_int8"])
def test_pallas_off_tpu_raises_unless_interpreted(op):
    """Asking for the Pallas kernel on a backend that is not a TPU raises:
    the kernel runs in interpret mode only when the caller passes
    interpret=True, never as a silent fallback."""
    if jax.default_backend() == "tpu":
        pytest.skip("the kernel runs natively on a TPU")
    x = jnp.ones((2, 128), jnp.float32)
    w = jnp.ones((128, 256), jnp.float32) * 0.01
    calls = {
        "flash_decode": lambda **kw: flash_decode(
            jnp.ones((1, 2, 16)), jnp.ones((1, 1, 32, 16)),
            jnp.ones((1, 1, 32, 16)), jnp.ones((1, 32), bool), block_s=32,
            **kw),
        "fused_ffn": lambda **kw: fused_ffn(x, w, w, w.T, block_f=128,
                                            out_dtype=jnp.float32, **kw),
        "gemv_int8": lambda **kw: gemv_int8(x, quantize_int8(w, axis=0),
                                            out_dtype=jnp.float32, **kw),
    }
    with pytest.raises(ValueError, match="interpret=True"):
        calls[op](use_pallas=True)
    got = calls[op](use_pallas=True, interpret=True)
    want = calls[op](use_pallas=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-2, atol=1e-2)
