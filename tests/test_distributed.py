"""Multi-device behaviour via SUBPROCESSES that set the host-device-count
flag themselves (the main test process must keep seeing 1 device)."""
import os
import subprocess
import sys
import textwrap


ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(code: str, devices: int = 8, timeout: int = 420) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env.pop("XLA_FLAGS", None)
    prelude = ("import os\n"
               "os.environ['XLA_FLAGS'] = "
               f"'--xla_force_host_platform_device_count={devices}'\n")
    out = subprocess.run([sys.executable, "-c", prelude + textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=timeout)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_main_process_sees_one_device():
    import jax
    assert len(jax.devices()) == 1


def test_executors_differ_operator_centric_pays_in_bytes():
    """The paper's Challenge 2, as it manifests on TPU (EXPERIMENTS §Perf
    cell 1): operator-boundary materialization costs strictly more HLO
    bytes/flops (redundant replicated execution), while the sub-operator
    schedule keeps work on the owning shard. Measured from compiled HLO."""
    out = run_py("""
    import jax, numpy as np
    from jax.sharding import Mesh
    from repro.configs.registry import get_config
    from repro.configs.shapes import ShapeConfig
    from repro.core.execution import make_step

    mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("data", "model"))
    cfg = get_config("internlm2-1.8b").reduced()
    shape = ShapeConfig("t", seq_len=64, global_batch=4, mode="prefill")
    res = {}
    for ex in ("operator_centric", "sub_operator"):
        b = make_step(cfg, shape, mesh, executor=ex)
        comp = b.lower().compile()
        res[ex] = comp.cost_analysis().get("bytes accessed", 0.0)
    print("RESULT", res["operator_centric"], res["sub_operator"])
    assert res["operator_centric"] >= res["sub_operator"], res
    """)
    assert "RESULT" in out


def test_sharded_decode_matches_single_device():
    """GSPMD-sharded decode (2×4 mesh) is numerically identical to the
    unsharded execution."""
    run_py("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh
    from repro.configs.registry import get_config
    from repro.models import NULL_CTX, build_model
    from repro.models.sharding import ShardingCtx, sub_operator

    cfg = get_config("internlm2-1.8b").reduced().replace(dtype="float32")
    api = build_model(cfg)
    params = api.init(jax.random.key(0))
    B, S = 4, 12
    toks = jax.random.randint(jax.random.key(1), (B, S + 1), 0,
                              cfg.vocab_size)
    c0, _ = api.prefill(params, {"tokens": toks[:, :S]}, NULL_CTX)
    _, want = api.decode(params, c0, toks[:, S], NULL_CTX)

    mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("data", "model"))
    ctx = ShardingCtx(mesh, sub_operator())
    with mesh:
        c1, _ = jax.jit(lambda p, b: api.prefill(p, b, ctx))(
            params, {"tokens": toks[:, :S]})
        _, got = jax.jit(lambda p, c, t: api.decode(p, c, t, ctx))(
            params, c1, toks[:, S])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    print("OK")
    """)


def test_hierarchical_psum_correct_and_cheaper_cross_pod():
    run_py("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.core.collectives import hierarchical_psum
    from repro.launch.hlo_analysis import parse_collectives

    mesh = Mesh(np.array(jax.devices()).reshape(2, 2, 2),
                ("pod", "data", "model"))

    def flat(x):
        return jax.lax.psum(x, ("data", "pod"))

    def hier(x):
        return hierarchical_psum(x, "data", "pod", scatter_dim=0)

    x = jnp.arange(16, dtype=jnp.float32).reshape(8, 2)
    outs = {}
    byts = {}
    for name, fn in (("flat", flat), ("hier", hier)):
        # out stays replicated-per-shard: use full specs
        f = jax.jit(jax.shard_map(fn, mesh=mesh,
                                  in_specs=P(("pod", "data"), None),
                                  out_specs=P(),
                                  check_vma=False))
        lowered = f.lower(x)
        comp = lowered.compile()
        outs[name] = np.asarray(comp(x))
        coll = parse_collectives(comp.as_text(), mesh.devices.shape,
                                 mesh.axis_names)
        byts[name] = sum(o.operand_bytes for o in coll.ops
                         if "pod" in o.axes)
    np.testing.assert_allclose(outs["flat"], outs["hier"], rtol=1e-6)
    assert byts["hier"] <= byts["flat"], byts
    print("cross-pod bytes:", byts)
    """)


def test_wa_slotted_decode_matches_colocated():
    """Slot admission in the weight/attention-decoupled path: WA
    decode_step_slotted with STAGGERED per-slot cursors is numerically
    identical to the colocated slotted decode (DESIGN.md §7)."""
    run_py("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh
    from repro.configs.registry import get_config
    from repro.core.wa import WADisaggregated, WAPlan
    from repro.kv.cache import write_slot_kv
    from repro.models import NULL_CTX, build_model

    cfg = get_config("internlm2-1.8b").reduced().replace(dtype="float32")
    api = build_model(cfg)
    params = api.init(jax.random.key(0))
    B, S = 2, 12
    toks = jax.random.randint(jax.random.key(1), (B, S), 0, cfg.vocab_size)
    # joint prefill, then ADMIT a fresh batch-1 prefill into slot 1 so the
    # two slots sit at different depths (slot0 at S, slot1 at 6)
    caches, logits = api.prefill(params, {"tokens": toks}, NULL_CTX)
    c1, l1 = api.prefill(params, {"tokens": toks[1:, :6]}, NULL_CTX)
    caches = write_slot_kv(caches, c1, jnp.asarray(1, jnp.int32))
    cur = jnp.stack([jnp.argmax(logits[0, -1]),
                     jnp.argmax(l1[0, -1])]).astype(jnp.int32)
    positions = jnp.array([S, 6], jnp.int32)
    active = jnp.array([True, True])
    _, want = api.decode_slotted(params, caches, cur, positions, active,
                                 NULL_CTX)

    mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("data", "model"))
    wa = WADisaggregated(cfg, mesh, WAPlan(True, 2, 2, "test"))
    _, got = wa.decode_step_slotted(params, caches, cur, positions, active)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    print("OK")
    """)


def test_wa_backend_serves_on_mesh_matches_colocated():
    """The WA serving backend on a REAL (4,2) mesh: the W/A split becomes
    two sharding regimes over the serving mesh with the routing compiled
    into each program (DESIGN.md §3). A staggered chunked-admission serve
    must produce the colocated backend's exact token streams with
    compiles == 1 for every routed program."""
    run_py("""
    import jax, numpy as np
    from jax.sharding import Mesh
    from repro.configs.registry import get_config
    from repro.models import build_model
    from repro.models.sharding import ShardingCtx, sub_operator
    from repro.runtime.serving import Request, ServingEngine

    cfg = get_config("internlm2-1.8b").reduced().replace(dtype="float32")
    api = build_model(cfg)
    params = api.init(jax.random.key(0))
    mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("data", "model"))
    ctx = ShardingCtx(mesh, sub_operator())

    def reqs():
        rng = np.random.default_rng(0)
        return [Request(rid=i,
                        prompt=rng.integers(0, cfg.vocab_size, 8,
                                            dtype=np.int32),
                        max_new_tokens=n, arrival_step=a)
                for i, (n, a) in enumerate([(6, 0), (10, 0), (6, 2)])]

    kw = dict(mode="continuous", max_new_cap=24, block_size=4,
              kv_bucket_chunk=16, prefill_chunk=4)
    r_co, r_wa = reqs(), reqs()
    ServingEngine(api, ctx, 2, 8, **kw).run(params, r_co, max_steps=300)
    st = ServingEngine(api, ctx, 2, 8, backend="wa", **kw).run(
        params, r_wa, max_steps=300)
    assert st["completed"] == 3
    for name, rec in st["runtime"].items():
        assert rec["compiles"] == 1, (name, rec)
        assert name.startswith("serve_wa_"), name
    assert st["wa"]["routing_total_bytes"] > 0
    for a, b in zip(r_co, r_wa):
        assert a.generated == b.generated, a.rid
    print("OK")
    """)


def test_split_kv_serve_on_8_device_mesh_matches_sequential():
    """Split-KV flash decode on a REAL (1,8) mesh (``make test-long``): the
    WA backend with a_shards=4 spreads each slot's four KV sequence shards
    over the 8-wide A-domain model axis (``seq_sharded_kv``'s "kv_shard"
    rule), computes the partial flash statistics shard-locally, and merges
    the (o, m, l) triples across devices. The token streams must equal the
    colocated sequential walk exactly, with compiles == 1 for every routed
    program — distribution is invisible to both the scheduler and the
    emitted tokens."""
    run_py("""
    import jax, numpy as np
    from jax.sharding import Mesh
    from repro.configs.registry import get_config
    from repro.models import build_model, NULL_CTX
    from repro.models.sharding import ShardingCtx, sub_operator
    from repro.runtime.serving import Request, ServingEngine

    cfg = get_config("internlm2-1.8b").reduced().replace(dtype="float32")
    api = build_model(cfg)
    params = api.init(jax.random.key(0))
    mesh = Mesh(np.array(jax.devices()).reshape(1, 8), ("data", "model"))
    ctx = ShardingCtx(mesh, sub_operator())

    def reqs():
        rng = np.random.default_rng(0)
        # ragged true lengths: one ends inside shard 0 (extent 32 → shard
        # blocks of 8), one crosses a shard boundary mid-decode
        plan = [(6, 0, 5), (10, 0, 8), (6, 2, 7)]
        return [Request(rid=i,
                        prompt=rng.integers(0, cfg.vocab_size, p,
                                            dtype=np.int32),
                        max_new_tokens=n, arrival_step=a)
                for i, (n, a, p) in enumerate(plan)]

    # extent 8 + 24 = 32 cuts into 4 shard blocks of 8
    kw = dict(mode="continuous", max_new_cap=24, block_size=4,
              kv_bucket_chunk=16, prefill_chunk=4)
    r_seq, r_spl = reqs(), reqs()
    # sequential baseline needs no mesh: colocated math on NULL_CTX is the
    # token-exact reference the distributed split walk must reproduce
    ServingEngine(api, NULL_CTX, 2, 8, **kw).run(params, r_seq, max_steps=300)
    st = ServingEngine(api, ctx, 2, 8, backend="wa", a_shards=4, **kw).run(
        params, r_spl, max_steps=300)
    assert st["completed"] == 3
    assert st["a_shards"] == 4
    for name, rec in st["runtime"].items():
        assert rec["compiles"] == 1, (name, rec)
        assert name.startswith("serve_wa_"), name
    for a, b in zip(r_seq, r_spl):
        assert a.generated == b.generated, a.rid
    print("OK")
    """)


def test_pp_decode_lowering_small_mesh():
    """Pipelined decode compiles + runs on a (2,2,2) mesh and every stage's
    KV advances by one position per call."""
    run_py("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh
    from repro.configs.registry import get_config
    from repro.configs.shapes import ShapeConfig
    from repro.core.pipeline import make_pp_step, stage_params
    from repro.models import build_model

    mesh = Mesh(np.array(jax.devices()).reshape(2, 2, 2),
                ("pod", "data", "model"))
    cfg = get_config("internlm2-1.8b").reduced().replace(n_layers=4)
    shape = ShapeConfig("t", seq_len=32, global_batch=4, mode="decode")
    bundle = make_pp_step(cfg, shape, mesh)
    compiled = bundle.lower().compile()
    # run it with real (tiny) values, placed per the compiled shardings
    api = build_model(cfg.replace(kv_dtype="int8"))
    params = jax.device_put(stage_params(api.init(jax.random.key(0)), 2),
                            bundle.in_shardings[0])
    caches = jax.tree.map(lambda s, sh: jax.device_put(
        jnp.zeros(s.shape, s.dtype), sh),
        bundle.abstract_args[1], bundle.in_shardings[1])
    toks = jax.device_put(jnp.ones((2, 4), jnp.int32),
                          bundle.in_shardings[2])
    with mesh:
        caches, logits = compiled(params, caches, toks)
        assert np.asarray(caches["lengths"]).tolist() == [1, 1]
        caches, logits = compiled(params, caches, toks)
        assert np.asarray(caches["lengths"]).tolist() == [2, 2]
    assert logits.shape == (2, 4, 1, cfg.vocab_size)
    print("OK")
    """, devices=8, timeout=420)
