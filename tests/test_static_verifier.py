"""Static program verifier (repro.analysis): positive runs over real cells
plus one NEGATIVE test per pass — each deliberately-broken program must
produce an actionable diagnostic naming the program and the operand.

Residency needs a real multi-device mesh, so its tests run in subprocesses
(the main test process must keep seeing 1 device; see conftest.py). Every
other pass is exercised in-process — on a 1-device mesh the W↔A hops are
still tagged, so even the routing cross-check runs for real.
"""
import dataclasses
import os
import subprocess
import sys
import textwrap
import warnings
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest
from jax.experimental import pallas as pl

from repro.analysis import compile_once, host_sync, kernel_bounds, residency
from repro.analysis import routing_check
from repro.analysis.findings import Report
from repro.analysis.jaxpr_walk import iter_eqns, literal_value
from repro.analysis.programs import (CellSpec, build_cell, ci_matrix,
                                     classify, full_matrix, make_mesh)
from repro.analysis.verify import verify_cell
from repro.runtime.static_runtime import StaticRuntime

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(code: str, devices: int = 8, timeout: int = 420) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env.pop("XLA_FLAGS", None)
    prelude = ("import os\n"
               "os.environ['XLA_FLAGS'] = "
               f"'--xla_force_host_platform_device_count={devices}'\n")
    out = subprocess.run(
        [sys.executable, "-c", prelude + textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=timeout)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


# ---------------------------------------------------------------------------
# fixtures: real cells, built once
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def nomesh_cell():
    return build_cell(CellSpec(label="colocated-nomesh"), None)


@pytest.fixture(scope="module")
def wa_cell():
    # 1-device mesh: hops are tagged (mesh non-empty) so the routing
    # cross-check runs for real; residency is vacuously satisfiable
    return build_cell(CellSpec(label="wa-1dev", backend="wa"),
                      make_mesh(1, 1))


# ---------------------------------------------------------------------------
# positive: real cells are clean end to end
# ---------------------------------------------------------------------------

def test_nomesh_cell_verifies_clean(nomesh_cell):
    rep = verify_cell(nomesh_cell)
    assert rep.ok, rep.format(verbose=True)
    assert nomesh_cell.records, "cell built no programs"


def test_wa_cell_verifies_clean(wa_cell):
    rep = verify_cell(wa_cell)
    assert rep.ok, rep.format(verbose=True)
    names = {r.name for r in wa_cell.records}
    assert any(n.startswith("serve_wa_decode_block") for n in names)


def test_routing_confirms_analytic_meter(wa_cell):
    """The bytes identity holds exactly — the pass leaves an INFO record
    with the confirmed per-dispatch analytic bytes for each WA program."""
    rep = Report()
    routing_check.check_routing(wa_cell, rep)
    assert rep.ok, rep.format(verbose=True)
    infos = [f for f in rep.findings if f.severity == "info"]
    assert any("confirmed" in f.message for f in infos),\
        rep.format(verbose=True)


def test_matrices_cover_acceptance_grid():
    ci = ci_matrix()
    assert len(ci) == 12
    assert {s.backend for s in ci} == {"colocated", "wa"}
    assert {s.a_shards for s in ci} == {1, 2, 4}
    # tiered-KV cells gate the hot-ring/cold-tier program variants on both
    # backends, including the monolithic (degenerate-chunk) admission lane
    tiered = [s for s in ci if s.hot_window > 0]
    assert {s.label for s in tiered} == {"colocated-int8cold-mono",
                                         "wa-int4cold-a2"}
    assert {s.kv_cold_dtype for s in tiered} == {"int8", "int4"}
    # sub-operator overlap cells gate the pipelined decode programs; their
    # slot count must split into equal micro-batches
    ov = [s for s in ci if s.overlap > 1]
    assert {s.overlap for s in ov} == {2, 4}
    assert all(s.backend == "wa" and s.slots % s.overlap == 0 for s in ov)
    full = full_matrix()
    labels = {s.label for s in full}
    assert {"colocated-dense-a1-mono", "wa-dense-a2",
            "wa-dense-a1-T1", "wa-dense-a1-T1-ov2"} <= labels


def test_classify_kinds():
    assert classify("serve_prefill_chunk") == "chunk"
    assert classify("serve_wa_admit") == "chunk"
    assert classify("serve_decode_block_s16") == "block"
    assert classify("serve_admit") == "admit"
    assert classify("serve_reset") == "reset"
    assert classify("serve_decode") == "decode"


def test_verify_cli_no_mesh_exits_zero():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run(
        [sys.executable, "-m", "repro.analysis.verify", "--no-mesh",
         "--preset", "ci", "--cell", "colocated-dense-a1"],
        capture_output=True, text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr[-2000:]
    assert "PASS" in out.stdout


# ---------------------------------------------------------------------------
# pass 2 negatives: compile-once
# ---------------------------------------------------------------------------

def test_compile_once_flags_signature_drift():
    rt = StaticRuntime(None)
    rt.compile_step("serve_x", lambda x: x + 1, (jnp.zeros((2,)),))
    rt.compile_step("serve_x", lambda x: x + 1, (jnp.zeros((4,)),))
    rep = Report()
    compile_once.audit_runtime(rt, rep)
    errs = [f for f in rep.errors if f.program == "serve_x"]
    assert errs, rep.format(verbose=True)
    assert "2 distinct operand signatures" in errs[0].message


def test_compile_once_flags_weak_typed_leaf():
    rt = StaticRuntime(None)
    weak = jnp.asarray(1.0)             # bare python scalar → weak f32
    assert weak.weak_type
    rt.compile_step("serve_weak", lambda x: x * 2, (weak,))
    rep = Report()
    compile_once.audit_runtime(rt, rep)
    errs = [f for f in rep.errors if f.program == "serve_weak"]
    assert errs and "weak-typed" in errs[0].message, rep.format(verbose=True)


def test_compile_once_warns_on_non_serve_name():
    rt = StaticRuntime(None)
    rt.compile_step("adhoc_step", lambda x: x, (jnp.zeros((2,)),))
    rep = Report()
    compile_once.audit_runtime(rt, rep)
    assert any(f.program == "adhoc_step" for f in rep.warnings)


# ---------------------------------------------------------------------------
# pass 3 negatives: host-sync
# ---------------------------------------------------------------------------

def _record(rt, name, fn, args, kind=None, roles=None, **kw):
    from repro.analysis.programs import ProgramRecord
    step = rt.compile_step(name, fn, args, **kw)
    return ProgramRecord(name, step, kind or classify(name), roles or {})


def test_host_sync_flags_compiled_callback():
    def cb_fn(x):
        y = jax.pure_callback(
            lambda a: a, jax.ShapeDtypeStruct((2,), jnp.float32), x)
        return y + 1.0

    rt = StaticRuntime(None)
    rec = _record(rt, "serve_cb_decode", cb_fn, (jnp.zeros((2,)),))
    rep = Report()
    host_sync.check_host_sync(SimpleNamespace(records=[rec]), rep)
    errs = [f for f in rep.errors if f.program == "serve_cb_decode"]
    assert any("pure_callback" in f.operand for f in errs),\
        rep.format(verbose=True)


def test_host_sync_flags_missing_donation(nomesh_cell):
    chunk = next(r for r in nomesh_cell.records if r.kind == "chunk")
    broken = dataclasses.replace(
        chunk, step=dataclasses.replace(chunk.step, donate_argnums=()))
    rep = Report()
    host_sync.check_host_sync(
        SimpleNamespace(records=[broken],
                        caches_aval=nomesh_cell.caches_aval), rep)
    errs = [f for f in rep.errors if f.program == chunk.name]
    assert errs and "does not donate" in errs[0].message,\
        rep.format(verbose=True)


def test_host_sync_flags_dead_donation_alias(nomesh_cell):
    """donate_argnums set but the output never reuses the cache: the alias
    map in the optimized HLO is empty and every leaf must be flagged."""
    caches = nomesh_cell.caches_aval

    def dead(caches, tok):              # consumes the cache, returns a token
        return tok + caches.length.astype(jnp.int32)

    rt = StaticRuntime(None)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # XLA: donated buffers unused
        rec = _record(rt, "serve_dead_decode", dead,
                      (caches, jnp.zeros((), jnp.int32)),
                      roles={"caches": 0}, donate_argnums=(0,))
    rep = Report()
    host_sync.check_host_sync(
        SimpleNamespace(records=[rec], caches_aval=caches), rep)
    errs = [f for f in rep.errors if "alias map" in f.message]
    assert errs, rep.format(verbose=True)
    assert all(f.operand.startswith("caches") for f in errs)


# ---------------------------------------------------------------------------
# pass 4 negatives: routing cross-check
# ---------------------------------------------------------------------------

def test_routing_flags_meter_drift(wa_cell):
    """An expected_routing that over-claims rows breaks the exact bytes
    identity — the meter can no longer drift silently from the program."""
    tampered = SimpleNamespace(
        spec=wa_cell.spec, cfg=wa_cell.cfg, mesh=wa_cell.mesh,
        records=wa_cell.records,
        backend=SimpleNamespace(
            _el=wa_cell.backend._el,
            overlap=wa_cell.backend.overlap,
            expected_routing=lambda name: (
                10 * wa_cell.backend.expected_routing(name)[0],
                wa_cell.backend.expected_routing(name)[1])))
    rep = Report()
    routing_check.check_routing(tampered, rep)
    errs = [f for f in rep.errors if f.operand == "hop bytes"]
    assert errs, rep.format(verbose=True)
    assert "drifted from the program" in errs[0].message


def test_routing_flags_dropped_hops(wa_cell):
    """A WA-named program with NO tagged hops = a layer bypassing the A
    domain; the count audit must fire."""
    rt = StaticRuntime(wa_cell.mesh)
    rec = _record(rt, "serve_wa_decode", lambda t: t + 1,
                  (jnp.zeros((2,), jnp.int32),))
    fake = SimpleNamespace(spec=wa_cell.spec, cfg=wa_cell.cfg,
                           mesh=wa_cell.mesh, backend=wa_cell.backend,
                           records=[rec])
    rep = Report()
    routing_check.check_routing(fake, rep)
    errs = [f for f in rep.errors if f.program == "serve_wa_decode"]
    assert errs and "dropped or duplicated" in errs[0].message,\
        rep.format(verbose=True)


# ---------------------------------------------------------------------------
# pass 5 negatives: kernel bounds
# ---------------------------------------------------------------------------

def test_kernel_bounds_flags_undercovering_grid():
    def kern(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    def f(x):                            # grid (1,) × block 4 over extent 8
        return pl.pallas_call(
            kern, grid=(1,),
            in_specs=[pl.BlockSpec((4,), lambda i: (i,))],
            out_specs=pl.BlockSpec((4,), lambda i: (i,)),
            out_shape=jax.ShapeDtypeStruct((8,), x.dtype))(x)

    jaxpr = jax.make_jaxpr(f)(jnp.zeros((8,)))
    rep = Report()
    n = kernel_bounds.check_pallas_sites(jaxpr, "bad_kernel", rep)
    assert n == 1
    errs = [f for f in rep.errors if f.program == "bad_kernel"]
    assert errs, rep.format(verbose=True)
    assert "cover only 4/8" in errs[0].message


def test_kernel_bounds_flags_dead_kv_limit():
    def kern(x_ref, lim_ref, o_ref):     # lim_ref never read
        o_ref[...] = x_ref[...]

    def f(x, lim):
        return pl.pallas_call(
            kern, grid=(2,),
            in_specs=[pl.BlockSpec((4,), lambda i: (i,)),
                      pl.BlockSpec((1, 1), lambda i: (0, 0))],
            out_specs=pl.BlockSpec((4,), lambda i: (i,)),
            out_shape=jax.ShapeDtypeStruct((8,), x.dtype))(x, lim)

    jaxpr = jax.make_jaxpr(f)(jnp.zeros((8,)),
                              jnp.zeros((1, 1), jnp.int32))
    rep = Report()
    kernel_bounds.check_pallas_sites(jaxpr, "dead_lim", rep,
                                     expect_limit=True)
    errs = [f for f in rep.errors if "kv_limit" in f.operand]
    assert errs and "never read" in errs[0].message, rep.format(verbose=True)


@pytest.mark.parametrize("S,bs,windows", [(4096, 512, ()),
                                           (4096, 256, (1024, 0)),
                                           (256, 128, (24, 0))])
def test_live_tile_map_stays_in_each_rows_range(S, bs, windows):
    rep = Report()
    n = kernel_bounds.check_live_tile_map("live", S, bs, windows, rep)
    assert n > 10 and rep.ok, rep.format(verbose=True)


@pytest.mark.parametrize("broken,message", [
    # a list one tile short: a range's last partial tile is never fetched
    (lambda lo, hi, bs: (lo // bs, (hi - lo) // bs), "never fetched"),
    # a list one tile long: past the range, and past the extent at its end
    (lambda lo, hi, bs: (lo // bs, (hi - 1) // bs - lo // bs + 2),
     "outside"),
], ids=["short", "long"])
def test_live_tile_map_flags_a_broken_map(broken, message):
    rep = Report()
    kernel_bounds.check_live_tile_map("broken", 1024, 128, (), rep,
                                      tiles=broken)
    errs = [f for f in rep.errors if f.program == "broken"]
    assert errs and message in errs[0].message, rep.format(verbose=True)


def test_kernel_bounds_flags_multi_slot_chunk_write(nomesh_cell):
    """A chunk program whose DUS spans 2 slots at a traced offset can alias
    a neighbour's live KV — must be an ERROR naming the write."""
    caches = nomesh_cell.caches_aval
    k0 = caches.k                        # (L, B, n_kv, S, hd)
    upd = jax.ShapeDtypeStruct((2,) + tuple(k0.shape[2:]), k0.dtype)

    def bad_chunk(caches, upd, slot):
        layer0 = caches.k[0]
        out = jax.lax.dynamic_update_slice(layer0, upd, (slot, 0, 0, 0))
        return out.sum()

    rt = StaticRuntime(None)
    rec = _record(rt, "serve_prefill_chunk", bad_chunk,
                  (caches, upd, jnp.zeros((), jnp.int32)),
                  roles={"caches": 0})
    rep = Report()
    kernel_bounds.check_chunk_writes(
        SimpleNamespace(caches_aval=caches, spec=nomesh_cell.spec),
        rec, rep)
    errs = [f for f in rep.errors if "dynamic_update_slice" in f.operand]
    assert errs, rep.format(verbose=True)
    assert "updates 2 slots" in errs[0].message
    assert "TRACED offset" in errs[0].message


# ---------------------------------------------------------------------------
# jaxpr-walk plumbing the passes stand on
# ---------------------------------------------------------------------------

def test_iter_eqns_multiplies_scan_trips():
    def f(x):
        def body(c, _):
            return c * 2.0, c
        return jax.lax.scan(body, x, None, length=5)

    jaxpr = jax.make_jaxpr(f)(jnp.zeros(()))
    muls = [s for s in iter_eqns(jaxpr) if s.eqn.primitive.name == "mul"]
    assert muls and muls[0].trips == 5


def test_iter_eqns_marks_while_unbounded():
    def f(x):
        return jax.lax.while_loop(lambda c: c < 10.0, lambda c: c + 1.0, x)

    jaxpr = jax.make_jaxpr(f)(jnp.zeros(()))
    adds = [s for s in iter_eqns(jaxpr) if s.eqn.primitive.name == "add"]
    assert adds and all(s.unbounded for s in adds)


def test_literal_value():
    def f(x):
        return jax.lax.dynamic_update_slice(x, jnp.ones((1,)), (3,))

    jaxpr = jax.make_jaxpr(f)(jnp.zeros((8,)))
    dus = [s.eqn for s in iter_eqns(jaxpr)
           if s.eqn.primitive.name == "dynamic_update_slice"]
    assert dus
    assert literal_value(dus[0].invars[2]) == 3


# ---------------------------------------------------------------------------
# pass 1: residency (multi-device → subprocess)
# ---------------------------------------------------------------------------

def test_residency_clean_and_catches_dropped_pins():
    """On a (2,4) mesh the full residency pass is clean for a WA cell, and
    removing the cache-entry pins reintroduces the PR-5 bug class — the
    pass must fail with diagnostics naming program and cache leaf."""
    out = run_py("""
    from repro.analysis.programs import CellSpec, build_cell, make_mesh
    from repro.analysis.findings import Report
    from repro.analysis import residency

    mesh = make_mesh(2, 4)
    cell = build_cell(CellSpec(label="wa", backend="wa", a_shards=4), mesh)
    rep = Report()
    residency.check_residency(cell, rep)
    assert not rep.errors, rep.format(verbose=True)
    print("CLEAN")

    # drop the cache-entry pins: write-slot admission compiles with no
    # sharding anchor at all and the cross-program coherence check fires
    import repro.runtime.serving as serving
    serving._pin_cache_tree = lambda caches, ctx: caches
    cell2 = build_cell(CellSpec(label="mono", backend="colocated",
                                prefill_chunk=0), mesh)
    rep2 = Report()
    residency.check_residency(cell2, rep2)
    errs = rep2.errors
    assert errs, "expected residency errors with the pins removed"
    assert any("caches.k" in f.operand for f in errs), \\
        rep2.format(verbose=True)
    assert any(f.program.startswith("serve_") for f in errs)
    print("CAUGHT", len(errs))
    """)
    assert "CLEAN" in out and "CAUGHT" in out
