"""Async oracle pipelining (``mpbcfw-async`` / ``mpbcfw-shard-async``).

Covers: dual monotonicity of the pipelined trace (every fold-in is an
exact line search at the current phi, so stale oracle results cannot
decrease the dual); the <= 2 dispatches + 1 host sync contract and the
``oracle_overlap`` ledger accounting; bit-for-bit checkpoint/resume;
straggler-aware deadline fallbacks (``repro.ft`` outcome masks drive
the engine's ``done`` fold gating); CollectiveTrace byte accounting
across the two-program split; the chunked fold-scatter equivalence;
rule J009 (positive on both async engines, negative on a fused engine
masquerading as async); and the 8-device subprocess run.
"""
import dataclasses
import math
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import RunConfig, Solver, capabilities_of
from repro.checkpoint.manager import CheckpointManager
from repro.core import distributed, mpbcfw
from repro.core.selection import CostModel
from repro.core.ssvm import dual_value, weights_of
from repro.ft import StragglerPolicy, simulate_oracle_outcomes

ROOT = Path(__file__).resolve().parents[1]


def _cfg(prob, *, algo="mpbcfw-async", max_iters=6, seed=0, **kw):
    kw.setdefault("cost_model", CostModel(oracle_cost=0.5,
                                          plane_cost=0.01))
    return RunConfig(lam=1.0 / prob.n, algo=algo, cap=8, ttl=10,
                     seed=seed, max_iters=max_iters, approx_batch=16,
                     max_approx_passes=16, **kw)


def _rows_equal(ra, rb):
    da, db = dataclasses.asdict(ra), dataclasses.asdict(rb)
    assert da.keys() == db.keys()
    for k in da:
        va, vb = da[k], db[k]
        if isinstance(va, float) and math.isnan(va):
            assert isinstance(vb, float) and math.isnan(vb), k
        else:
            assert va == vb, (k, va, vb)


# ---------------------------------------------------------------------------
# The pipelined trace: monotone dual, dispatch/sync contract, overlap


def test_async_dual_monotone_and_contract(multiclass_problem):
    prob = multiclass_problem
    solver = Solver(prob, _cfg(prob))
    res = solver.run()
    duals = [r.dual for r in res.trace]
    assert all(b >= a - 1e-7 for a, b in zip(duals, duals[1:])), duals
    assert res.trace[-1].gap < res.trace[0].gap
    for row in res.trace:
        assert row.dispatches <= 2, row
        assert row.host_syncs == 1, row
        assert 0.0 <= row.oracle_overlap <= 1.0, row
    # the pipeline actually hides oracle time once the cache warms up
    assert any(r.oracle_overlap > 0.0 for r in res.trace)
    # ledger totals mirror the per-row column
    led = solver.engine.ledger
    assert led.oracle_time_hidden <= led.oracle_time_total
    assert led.oracle_time_total > 0.0


def test_async_capabilities_declared():
    caps = capabilities_of("mpbcfw-async")
    assert caps.async_oracle and caps.multipass
    caps_sh = capabilities_of("mpbcfw-shard-async")
    assert caps_sh.async_oracle and caps_sh.supports_mesh


def test_async_overlap_credits_costmodel_time(multiclass_problem):
    """Pipelined modeled time = serial charges minus the hidden oracle
    span: the CostModel clock must run strictly behind a zero-overlap
    replay of the same trace."""
    prob = multiclass_problem
    solver = Solver(prob, _cfg(prob))
    res = solver.run()
    led = solver.engine.ledger
    serial_floor = res.trace[-1].time + led.oracle_time_hidden
    assert led.oracle_time_hidden > 0.0
    # re-run with the same config through the serial fused engine: its
    # modeled clock pays the oracle in full every iteration
    res_f = Solver(prob, _cfg(prob, algo="mpbcfw")).run()
    assert res.trace[-1].time < serial_floor
    assert res_f.trace[-1].time > res.trace[-1].time


# ---------------------------------------------------------------------------
# Checkpoint/resume: bit-for-bit


def test_async_checkpoint_resume_trace_bitwise(tmp_path,
                                               multiclass_problem):
    prob = multiclass_problem

    full = Solver(prob, _cfg(prob)).run()

    mgr = CheckpointManager(str(tmp_path / "async-ckpt"))
    s1 = Solver(prob, _cfg(prob))
    it = s1.iterate()
    rows_head = [next(it) for _ in range(3)]
    assert s1.save(mgr) == 3

    s2 = Solver.restore(prob, _cfg(prob), mgr)
    rows_tail = list(s2.iterate())
    assert [r.iteration for r in rows_tail] == [3, 4, 5]
    for ra, rb in zip(rows_head + rows_tail, full.trace):
        _rows_equal(ra, rb)
    np.testing.assert_array_equal(s2.result().w, full.w)


# ---------------------------------------------------------------------------
# Straggler-aware deadlines: ft outcome masks drive the fold gating


@pytest.mark.parametrize("straggler_prob,seed", [(0.3, 0), (0.6, 1),
                                                 (0.95, 2)])
def test_async_straggler_fallback_dual_monotone(multiclass_problem,
                                                straggler_prob, seed):
    """Missed-deadline oracle results fall back to the block's cached
    plane (``fallback_planes``); the dual stays monotone at any
    straggler rate because both branches fold with exact line search at
    the current phi."""
    prob = multiclass_problem
    policy = StragglerPolicy(straggler_prob=straggler_prob,
                             deadline_factor=1.5)
    rng = np.random.RandomState(seed)

    solver = Solver(prob, _cfg(prob))
    masks = []

    def outcomes(it, k):
        done, _ = simulate_oracle_outcomes(k, policy, rng)
        masks.append(done)
        return jnp.asarray(done)

    solver.engine.outcome_fn = outcomes
    res = solver.run()
    duals = [r.dual for r in res.trace]
    assert all(b >= a - 1e-7 for a, b in zip(duals, duals[1:])), duals
    assert res.trace[-1].dual > 0.0
    # the policy actually dropped oracles (the fallback path ran)
    assert any(not m.all() for m in masks)


def test_async_straggler_trace_differs_from_clean_run(multiclass_problem):
    """Dropping oracle results must change the trajectory (the mask is
    load-bearing, not decorative) while staying monotone."""
    prob = multiclass_problem
    clean = Solver(prob, _cfg(prob)).run()

    solver = Solver(prob, _cfg(prob))
    solver.engine.outcome_fn = \
        lambda it, k: jnp.asarray(np.arange(k) % 2 == 0)
    res = solver.run()
    assert not np.array_equal(np.asarray(res.w), np.asarray(clean.w))
    # and the all-arrived mask reproduces the clean run bit for bit
    solver2 = Solver(prob, _cfg(prob))
    solver2.engine.outcome_fn = lambda it, k: jnp.ones((k,), bool)
    res2 = solver2.run()
    for ra, rb in zip(res2.trace, clean.trace):
        _rows_equal(ra, rb)


# ---------------------------------------------------------------------------
# CollectiveTrace byte accounting across the two-program split


def test_shard_async_collective_bytes_survive_split(multiclass_problem,
                                                    data_mesh):
    """The oracle program must contribute zero collective sites; every
    psum (and its payload bytes) lives in the cache program, and the
    ledger's runtime totals still reconcile as setup + passes * per_pass
    per iteration."""
    prob = multiclass_problem
    solver = Solver(prob, _cfg(prob, algo="mpbcfw-shard-async",
                               mesh=data_mesh, max_iters=4))
    res = solver.run()
    eng = solver.engine.eng
    # only the cache program traced collective sites
    assert set(eng.collectives.sites) == {"multi_approx"}
    per_pass = eng.collectives.count("multi_approx", "pass")
    setup = eng.collectives.count("multi_approx", "setup")
    assert per_pass == 1 and setup == 1
    b_pass = eng.collectives.bytes_of("multi_approx", "pass")
    b_setup = eng.collectives.bytes_of("multi_approx", "setup")
    assert b_pass > 0 and b_setup > 0
    iters = len(res.trace)
    passes = sum(r.approx_passes for r in res.trace)
    led = solver.engine.ledger
    assert led.collectives == iters * setup + passes * per_pass
    assert led.collective_bytes == iters * b_setup + passes * b_pass


def test_shard_async_trace_monotone_one_sync(multiclass_problem,
                                             data_mesh):
    prob = multiclass_problem
    res = Solver(prob, _cfg(prob, algo="mpbcfw-shard-async",
                            mesh=data_mesh)).run()
    duals = [r.dual for r in res.trace]
    assert all(b >= a - 1e-7 for a, b in zip(duals, duals[1:])), duals
    for row in res.trace:
        assert row.dispatches <= 2 and row.host_syncs == 1, row
    assert any(r.oracle_overlap > 0.0 for r in res.trace)


# ---------------------------------------------------------------------------
# Fold-in scatter strategies (CacheLayout.fold_scatter)


def _warm_mp(prob, lam, cap=8):
    rng = np.random.RandomState(0)
    mp = mpbcfw.init_mp_state(prob, cap)
    mp = mpbcfw.jit_exact_pass(prob, mp,
                               jnp.asarray(rng.permutation(prob.n)),
                               lam=lam)
    return mp, rng


def test_fold_scatter_chunked_bitwise_matches_per_elem(multiclass_problem):
    prob = multiclass_problem
    lam = 1.0 / prob.n
    mp, rng = _warm_mp(prob, lam)
    ids = jnp.asarray(rng.permutation(prob.n)[:12])
    w = weights_of(mp.inner.phi, lam)
    planes = distributed.parallel_oracles(prob, w, ids)
    fbp, fbs, _ = distributed.fallback_planes(mp.cache, ids, w)
    done = jnp.asarray(rng.rand(12) > 0.3)  # mix folds and fallbacks
    out_p = distributed.jit_fold_planes(mp, ids, planes, fbp, fbs, done,
                                        lam=lam, scatter="per-elem")
    out_c = distributed.jit_fold_planes(mp, ids, planes, fbp, fbs, done,
                                        lam=lam, scatter="chunked")
    for leaf_p, leaf_c in zip(jax.tree_util.tree_leaves(out_p),
                              jax.tree_util.tree_leaves(out_c)):
        np.testing.assert_array_equal(np.asarray(leaf_p),
                                      np.asarray(leaf_c))
    assert float(dual_value(out_c.inner.phi, lam)) >= \
        float(dual_value(mp.inner.phi, lam)) - 1e-7


def test_fold_scatter_unknown_strategy_rejected(multiclass_problem):
    prob = multiclass_problem
    lam = 1.0 / prob.n
    mp, rng = _warm_mp(prob, lam)
    ids = jnp.asarray(rng.permutation(prob.n)[:4])
    w = weights_of(mp.inner.phi, lam)
    planes = distributed.parallel_oracles(prob, w, ids)
    fbp, fbs, _ = distributed.fallback_planes(mp.cache, ids, w)
    with pytest.raises(ValueError, match="unknown scatter strategy"):
        distributed.fold_planes(mp, ids, planes, fbp, fbs,
                                jnp.ones((4,), bool), lam,
                                scatter="banana")


def test_async_engine_runs_chunked_fold(multiclass_problem):
    """The chunked scatter path drives the full pipelined engine to the
    same trace as the per-element default (distinct permutation ids =>
    the strategies are bit-identical)."""
    from repro.api.engine import engine_entry

    prob = multiclass_problem
    entry = engine_entry("mpbcfw-async")
    res_p = Solver(prob, _cfg(prob)).run()

    cfg = _cfg(prob)
    solver_c = Solver(prob, cfg)
    solver_c.engine = entry.factory(prob, cfg)
    solver_c.engine.fold_scatter = "chunked"
    res_c = solver_c.run()
    for ra, rb in zip(res_c.trace, res_p.trace):
        _rows_equal(ra, rb)


# ---------------------------------------------------------------------------
# Rule J009


def test_j009_async_engines_clean():
    from repro.analysis.contracts import check_trace, trace_engine

    for name in ("mpbcfw-async", "mpbcfw-shard-async"):
        et = trace_engine(name)
        findings, _ = check_trace(et)
        assert [f for f in findings if f.rule == "J009"] == [], \
            [str(f) for f in findings]
        outer = next(p for p in et.programs if p.name == "outer")
        names = [str(e.params.get("name", ""))
                 for e in outer.jaxpr.jaxpr.eqns if e.primitive.name ==
                 "jit"]
        assert any("async_oracle" in s for s in names)
        assert any("async_cache" in s for s in names)


def test_j009_flags_fused_engine_masquerading_as_async():
    """A one-program engine that *declares* async_oracle has no
    async_oracle/async_cache jit pair — J009 must fire."""
    from repro.analysis.contracts import (EngineTrace, check_trace,
                                          trace_engine)

    et = trace_engine("mpbcfw")
    fake_caps = dataclasses.replace(et.caps, async_oracle=True)
    fake = EngineTrace(engine="fake-async", label="fake-async",
                       caps=fake_caps, on_mesh=False,
                       programs=et.programs)
    findings, _ = check_trace(fake)
    assert any(f.rule == "J009" for f in findings), \
        [str(f) for f in findings]


# ---------------------------------------------------------------------------
# Multi-device (8 forced host devices, fresh subprocess)

_MULTIDEV_ASYNC_SCRIPT = textwrap.dedent("""
    from repro.launch.mesh import force_host_platform_device_count, \\
        make_data_mesh
    assert force_host_platform_device_count(8)
    import jax
    import jax.numpy as jnp
    from repro.api import RunConfig, Solver
    from repro.core.selection import CostModel
    from repro.data import synthetic
    from repro.core.oracles import multiclass

    assert jax.local_device_count() == 8
    x, y = synthetic.usps_like(n=48, f=12, num_classes=5, seed=0)
    prob = multiclass.make_problem(jnp.asarray(x), jnp.asarray(y), 5)
    lam = 1.0 / prob.n
    res = Solver(prob, RunConfig(
        lam=lam, algo="mpbcfw-shard-async", mesh=make_data_mesh(8),
        max_iters=4, cap=8, max_approx_passes=16, approx_batch=16,
        cost_model=CostModel(oracle_cost=0.5, plane_cost=0.01))).run()
    for row in res.trace:
        assert row.host_syncs == 1, row
        assert row.dispatches <= 2, row
    duals = [t.dual for t in res.trace]
    assert all(b >= a - 1e-7 for a, b in zip(duals, duals[1:])), duals
    assert res.trace[-1].gap < res.trace[0].gap
    assert any(t.oracle_overlap > 0.0 for t in res.trace)
    print("MULTIDEV_ASYNC_OK", duals[-1])
""")


@pytest.mark.mesh
def test_shard_async_on_eight_forced_devices():
    """`mpbcfw-shard-async` end-to-end on a real 8-shard mesh: monotone
    duals, <= 2 dispatches + 1 host sync per outer iteration, positive
    oracle overlap.  Fresh subprocess (device count forced before jax
    initializes)."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["PYTHONPATH"] = str(ROOT / "src")
    out = subprocess.run([sys.executable, "-c", _MULTIDEV_ASYNC_SCRIPT],
                         capture_output=True, text=True, timeout=600,
                         env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "MULTIDEV_ASYNC_OK" in out.stdout
