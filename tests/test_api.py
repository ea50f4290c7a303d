"""The public Solver/Engine/Oracle protocol layer (repro.api).

Covers: `Solver.run()` is deterministic for every registered algorithm
under CostModel (and the removed `driver.run` shim stays removed);
third-party engines and oracles registered from test code (no edits to
repro.core) run end-to-end through `Solver.iterate()`; invalid configs
raise the typed `UnsupportedConfigError`; gap-tolerance stopping;
checkpoint/resume determinism; and the on-device slope rule vs the host
IterationTracker.
"""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (EngineCapabilities, MaxIters, RunConfig, Solver,
                       StopContext, StopOnGap, OracleSpec,
                       UnsupportedConfigError, WallTimeBudget, algorithms,
                       build_problem, capabilities_of, register_engine,
                       unregister_engine)
from repro.checkpoint.manager import CheckpointManager
from repro.core import bcfw, driver, mpbcfw
from repro.core.averaging import init_averaging
from repro.core.selection import (CostModel, IterationTracker, SyncLedger)
from repro.core.ssvm import dual_value, init_state, weights_of

def _cm():
    return CostModel(oracle_cost=0.02, plane_cost=1e-4)


def _solver_run(problem, cfg):
    """The one-call convenience the removed driver.run shim provided."""
    return Solver(problem, cfg).run()


def _rows_equal(ra, rb):
    """TraceRow equality with NaN == NaN (ssg's dual/gap)."""
    da, db = dataclasses.asdict(ra), dataclasses.asdict(rb)
    assert da.keys() == db.keys()
    for k in da:
        va, vb = da[k], db[k]
        if isinstance(va, float) and math.isnan(va):
            assert isinstance(vb, float) and math.isnan(vb), k
        else:
            assert va == vb, (k, va, vb)


# ---------------------------------------------------------------------------
# Solver.run is deterministic for every registered algorithm; the
# one-release driver.run shim is gone (R002 polices any respelling)


@pytest.mark.parametrize("algo", algorithms())
def test_solver_run_deterministic_per_algorithm(multiclass_problem,
                                                data_mesh, algo):
    prob = multiclass_problem
    lam = 1.0 / prob.n

    def cfg():
        kw = dict(lam=lam, algo=algo, max_iters=3, cap=8, seed=7,
                  cost_model=_cm())
        if capabilities_of(algo).supports_mesh:
            kw["mesh"] = data_mesh
        if capabilities_of(algo).requires_tau:
            kw["tau"] = 8
        return RunConfig(**kw)

    res_a = _solver_run(prob, cfg())
    res_api = Solver(prob, cfg()).run()
    assert len(res_a.trace) == len(res_api.trace) == 3
    for ra, rb in zip(res_a.trace, res_api.trace):
        _rows_equal(ra, rb)
    np.testing.assert_array_equal(res_a.w, res_api.w)
    if res_a.w_avg is None:
        assert res_api.w_avg is None
    else:
        np.testing.assert_array_equal(res_a.w_avg, res_api.w_avg)


def test_driver_run_shim_is_gone():
    """The deprecation window closed: repro.core.driver no longer has a
    ``run`` attribute (and the analysis lint flags any new spelling)."""
    with pytest.raises(AttributeError):
        driver.run  # noqa: B018  # repro: allow[R002] asserting removal


def test_solver_iterate_streams_rows_and_callbacks(multiclass_problem):
    prob = multiclass_problem
    lam = 1.0 / prob.n
    seen = []
    solver = Solver(prob, RunConfig(lam=lam, algo="mpbcfw", max_iters=4,
                                    cap=8, cost_model=_cm()),
                    callbacks=[lambda s, row: seen.append(row.iteration)])
    rows = []
    for row in solver.iterate():
        rows.append(row)
        assert row.iteration == len(rows) - 1
    assert seen == [0, 1, 2, 3]
    assert solver.result().trace == rows
    # iterating again is a no-op: MaxIters already fired
    assert list(solver.iterate()) == []


# ---------------------------------------------------------------------------
# Uniform typed config validation off EngineCapabilities


def test_unknown_algorithm_is_typed_error(multiclass_problem):
    with pytest.raises(UnsupportedConfigError, match="unknown algorithm"):
        Solver(multiclass_problem,
               RunConfig(lam=0.1, algo="does-not-exist"))


def test_gram_plus_mesh_now_resolves_to_sharded_engine(multiclass_problem,
                                                       data_mesh):
    """Regression for the capability routing: mpbcfw-gram + mesh used to
    raise the typed UnsupportedConfigError ("no sharded twin"); with the
    gram blocks living inside the sharded PlaneCache it now resolves to
    the sharded gram engine — while tau without a mesh keeps raising."""
    from repro.api.engines import ShardDriverEngine

    solver = Solver(multiclass_problem,
                    RunConfig(lam=0.1, algo="mpbcfw-gram", mesh=data_mesh,
                              cost_model=_cm()))
    assert isinstance(solver.engine, ShardDriverEngine)
    assert solver.engine.use_gram
    assert solver.state.cache.gram is not None
    # ... and without a mesh it stays the single-device fused engine
    solver1 = Solver(multiclass_problem,
                     RunConfig(lam=0.1, algo="mpbcfw-gram",
                               cost_model=_cm()))
    assert not isinstance(solver1.engine, ShardDriverEngine)
    # ... with the mesh, tau flows through to the sharded gram engine
    solver_tau = Solver(multiclass_problem,
                        RunConfig(lam=0.1, algo="mpbcfw-gram",
                                  mesh=data_mesh, tau=4, cost_model=_cm()))
    assert solver_tau.engine.tau == 4
    # tau still needs the mesh: the typed error is not gone
    with pytest.raises(UnsupportedConfigError, match="tau"):
        Solver(multiclass_problem,
               RunConfig(lam=0.1, algo="mpbcfw-gram", tau=4,
                         cost_model=_cm()))


def test_tau_without_mesh_rejected_by_capabilities(multiclass_problem):
    """Regression: tau used to be silently ignored off the shard path."""
    with pytest.raises(UnsupportedConfigError, match="tau"):
        Solver(multiclass_problem,
               RunConfig(lam=0.1, algo="mpbcfw", tau=4, cost_model=_cm()))
    with pytest.raises(UnsupportedConfigError, match="tau"):
        _solver_run(multiclass_problem,
                   RunConfig(lam=0.1, algo="bcfw", tau=4,
                             cost_model=_cm()))


def test_mesh_on_single_device_engine_rejected(multiclass_problem,
                                               data_mesh):
    with pytest.raises(UnsupportedConfigError, match="only consumed by"):
        Solver(multiclass_problem,
               RunConfig(lam=0.1, algo="bcfw", mesh=data_mesh,
                         cost_model=_cm()))


def test_capabilities_descriptors():
    caps = capabilities_of("mpbcfw-shard")
    assert caps.supports_mesh and caps.multipass and caps.uses_tau
    assert capabilities_of("mpbcfw-gram").supports_mesh  # routes to shard
    assert capabilities_of("mpbcfw-gram").supports_gram
    shard_gram = capabilities_of("mpbcfw-shard-gram")
    assert shard_gram.supports_mesh and shard_gram.supports_gram
    assert shard_gram.uses_tau and shard_gram.multipass
    assert not capabilities_of("fw").needs_perm
    assert capabilities_of("bcfw-avg").supports_averaging


# ---------------------------------------------------------------------------
# Gap-tolerance early stopping (Osokin et al.-style)


def test_gap_tol_stops_early_on_multiclass(multiclass_problem):
    prob = multiclass_problem
    lam = 1.0 / prob.n
    tol = 1e-3
    res = Solver(prob, RunConfig(lam=lam, algo="mpbcfw", max_iters=40,
                                 cap=16, gap_tol=tol,
                                 cost_model=_cm())).run()
    assert len(res.trace) < 40              # converged well before budget
    assert res.trace[-1].gap <= tol         # ... to the requested gap
    assert all(r.gap > tol for r in res.trace[:-1])  # stopped ASAP
    # the shim takes the same early exit
    res2 = _solver_run(prob, RunConfig(lam=lam, algo="mpbcfw", max_iters=40,
                                      cap=16, gap_tol=tol,
                                      cost_model=_cm()))
    assert len(res2.trace) == len(res.trace)


def test_stop_criteria_units():
    row = driver.TraceRow(0, 1, 0, 2.0, 1.0, 0.9, 0.1, 1.0, 0.0, 0)
    assert StopOnGap(0.2).should_stop(StopContext(1, row, 2.0))
    assert not StopOnGap(0.05).should_stop(StopContext(1, row, 2.0))
    nan_row = dataclasses.replace(row, gap=float("nan"))
    assert not StopOnGap(0.2).should_stop(StopContext(1, nan_row, 2.0))
    assert MaxIters(1).should_stop(StopContext(1, row, 2.0))
    assert not MaxIters(2).should_stop(StopContext(1, row, 2.0))
    assert WallTimeBudget(1.5).should_stop(StopContext(1, row, 2.0))
    assert not WallTimeBudget(3.0).should_stop(StopContext(1, row, 2.0))


def test_time_budget_stops_on_virtual_clock(multiclass_problem):
    prob = multiclass_problem
    lam = 1.0 / prob.n
    cm = CostModel(oracle_cost=1.0, plane_cost=1e-4)  # ~n sec per iter
    res = Solver(prob, RunConfig(lam=lam, algo="mpbcfw", max_iters=50,
                                 cap=8, time_budget=2.5 * prob.n,
                                 cost_model=cm)).run()
    assert 1 <= len(res.trace) < 50
    assert res.trace[-1].time >= 2.5 * prob.n - prob.n  # stopped near budget


def test_wall_clock_anchors_at_first_iteration(multiclass_problem):
    """Regression: setup time between constructing a Solver and running
    it must not be charged to trace rows (the wall clock anchors at the
    first iterate() call, not at __init__)."""
    import time as _time

    prob = multiclass_problem
    lam = 1.0 / prob.n
    solver = Solver(prob, RunConfig(lam=lam, algo="mpbcfw", max_iters=1,
                                    cap=8, max_approx_passes=2,
                                    cost_model=None))   # wall clock
    _time.sleep(0.3)
    t0 = _time.perf_counter()
    res_rows = list(solver.iterate())
    run_wall = _time.perf_counter() - t0
    # the iteration may legitimately be slow (XLA compile), but the
    # pre-run sleep must not appear in the trace: the reported time
    # cannot exceed the wall time of the run itself
    assert res_rows[0].time <= run_wall + 0.05


# ---------------------------------------------------------------------------
# Checkpoint / resume determinism


@pytest.mark.parametrize("algo", ["mpbcfw", "mpbcfw-gram"])
def test_checkpoint_resume_trace_bitwise(tmp_path, multiclass_problem,
                                         algo):
    """Solver run k iterations, checkpointed, resumed == uninterrupted,
    bit for bit under CostModel (state, RNG stream, virtual clock).
    The gram engine covers the cache-resident Gram blocks riding in the
    checkpointed PlaneCache (no side-channel engine state)."""
    prob = multiclass_problem
    lam = 1.0 / prob.n

    def cfg():
        return RunConfig(lam=lam, algo=algo, max_iters=6, cap=8,
                         seed=3, cost_model=CostModel(plane_cost=1e-3))

    full = Solver(prob, cfg()).run()

    mgr = CheckpointManager(str(tmp_path / "ckpt"))
    s1 = Solver(prob, cfg())
    it = s1.iterate()
    rows_head = [next(it) for _ in range(3)]
    step = s1.save(mgr)
    assert step == 3

    s2 = Solver.restore(prob, cfg(), mgr)
    assert s2.iteration == 3
    rows_tail = list(s2.iterate())
    assert [r.iteration for r in rows_tail] == [3, 4, 5]
    for ra, rb in zip(rows_head + rows_tail, full.trace):
        _rows_equal(ra, rb)
    res2 = s2.result()
    np.testing.assert_array_equal(res2.w, full.w)
    np.testing.assert_array_equal(res2.w_avg, full.w_avg)


def test_checkpoint_every_autosaves(tmp_path, multiclass_problem):
    prob = multiclass_problem
    lam = 1.0 / prob.n
    mgr = CheckpointManager(str(tmp_path / "auto"), keep=10)
    Solver(prob, RunConfig(lam=lam, algo="mpbcfw", max_iters=5, cap=8,
                           cost_model=_cm()),
           checkpoint=mgr, checkpoint_every=2).run()
    assert mgr.all_steps() == [2, 4]


def test_resume_honors_gap_tol_from_saved_row(tmp_path,
                                              multiclass_problem):
    """Regression: a checkpoint taken after the gap already met gap_tol
    must not run one extra iteration on resume (StopOnGap consults the
    restored last row before the first resumed iteration)."""
    prob = multiclass_problem
    lam = 1.0 / prob.n

    def cfg():
        # gap_tol large enough that iteration 0 satisfies it
        return RunConfig(lam=lam, algo="mpbcfw", max_iters=10, cap=16,
                         gap_tol=1.0, cost_model=_cm())

    full = Solver(prob, cfg()).run()
    assert len(full.trace) == 1

    mgr = CheckpointManager(str(tmp_path / "gap"))
    s1 = Solver(prob, cfg())
    next(s1.iterate())
    s1.save(mgr)
    s2 = Solver.restore(prob, cfg(), mgr)
    assert list(s2.iterate()) == []   # uninterrupted run stopped here too


def test_checkpoint_resume_rejects_algo_mismatch(tmp_path,
                                                 multiclass_problem):
    prob = multiclass_problem
    lam = 1.0 / prob.n
    mgr = CheckpointManager(str(tmp_path / "mismatch"))
    s = Solver(prob, RunConfig(lam=lam, algo="bcfw", max_iters=2,
                               cost_model=_cm()))
    next(s.iterate())
    s.save(mgr)
    with pytest.raises(ValueError, match="cannot resume"):
        Solver.restore(prob, RunConfig(lam=lam, algo="mpbcfw",
                                       cost_model=_cm()), mgr)


# ---------------------------------------------------------------------------
# On-device slope rule vs the host IterationTracker rule (ROADMAP item)


def test_device_slope_rule_matches_host_tracker(multiclass_problem):
    """Replay the fused program's per-pass telemetry through the host
    IterationTracker under the same CostModel constants: every
    continue/stop decision must agree (paper's USPS-like cheap-oracle
    regime, where the rule actually bites)."""
    prob = multiclass_problem
    lam = 1.0 / prob.n
    n = prob.n
    cm = _cm()   # USPS-like: 20ms oracle, 0.1ms per plane-step
    rng = np.random.RandomState(0)
    mp = mpbcfw.init_mp_state(prob, cap=16)
    B = 32
    decisions_checked = 0
    for _ in range(4):
        f0 = float(dual_value(mp.inner.phi, lam))   # pre-iteration dual
        perm = jnp.asarray(rng.permutation(n))
        perms = jnp.asarray(np.stack([rng.permutation(n)
                                      for _ in range(B)]))
        clock = mpbcfw.make_slope_clock(0.0, 0.0, cm.oracle_cost * n,
                                        cm.plane_cost)
        mp, clock, st = mpbcfw.jit_outer_iteration(
            prob, mp, perm, perms, clock, lam=lam, ttl=10)
        st = jax.device_get(st)
        k = int(st.passes_run)
        assert k >= 1
        # Host rule on the same telemetry and the same cost constants.
        tracker = IterationTracker()
        tracker.start(0.0, f0)
        t_exact = cm.oracle_cost * n
        tracker.record(t_exact, float(st.f_entry))
        cost = cm.plane_cost * max(int(st.ws_total), 1)
        t = t_exact
        for j in range(k):
            t += cost
            tracker.record(t, float(st.duals[j]))
            host_continue = tracker.continue_approx()
            if j < k - 1:
                assert host_continue, f"host rule stopped early at pass {j}"
            else:
                # device: more=True iff the rule still wanted another pass
                # when the batch cap was hit
                assert host_continue == bool(st.more)
            decisions_checked += 1
    assert decisions_checked >= 8   # the regime actually exercised the rule


def test_fused_engine_donates_state(multiclass_problem):
    """FusedEngine hands the state to its fused programs (the paper-size
    OCR cache fits one chip only so); a direct jit_outer_iteration call
    keeps the caller's state, and both compute the same iteration."""
    from repro.api.engines import FusedEngine

    prob = multiclass_problem
    lam = 1.0 / prob.n
    rng = np.random.RandomState(0)
    perm = jnp.asarray(rng.permutation(prob.n))
    perms = jnp.asarray(np.stack([rng.permutation(prob.n)
                                  for _ in range(2)]))
    clock = mpbcfw.make_slope_clock(0.0, 0.0, 1.0, 1e-3)
    engine = FusedEngine(prob, lam)
    mp = engine.init_state(8)
    kept, _, _ = mpbcfw.jit_outer_iteration(prob, mp, perm, perms, clock,
                                            lam=lam, ttl=10)
    assert not any(a.is_deleted() for a in jax.tree_util.tree_leaves(mp))
    mp2, clock2, _ = engine.outer_iteration(mp, perm, perms, clock, ttl=10)
    assert all(a.is_deleted() for a in jax.tree_util.tree_leaves(mp))
    assert np.array_equal(np.asarray(kept.inner.phi),
                          np.asarray(mp2.inner.phi))
    engine.continue_passes(mp2, perms, clock2)
    assert mp2.cache.planes.is_deleted()


# ---------------------------------------------------------------------------
# Third-party extension points (no edits to repro.core)


class _CyclicBCFWEngine:
    """A from-scratch engine: BCFW with a fixed cyclic block schedule.

    Registered from test code through the public protocol — exercises the
    full Solver loop (ledger accounting, evaluation, extraction) without
    touching repro.core internals.
    """

    capabilities = EngineCapabilities(needs_perm=False,
                                      supports_averaging=True)

    def __init__(self, problem, cfg):
        self.problem, self.lam = problem, cfg.lam
        self.ledger = SyncLedger()

    def init_state(self, cap):
        del cap
        return (init_state(self.problem), init_averaging(self.problem.d))

    def outer_iteration(self, state, perm, perms, clock, *, ttl):
        del perm, perms, clock, ttl
        st, avg = state
        self.ledger.dispatched()
        st, avg = bcfw.jit_exact_pass(
            self.problem, st, avg, jnp.arange(self.problem.n), lam=self.lam)
        return (st, avg), None, st.n_exact

    def read_stats(self, stats):
        from repro.api.engines import IterStats
        return IterStats(n_exact=int(self.ledger.sync(stats)), n_approx=0)

    def evaluate(self, state):
        from repro.api import evaluate_objectives
        return evaluate_objectives(self.problem, state[0].phi, None,
                                   self.lam)

    def extract(self, state):
        return np.asarray(weights_of(state[0].phi, self.lam)), None


def test_third_party_engine_end_to_end(multiclass_problem):
    prob = multiclass_problem
    lam = 1.0 / prob.n
    register_engine("cyclic-bcfw", _CyclicBCFWEngine,
                    _CyclicBCFWEngine.capabilities)
    try:
        assert "cyclic-bcfw" in algorithms()
        solver = Solver(prob, RunConfig(lam=lam, algo="cyclic-bcfw",
                                        max_iters=4, cost_model=_cm()))
        rows = list(solver.iterate())
        assert len(rows) == 4
        duals = [r.dual for r in rows]
        assert all(b >= a - 1e-7 for a, b in zip(duals, duals[1:]))
        assert rows[-1].gap < rows[0].gap
        assert rows[-1].n_exact == 4 * prob.n
        for r in rows:
            assert r.host_syncs == 1 and r.dispatches == 1
        res = solver.result()
        assert res.w is not None and res.w_avg is None
        # the shim drives the registered engine too
        res2 = _solver_run(prob, RunConfig(lam=lam, algo="cyclic-bcfw",
                                          max_iters=4, cost_model=_cm()))
        for ra, rb in zip(rows, res2.trace):
            _rows_equal(ra, rb)
    finally:
        unregister_engine("cyclic-bcfw")
    with pytest.raises(UnsupportedConfigError):
        Solver(prob, RunConfig(lam=lam, algo="cyclic-bcfw"))


class _SignSpec(OracleSpec):
    """User-defined task: binary classification of sign(u @ x), written
    against the public OracleSpec only (decode/features/loss)."""

    def dim(self, data):
        return 2 * int(data["x"].shape[-1])

    def truth(self, ex):
        return ex["y"]

    def decode(self, w, ex):
        x, y = ex["x"], ex["y"]
        wc = w.reshape(2, x.shape[0])
        scores = wc @ x + (1.0 - jax.nn.one_hot(y, 2, dtype=x.dtype))
        return jnp.argmax(scores)

    def features(self, ex, y):
        x = ex["x"]
        return (jnp.zeros((2, x.shape[0]), x.dtype).at[y].add(x)).reshape(-1)

    def loss(self, ex, y):
        return (y != ex["y"]).astype(ex["x"].dtype)


def test_custom_oracle_spec_end_to_end():
    r = np.random.RandomState(0)
    n, f = 40, 6
    x = r.randn(n, f).astype(np.float32)
    u = r.randn(f)
    y = (x @ u > 0).astype(np.int32)
    prob = build_problem(_SignSpec(), {"x": jnp.asarray(x),
                                       "y": jnp.asarray(y)})
    assert prob.n == n and prob.d == 2 * f
    lam = 1.0 / n
    res = Solver(prob, RunConfig(lam=lam, algo="mpbcfw", max_iters=8,
                                 cap=8, cost_model=_cm())).run()
    duals = [r_.dual for r_ in res.trace]
    assert all(b >= a - 1e-7 for a, b in zip(duals, duals[1:]))
    assert res.trace[-1].gap < res.trace[0].gap
    w = res.w.reshape(2, f)
    pred = np.argmax(x @ w.T, axis=1)
    assert np.mean(pred == y) > 0.9


def test_spec_problems_match_legacy_constructors(multiclass_problem):
    """make_problem (now a spec + the shared build_problem) still yields
    planes with the documented algebra: ground-truth label => zero plane,
    oracle score == the example's max margin violation."""
    prob = multiclass_problem
    ex = jax.tree_util.tree_map(lambda a: a[0], prob.data)
    w = jnp.zeros((prob.d,), jnp.float32)
    plane = prob.oracle(w, ex)
    # at w=0 every label violates by exactly loss/n; argmax picks loss 1
    assert float(plane[-1]) == pytest.approx(1.0 / prob.n)
    # plane built from the truth is exactly zero (features cancel)
    from repro.core.oracles.multiclass import MulticlassSpec
    spec = MulticlassSpec(prob.meta["num_classes"])
    np.testing.assert_array_equal(
        np.asarray(spec.features(ex, ex["y"])
                   - spec.features(ex, ex["y"])), 0.0)
