"""Unit + property tests for the BCFW/MP-BCFW core (the paper's Alg. 1-3).

Property tests use deterministic seeded parametrization (this container has
no ``hypothesis``): seeds are drawn once from a fixed RandomState, so every
run exercises the same randomized cases.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import cache as pcache
from repro.cache import CacheLayout
from repro.core import averaging, bcfw, driver, gram, mpbcfw
from repro.core.selection import CostModel, IterationTracker
from repro.core.ssvm import (batched_oracle, dual_value, duality_gap,
                             init_state, primal_value, weights_of)


def _solver_run(problem, cfg):
    """The one-call convenience the removed driver.run shim provided."""
    from repro.api import Solver

    return Solver(problem, cfg).run()

LAM = 0.05

# Deterministic stand-in for hypothesis' integer strategy.
PROPERTY_SEEDS = [int(s) for s in
                  np.random.RandomState(1234).randint(0, 2 ** 31 - 1, 12)]


# ---------------------------------------------------------------------------
# Line search & dual algebra


@pytest.mark.parametrize("seed", PROPERTY_SEEDS)
def test_line_search_maximizes_dual(seed):
    """gamma* from the closed form beats any sampled gamma in [0,1]."""
    r = np.random.RandomState(seed)
    d = 6
    phi_i = jnp.asarray(r.randn(d + 1).astype(np.float32))
    phi_hat = jnp.asarray(r.randn(d + 1).astype(np.float32))
    phi = phi_i + jnp.asarray(r.randn(d + 1).astype(np.float32))
    g = bcfw.line_search_gamma(phi, phi_i, phi_hat, LAM)
    assert 0.0 <= float(g) <= 1.0

    def F(gam):
        p = phi + gam * (phi_hat - phi_i)
        return float(dual_value(p, LAM))

    best = F(float(g))
    for gam in np.linspace(0, 1, 21):
        assert best >= F(float(gam)) - 1e-4 * max(1.0, abs(best))


def test_dual_value_closed_form():
    phi = jnp.asarray([1.0, -2.0, 3.0, 0.5])
    expected = -(1 + 4 + 9) / (2 * LAM) + 0.5
    np.testing.assert_allclose(dual_value(phi, LAM), expected, rtol=1e-6)


def test_block_update_monotone(multiclass_problem):
    """Every BCFW block update is monotone in F (paper's invariant)."""
    prob = multiclass_problem
    lam = 1.0 / prob.n
    state = init_state(prob)
    r = np.random.RandomState(0)
    f_prev = float(dual_value(state.phi, lam))
    for _ in range(40):
        i = jnp.asarray(r.randint(prob.n))
        w = weights_of(state.phi, lam)
        ex = jax.tree_util.tree_map(lambda a: a[i], prob.data)
        phi_hat = prob.oracle(w, ex)
        state, _ = bcfw.block_update(state, i, phi_hat, lam)
        f = float(dual_value(state.phi, lam))
        assert f >= f_prev - 1e-7
        f_prev = f


def test_duality_gap_nonnegative(multiclass_problem):
    prob = multiclass_problem
    lam = 1.0 / prob.n
    state = init_state(prob)
    avg = averaging.init_averaging(prob.d)
    perm = jnp.arange(prob.n)
    for _ in range(3):
        state, avg = bcfw.jit_exact_pass(prob, state, avg, perm, lam=lam)
        assert float(duality_gap(prob, state, lam)) >= -1e-6


def test_phi_stays_sum_of_blocks(multiclass_problem):
    prob = multiclass_problem
    lam = 1.0 / prob.n
    state = init_state(prob)
    avg = averaging.init_averaging(prob.d)
    state, _ = bcfw.jit_exact_pass(prob, state, avg, jnp.arange(prob.n),
                                   lam=lam)
    np.testing.assert_allclose(np.asarray(jnp.sum(state.phi_i, axis=0)),
                               np.asarray(state.phi), atol=1e-4)


# ---------------------------------------------------------------------------
# Working sets (the repro.cache plane-cache subsystem)


def test_cache_lru_eviction():
    ws = pcache.init(CacheLayout(cap=2), 1, 3)
    p1 = jnp.asarray([1.0, 0, 0, 0.1])
    p2 = jnp.asarray([0, 1.0, 0, 0.2])
    p3 = jnp.asarray([0, 0, 1.0, 0.3])
    i = jnp.asarray(0)
    ws = pcache.insert(ws, i, p1, jnp.asarray(1))
    ws = pcache.insert(ws, i, p2, jnp.asarray(2))
    assert int(pcache.sizes(ws)[0]) == 2
    ws = pcache.insert(ws, i, p3, jnp.asarray(3))  # evicts p1 (oldest)
    assert int(pcache.sizes(ws)[0]) == 2
    planes = np.asarray(ws.planes[0])
    assert not any(np.allclose(row, np.asarray(p1)) for row in planes)


def test_cache_ttl_eviction():
    ws = pcache.init(CacheLayout(cap=4), 1, 3)
    ws = pcache.insert(ws, jnp.asarray(0), jnp.ones(4), jnp.asarray(0))
    ws2 = pcache.evict_stale(ws, jnp.asarray(5), ttl=10)
    assert int(pcache.sizes(ws2)[0]) == 1
    ws3 = pcache.evict_stale(ws, jnp.asarray(20), ttl=10)
    assert int(pcache.sizes(ws3)[0]) == 0


def test_approx_oracle_matches_naive():
    r = np.random.RandomState(0)
    d = 8
    ws = pcache.init(CacheLayout(cap=5), 1, d)
    for t in range(4):
        ws = pcache.insert(
            ws, jnp.asarray(0),
            jnp.asarray(r.randn(d + 1).astype(np.float32)), jnp.asarray(t))
    w = jnp.asarray(r.randn(d).astype(np.float32))
    plane, slot, score = pcache.approx_oracle(ws, jnp.asarray(0), w)
    scores = np.array(ws.planes[0, :, :d] @ w + ws.planes[0, :, d])
    scores[~np.asarray(ws.valid[0])] = -np.inf
    assert int(slot) == int(np.argmax(scores))
    np.testing.assert_allclose(float(score), scores.max(), rtol=1e-5)


def test_empty_cache_returns_zero_plane():
    ws = pcache.init(CacheLayout(cap=3), 1, 4)
    plane, slot, score = pcache.approx_oracle(
        ws, jnp.asarray(0), jnp.ones(4))
    np.testing.assert_allclose(np.asarray(plane), 0.0)
    assert float(score) == 0.0


# ---------------------------------------------------------------------------
# MP-BCFW (Alg. 3)


@pytest.mark.parametrize("problem_fixture",
                         ["multiclass_problem", "chain_problem",
                          "graph_problem"])
def test_mpbcfw_monotone_dual(problem_fixture, request):
    prob = request.getfixturevalue(problem_fixture)
    lam = 1.0 / prob.n
    mp = mpbcfw.init_mp_state(prob, cap=8)
    r = np.random.RandomState(0)
    f_prev = float(dual_value(mp.inner.phi, lam))
    for it in range(3):
        mp = mpbcfw.begin_iteration(mp, ttl=10)
        mp = mpbcfw.jit_exact_pass(prob, mp,
                                   jnp.asarray(r.permutation(prob.n)),
                                   lam=lam)
        f = float(dual_value(mp.inner.phi, lam))
        assert f >= f_prev - 1e-7
        f_prev = f
        for _ in range(2):
            mp = mpbcfw.jit_approx_pass(prob, mp,
                                        jnp.asarray(r.permutation(prob.n)),
                                        lam=lam)
            f = float(dual_value(mp.inner.phi, lam))
            assert f >= f_prev - 1e-7
            f_prev = f


def test_mpbcfw_beats_bcfw_per_oracle_call(multiclass_problem):
    """The paper's core claim: better gap at equal exact-oracle budget."""
    prob = multiclass_problem
    lam = 1.0 / prob.n
    cm = lambda: CostModel(oracle_cost=1.0, plane_cost=1e-4)
    res_b = _solver_run(prob, driver.RunConfig(
        lam=lam, algo="bcfw", max_iters=6, cost_model=cm()))
    res_m = _solver_run(prob, driver.RunConfig(
        lam=lam, algo="mpbcfw", max_iters=6, cap=16, cost_model=cm()))
    assert res_m.trace[-1].n_exact == res_b.trace[-1].n_exact
    assert res_m.trace[-1].gap < res_b.trace[-1].gap


def test_gram_pass_equivalent_to_plain_updates(multiclass_problem):
    """Sec-3.5 scalar recurrences == materialized updates (same block)."""
    prob = multiclass_problem
    lam = 1.0 / prob.n
    mp = mpbcfw.init_mp_state(prob, CacheLayout(cap=8, gram=True))
    r = np.random.RandomState(1)
    perm = jnp.asarray(r.permutation(prob.n))
    mp = mpbcfw.jit_exact_pass(prob, mp, perm, lam=lam)
    i = jnp.asarray(3)
    # naive: repeated approximate updates with materialized planes
    inner_naive = mp.inner
    for _ in range(5):
        w = weights_of(inner_naive.phi, lam)
        plane, slot, _ = pcache.approx_oracle(mp.cache, i, w)
        inner_naive, _ = bcfw.block_update(inner_naive, i, plane, lam)
    # gram: scalar recurrences on the cache-resident Gram block
    phi_i, phi, won = gram.multi_step_block_update(
        mp.cache.planes[i], mp.cache.valid[i], mp.cache.gram[i],
        mp.inner.phi, mp.inner.phi_i[i], lam, steps=5)
    np.testing.assert_allclose(np.asarray(phi),
                               np.asarray(inner_naive.phi), atol=2e-4)
    np.testing.assert_allclose(np.asarray(phi_i),
                               np.asarray(inner_naive.phi_i[i]), atol=2e-4)


# ---------------------------------------------------------------------------
# Batched on-device multi-pass loop


def _warm_mp_state(prob, lam, cap=8, seed=0):
    """MP state after one exact pass (working sets populated)."""
    rng = np.random.RandomState(seed)
    mp = mpbcfw.init_mp_state(prob, cap=cap)
    mp = mpbcfw.begin_iteration(mp, ttl=10)
    mp = mpbcfw.jit_exact_pass(prob, mp,
                               jnp.asarray(rng.permutation(prob.n)), lam=lam)
    return mp, rng


def test_multi_approx_pass_matches_sequential(multiclass_problem):
    """One batched program == N sequential jit_approx_pass calls."""
    prob = multiclass_problem
    lam = 1.0 / prob.n
    mp, rng = _warm_mp_state(prob, lam)
    n_passes = 4
    perms = jnp.asarray(
        np.stack([rng.permutation(prob.n) for _ in range(n_passes)]))
    clock = mpbcfw.make_slope_clock(0.0, float(dual_value(mp.inner.phi, lam)),
                                    float(prob.n), 1e-3)
    mp_b, clock_out, stats = mpbcfw.jit_multi_approx_pass(
        prob, mp, perms, clock, lam=lam, run_all=True)
    mp_s = mp
    for k in range(n_passes):
        mp_s = mpbcfw.jit_approx_pass(prob, mp_s, perms[k], lam=lam)
    assert int(stats.passes_run) == n_passes
    assert np.asarray(stats.ran).all()
    np.testing.assert_allclose(np.asarray(mp_b.inner.phi),
                               np.asarray(mp_s.inner.phi), atol=1e-6)
    np.testing.assert_allclose(np.asarray(mp_b.inner.phi_i),
                               np.asarray(mp_s.inner.phi_i), atol=1e-6)
    assert int(mp_b.inner.n_approx) == int(mp_s.inner.n_approx)
    assert (np.asarray(mp_b.cache.last_active)
            == np.asarray(mp_s.cache.last_active)).all()
    # the clock advanced by plane_cost * total_planes per pass
    total = int(jnp.sum(pcache.sizes(mp.cache)))
    np.testing.assert_allclose(float(clock_out.t),
                               float(clock.t) + n_passes * 1e-3 * total,
                               rtol=1e-5)


def test_multi_approx_pass_early_exit(multiclass_problem):
    """The on-device slope rule stops early; skipped passes are true no-ops
    (state equals replaying exactly passes_run sequential passes)."""
    prob = multiclass_problem
    lam = 1.0 / prob.n
    mp, rng = _warm_mp_state(prob, lam)
    n_batch = 32
    perms = jnp.asarray(
        np.stack([rng.permutation(prob.n) for _ in range(n_batch)]))
    f0 = float(dual_value(mp.inner.phi, lam))
    clock = mpbcfw.make_slope_clock(0.0, f0, float(prob.n), 1e-3)
    mp_b, _, stats = mpbcfw.jit_multi_approx_pass(prob, mp, perms, clock,
                                                  lam=lam)
    k = int(stats.passes_run)
    assert 1 <= k < n_batch          # improvements stall => rule fires
    assert not bool(stats.more)
    ran = np.asarray(stats.ran)
    assert ran[:k].all() and not ran[k:].any()
    assert np.asarray(stats.duals)[k:].sum() == 0.0  # zero-filled tail
    mp_s = mp
    for j in range(k):
        mp_s = mpbcfw.jit_approx_pass(prob, mp_s, perms[j], lam=lam)
    np.testing.assert_allclose(np.asarray(mp_b.inner.phi),
                               np.asarray(mp_s.inner.phi), atol=1e-6)
    assert int(mp_b.inner.n_approx) == int(mp_s.inner.n_approx)
    assert (np.asarray(mp_b.cache.last_active)
            == np.asarray(mp_s.cache.last_active)).all()


def test_multi_approx_pass_stop_matches_host_rule(multiclass_problem):
    """Device stopping decision == IterationTracker fed the same telemetry."""
    prob = multiclass_problem
    lam = 1.0 / prob.n
    mp, rng = _warm_mp_state(prob, lam)
    perms = jnp.asarray(
        np.stack([rng.permutation(prob.n) for _ in range(32)]))
    f0 = float(dual_value(mp.inner.phi, lam))
    clock = mpbcfw.make_slope_clock(0.0, f0, float(prob.n), 1e-3)
    mp_b, _, stats = mpbcfw.jit_multi_approx_pass(prob, mp, perms, clock,
                                                  lam=lam)
    k = int(stats.passes_run)
    assert not bool(stats.more)      # stopped by the rule, not the batch cap
    tr = IterationTracker()
    tr.start(0.0, f0)
    tr.record(float(prob.n), float(stats.f_entry))
    for j in range(k):
        tr.record(float(stats.times[j]), float(stats.duals[j]))
        expect_continue = j < k - 1
        assert tr.continue_approx() == expect_continue


def test_multi_approx_pass_gram_variant(multiclass_problem):
    """Gram-cache body inside the batched loop == one jit_approx_pass_gram."""
    prob = multiclass_problem
    lam = 1.0 / prob.n
    rng = np.random.RandomState(3)
    mp = mpbcfw.init_mp_state(prob, CacheLayout(cap=8, gram=True))
    mp = mpbcfw.begin_iteration(mp, ttl=10)
    mp = mpbcfw.jit_exact_pass(prob, mp,
                               jnp.asarray(rng.permutation(prob.n)),
                               lam=lam)
    perm = jnp.asarray(rng.permutation(prob.n))
    clock = mpbcfw.make_slope_clock(
        0.0, float(dual_value(mp.inner.phi, lam)), float(prob.n), 1e-3)
    mp_b, _, stats = mpbcfw.jit_multi_approx_pass(
        prob, mp, perm[None], clock, lam=lam, steps=5, run_all=True)
    inner, cache_out, avg = gram.jit_approx_pass_gram(
        mp.inner, mp.cache, mp.avg, perm, mp.outer_it, lam=lam, steps=5)
    np.testing.assert_allclose(np.asarray(mp_b.inner.phi),
                               np.asarray(inner.phi), atol=1e-5)
    assert int(mp_b.inner.n_approx) == int(inner.n_approx)


@pytest.mark.parametrize("algo", ["mpbcfw", "mpbcfw-avg", "mpbcfw-gram",
                                  "mpbcfw-shard-gram"])
def test_driver_one_dispatch_one_sync_per_iteration(multiclass_problem,
                                                    algo):
    """SyncLedger contract: the fused control loop performs exactly one
    program dispatch and one host sync per outer iteration (previously
    two dispatches: exact pass, then multi_approx_pass)."""
    prob = multiclass_problem
    lam = 1.0 / prob.n
    res = _solver_run(prob, driver.RunConfig(
        lam=lam, algo=algo, max_iters=5, cap=16,
        cost_model=CostModel()))
    for row in res.trace:
        assert row.host_syncs == 1
        assert row.dispatches == 1
        # old loop: one sync per approximate pass + one for the exact pass
        assert row.approx_passes + 1 >= 5 * row.host_syncs


# ---------------------------------------------------------------------------
# Fused outer iteration (one program per outer iteration)


def test_outer_iteration_matches_two_program_sequence(multiclass_problem):
    """Fused program == begin_iteration + jit_exact_pass +
    jit_multi_approx_pass, bitwise — state, telemetry, clock, and the
    on-device f0 seed (vs the host-seeded legacy clock)."""
    prob = multiclass_problem
    lam = 1.0 / prob.n
    rng = np.random.RandomState(7)
    mp_l = mpbcfw.init_mp_state(prob, cap=8)
    mp_f = mpbcfw.init_mp_state(prob, cap=8)
    for _ in range(3):   # iterate to populate worksets / nonzero phi_i
        perm = jnp.asarray(rng.permutation(prob.n))
        perms = jnp.asarray(
            np.stack([rng.permutation(prob.n) for _ in range(8)]))
        # legacy: two programs, host-seeded f0
        f0 = float(dual_value(mp_l.inner.phi, lam))
        clock_l = mpbcfw.make_slope_clock(0.0, f0, float(prob.n), 1e-3)
        mp_l = mpbcfw.begin_iteration(mp_l, 10)
        mp_l = mpbcfw.jit_exact_pass(prob, mp_l, perm, lam=lam)
        mp_l, clock_l, st_l = mpbcfw.jit_multi_approx_pass(
            prob, mp_l, perms, clock_l, lam=lam)
        # fused: one program, f0 seeded from the on-device dual
        clock_f = mpbcfw.make_slope_clock(0.0, 0.0, float(prob.n), 1e-3)
        mp_f, clock_f, st_f = mpbcfw.jit_outer_iteration(
            prob, mp_f, perm, perms, clock_f, lam=lam, ttl=10)
        for a, b in zip(jax.tree_util.tree_leaves(mp_l),
                        jax.tree_util.tree_leaves(mp_f)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert int(st_l.passes_run) == int(st_f.passes_run)
        np.testing.assert_array_equal(np.asarray(st_l.duals),
                                      np.asarray(st_f.duals))
        np.testing.assert_array_equal(np.asarray(st_l.planes),
                                      np.asarray(st_f.planes))
        assert float(clock_l.t) == float(clock_f.t)
        assert int(st_f.ws_total) == int(jnp.sum(pcache.sizes(mp_f.cache)))


def test_outer_iteration_gram_matches_two_program_sequence(
        multiclass_problem):
    """The Sec-3.5 Gram variant is folded into the same fused program:
    == jit_exact_pass (gram-aware insert) + jit_multi_approx_pass on a
    gram-carrying cache, bitwise."""
    prob = multiclass_problem
    lam = 1.0 / prob.n
    rng = np.random.RandomState(11)
    layout = CacheLayout(cap=8, gram=True)
    mp_l = mpbcfw.init_mp_state(prob, layout)
    mp_f = mpbcfw.init_mp_state(prob, layout)
    for _ in range(2):
        perm = jnp.asarray(rng.permutation(prob.n))
        perms = jnp.asarray(
            np.stack([rng.permutation(prob.n) for _ in range(4)]))
        f0 = float(dual_value(mp_l.inner.phi, lam))
        clock_l = mpbcfw.make_slope_clock(0.0, f0, float(prob.n), 1e-3)
        mp_l = mpbcfw.begin_iteration(mp_l, 10)
        mp_l = mpbcfw.jit_exact_pass(prob, mp_l, perm, lam=lam)
        mp_l, clock_l, st_l = mpbcfw.jit_multi_approx_pass(
            prob, mp_l, perms, clock_l, lam=lam, steps=5)
        clock_f = mpbcfw.make_slope_clock(0.0, 0.0, float(prob.n), 1e-3)
        mp_f, clock_f, st_f = mpbcfw.jit_outer_iteration(
            prob, mp_f, perm, perms, clock_f, lam=lam, ttl=10, steps=5)
        for a, b in zip(jax.tree_util.tree_leaves(mp_l),
                        jax.tree_util.tree_leaves(mp_f)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert int(st_l.passes_run) == int(st_f.passes_run)
        np.testing.assert_array_equal(np.asarray(st_l.duals),
                                      np.asarray(st_f.duals))


def test_outer_iteration_zero_approx_budget(multiclass_problem):
    """max_approx_passes=0: the fused program still runs the exact pass
    and reports f_entry/ws_total in one sync (no fallback dual fetch)."""
    prob = multiclass_problem
    lam = 1.0 / prob.n
    res = _solver_run(prob, driver.RunConfig(
        lam=lam, algo="mpbcfw", max_iters=3, cap=16, max_approx_passes=0,
        cost_model=CostModel()))
    for row in res.trace:
        assert row.approx_passes == 0
        assert row.host_syncs == 1
        assert row.dispatches == 1
        assert row.ws_mean > 0.0
    duals = [t.dual for t in res.trace]
    assert all(b >= a - 1e-6 for a, b in zip(duals, duals[1:]))


def test_ws_mean_one_statistic_in_both_branches(multiclass_problem):
    """Fig. 5: ws_mean is the same statistic whether or not approximate
    passes ran.  Iteration 0's exact pass is identical across the two
    runs (the exact perm is drawn before the approx perms), so the
    reported ws_mean must agree exactly."""
    prob = multiclass_problem
    lam = 1.0 / prob.n
    kw = dict(lam=lam, algo="mpbcfw", max_iters=1, cap=16, seed=5)
    res_no = _solver_run(prob, driver.RunConfig(
        max_approx_passes=0, cost_model=CostModel(), **kw))
    res_yes = _solver_run(prob, driver.RunConfig(
        cost_model=CostModel(), **kw))
    assert res_yes.trace[0].approx_passes > 0
    assert res_no.trace[0].ws_mean == res_yes.trace[0].ws_mean


def test_wall_clock_excludes_evaluation_time(multiclass_problem,
                                             monkeypatch):
    """Regression: `_evaluate`'s oracle sweeps (n exact oracle calls per
    iteration) are "Not timed" — a deliberately slow evaluation must not
    inflate TraceRow.time."""
    from repro.api import solver as api_solver

    prob = multiclass_problem
    lam = 1.0 / prob.n
    real = api_solver.evaluate_objectives
    sleep_s = 0.15

    def slow_evaluation(*a, **kw):
        time.sleep(sleep_s)
        return real(*a, **kw)

    monkeypatch.setattr(api_solver, "evaluate_objectives", slow_evaluation)
    iters = 3
    wall0 = time.perf_counter()
    res = _solver_run(prob, driver.RunConfig(
        lam=lam, algo="mpbcfw", max_iters=iters, cap=16,
        max_approx_passes=4, cost_model=None))   # wall-clock mode
    wall = time.perf_counter() - wall0
    slept = iters * sleep_s                      # one _evaluate per iter
    assert wall >= slept                         # the sleeps did happen
    # ... but none of the slept time reached the trace:
    assert res.trace[-1].time <= wall - 0.9 * slept
    # times are still monotone and positive
    ts = [r.time for r in res.trace]
    assert all(t >= 0.0 for t in ts)
    assert all(b >= a for a, b in zip(ts, ts[1:]))


def test_cache_batched_scoring_matches_per_block(multiclass_problem):
    """approx_oracle_all (fused score+select) == per-block approx_oracle."""
    prob = multiclass_problem
    lam = 1.0 / prob.n
    mp, rng = _warm_mp_state(prob, lam)
    w = jnp.asarray(rng.randn(prob.d).astype(np.float32))
    planes_b, slots_b, scores_b = pcache.approx_oracle_all(mp.cache, w)
    for i in range(0, prob.n, 7):
        plane, slot, score = pcache.approx_oracle(mp.cache, jnp.asarray(i),
                                                  w)
        np.testing.assert_allclose(np.asarray(planes_b[i]),
                                   np.asarray(plane), atol=1e-6)
        assert int(slots_b[i]) == int(slot)
        np.testing.assert_allclose(float(scores_b[i]), float(score),
                                   rtol=1e-5)


def test_averaging_formula():
    """bar_phi^(k) = 2/(k(k+1)) sum_t t phi^(t) (paper Sec. 3.6)."""
    r = np.random.RandomState(0)
    d = 5
    avg = averaging.init_averaging(d)
    phis = [r.randn(d + 1).astype(np.float32) for _ in range(6)]
    for p in phis:
        avg = averaging.update_average(avg, jnp.asarray(p), exact=True)
    k = len(phis)
    expected = sum((t + 1) * p for t, p in enumerate(phis)) \
        * (2.0 / (k * (k + 1)))
    np.testing.assert_allclose(np.asarray(avg.bar_exact), expected,
                               rtol=1e-4, atol=1e-5)


def test_averaging_extract_best_interpolation():
    r = np.random.RandomState(0)
    d = 4
    avg = averaging.init_averaging(d)
    avg = averaging.update_average(
        avg, jnp.asarray(r.randn(d + 1).astype(np.float32)), exact=True)
    avg = averaging.update_average(
        avg, jnp.asarray(r.randn(d + 1).astype(np.float32)), exact=False)
    out = averaging.extract(avg, LAM)
    f = float(dual_value(out, LAM))
    for beta in np.linspace(0, 1, 11):
        cand = (1 - beta) * avg.bar_exact + beta * avg.bar_approx
        assert f >= float(dual_value(cand, LAM)) - 1e-5


# ---------------------------------------------------------------------------
# Selection rule (Sec. 3.4)


def test_slope_rule_continues_on_steep_segment():
    tr = IterationTracker()
    tr.start(0.0, 0.0)
    tr.record(10.0, 1.0)     # exact pass: slope 0.1
    tr.record(10.5, 1.5)     # approx: slope 1.0 > iteration chord
    assert tr.continue_approx()
    tr.record(11.0, 1.51)    # approx: slope 0.02 < chord
    assert not tr.continue_approx()


def test_cost_model_clock():
    cm = CostModel(oracle_cost=2.0, plane_cost=0.01)
    assert cm.exact_pass(10) == 20.0
    assert cm.approx_pass(100) == 21.0


# ---------------------------------------------------------------------------
# Driver end-to-end: all algorithms reach a small gap on an easy problem


@pytest.mark.parametrize("algo", ["bcfw", "bcfw-avg", "mpbcfw",
                                  "mpbcfw-avg", "mpbcfw-gram",
                                  "mpbcfw-shard", "mpbcfw-shard-avg",
                                  "mpbcfw-shard-gram"])
def test_algorithms_converge(multiclass_problem, algo):
    prob = multiclass_problem
    lam = 1.0 / prob.n
    res = _solver_run(prob, driver.RunConfig(
        lam=lam, algo=algo, max_iters=8, cap=16,
        cost_model=CostModel()))
    # MP variants converge much faster per pass (the paper's claim); plain
    # BCFW merely makes steady progress in 8 passes.
    frac = 0.05 if algo.startswith("mp") else 0.6
    assert res.trace[-1].gap < frac * (res.trace[0].gap + 1e-9) \
        or res.trace[-1].gap < 2e-3
    duals = [t.dual for t in res.trace]
    assert all(b >= a - 1e-6 for a, b in zip(duals, duals[1:]))


def test_fw_and_ssg_run(multiclass_problem):
    prob = multiclass_problem
    lam = 1.0 / prob.n
    res = _solver_run(prob, driver.RunConfig(lam=lam, algo="fw",
                                            max_iters=5,
                                            cost_model=CostModel()))
    assert res.trace[-1].dual >= res.trace[0].dual - 1e-6
    res2 = _solver_run(prob, driver.RunConfig(lam=lam, algo="ssg",
                                             max_iters=5,
                                             cost_model=CostModel()))
    assert np.isfinite(res2.trace[-1].primal)
