"""repro.obs.spans: the program's own spans, device scopes and counters.

They are always on and observe only, so the tests check that they are
where they should be: in the compiled programs' op metadata, in a
profiler capture of the Solver and of the serving round (nested as
designed), and in the host columns and counters they feed.
"""
import glob
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import serve
from repro.api import RunConfig, Solver
from repro.core import mpbcfw
from repro.core.oracles.chain import ChainSpec
from repro.data import synthetic
from repro.obs import spans


def _capture(tmp_path, fn):
    """Run ``fn`` under a CPU profiler session; return the program's
    ``repro:*`` host spans as ``(name, start, end, stats)``, by start."""
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(tmp_path, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(spans.PREFIX):
                    out.append((ev.name[len(spans.PREFIX):], ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return sorted(out, key=lambda s: s[1])


def _inside(outer, spans_):
    return [s for s in spans_ if outer[1] <= s[1] and s[2] <= outer[2]
            and s is not outer]


# ---------------------------------------------------------------------------
# Device scopes: the fused outer iteration's HLO op metadata


@pytest.mark.parametrize("gram", [False, True], ids=["plain", "gram"])
def test_fused_outer_iteration_hlo_carries_scopes(multiclass_problem, gram):
    from repro.cache import CacheLayout

    prob = multiclass_problem
    mp = mpbcfw.init_mp_state(prob, CacheLayout(cap=4, gram=gram))
    perm = jnp.arange(prob.n)
    clock = mpbcfw.make_slope_clock(0.0, 0.0, 1.0, 1e-3)
    hlo = mpbcfw._jit_outer_iteration.lower(
        prob.oracle, prob.n, prob.data, mp, perm, jnp.stack([perm] * 2),
        clock, None, lam=0.05, ttl=4, steps=2, run_all=False,
    ).compile().as_text()
    paths = [line.split('op_name="', 1)[1].split('"', 1)[0]
             for line in hlo.splitlines() if 'op_name="' in line]
    parts = [p.split("/") for p in paths]
    assert any(spans.EVICT in p for p in parts)
    assert any(spans.EXACT_PASS in p for p in parts)
    assert any(spans.APPROX_PASS in p for p in parts)
    # the oracle runs inside the exact pass, never outside it
    oracle = [p for p in parts if spans.ORACLE in p]
    assert oracle
    assert all(spans.EXACT_PASS in p[:p.index(spans.ORACLE)]
               for p in oracle)


# ---------------------------------------------------------------------------
# Host spans on the profiler's clock


def test_solver_profile_holds_nested_iteration_spans(tmp_path,
                                                     multiclass_problem):
    cfg = RunConfig(lam=0.05, algo="mpbcfw", cap=8, max_iters=2,
                    max_approx_passes=4, approx_batch=4, seed=1)
    Solver(multiclass_problem, cfg).run()      # compiled before the capture
    got = _capture(tmp_path, Solver(multiclass_problem, cfg).run)
    iters = [s for s in got if s[0] == spans.ITERATION]
    assert [int(s[3]["step_num"]) for s in iters] == [0, 1]
    for it in iters:
        names = [s[0] for s in _inside(it, got)]
        assert names == [spans.DISPATCH, spans.SYNC, spans.EVALUATE]
    assert {s[0] for s in got} == {spans.ITERATION, spans.DISPATCH,
                                   spans.SYNC, spans.EVALUATE}


def _chain_server(batch_size=4):
    spec = ChainSpec(num_labels=4)
    X, Y, M = synthetic.ocr_like(n=10, f=5, num_labels=4, mean_len=5,
                                 max_len=7, seed=4)
    w = jnp.asarray(np.random.RandomState(2).randn(
        spec.dim({"x": X})).astype(np.float32))
    server = serve.StructuredServer(serve.ServableModel(spec, w),
                                    batch_size=batch_size,
                                    bucket_granularity=16)
    reqs = [{"x": X[i, :L], "y": Y[i, :L], "mask": M[i, :L]}
            for i, L in enumerate(M.sum(axis=1).astype(int))]
    return server, reqs


def test_server_profile_holds_nested_round_spans(tmp_path):
    server, reqs = _chain_server()
    server.serve(reqs)                          # compiled before the capture
    got = _capture(tmp_path, lambda: server.serve(reqs))
    rounds = [s for s in got if s[0] == spans.ROUND]
    assert len(rounds) == 3                     # ceil(10 / 4)
    assert [int(r[3]["batch"]) for r in rounds] == [4, 4, 2]
    assert all(str(r[3]["bucket"]) == "16" for r in rounds)
    for r in rounds:
        assert [s[0] for s in _inside(r, got)] == [
            spans.PICK, spans.PAD, spans.STACK, spans.DECODE, spans.SYNC,
            spans.ANSWER]


# ---------------------------------------------------------------------------
# Host columns and counters


def test_eval_seconds_and_run_clock_add_up_to_the_wall(multiclass_problem):
    cfg = RunConfig(lam=0.05, algo="mpbcfw", cap=8, max_iters=4,
                    max_approx_passes=4, approx_batch=4, seed=1)
    Solver(multiclass_problem, cfg).run()      # compiled before timing
    solver = Solver(multiclass_problem, cfg)
    t0 = time.perf_counter()
    rows = solver.run().trace
    wall = time.perf_counter() - t0
    assert all(r.eval_s > 0 for r in rows)
    inside = sum(r.eval_s for r in rows) + rows[-1].time
    assert inside <= wall
    assert inside == pytest.approx(wall, rel=0.05, abs=0.02)


def test_compiles_count_a_forced_recompile(multiclass_problem):
    cfg = RunConfig(lam=0.05, algo="mpbcfw", cap=8, max_iters=3,
                    max_approx_passes=4, approx_batch=4, seed=1)
    Solver(multiclass_problem, cfg).run()      # every program compiled
    it = Solver(multiclass_problem, cfg).iterate()
    next(it)
    steady = next(it).compiles
    jax.clear_caches()                          # the next dispatch compiles
    forced = next(it).compiles
    assert forced > steady


def test_compile_count_listens_once():
    x = jnp.ones(7)
    base = spans.compile_count()
    assert spans.compile_count() == base       # a second call adds nothing
    jax.jit(lambda x: jnp.cos(x) * 5.0 - 3.0)(x)
    assert spans.compile_count() == base + 1


def test_cost_model_rows_keep_host_columns_at_zero(multiclass_problem):
    from repro.core.selection import CostModel

    cfg = RunConfig(lam=0.05, algo="mpbcfw", cap=8, max_iters=2,
                    max_approx_passes=4, approx_batch=4, seed=1,
                    cost_model=CostModel(oracle_cost=1.0, plane_cost=1e-3))
    for r in Solver(multiclass_problem, cfg).run().trace:
        assert (r.eval_s, r.compiles) == (0.0, 0)
