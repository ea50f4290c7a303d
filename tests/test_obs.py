"""repro.obs: recorder wiring, schema, metrics, and the
sync-contract / checkpoint guarantees the obs layer must not break.

The load-bearing assertions:

  * installing a :class:`~repro.obs.RunRecorder` leaves every
    mpbcfw-family engine at exactly 1 dispatch + 1 host sync per outer
    iteration (SyncLedger-asserted through the TraceRow columns);
  * the on-device ObsMetrics drain produces real hit/evict numbers with
    zero extra host work;
  * CostModel/wall calibration constants and the metrics registry
    survive a checkpoint round trip bit for bit, and attaching a
    recorder changes none of them;
  * CollectiveTrace raises a clear RuntimeError when used outside a
    begin()/commit() window (regression: used to be an AttributeError).
"""
import dataclasses
import json
import types

import numpy as np
import pytest

from repro.api import RunConfig, Solver
from repro.checkpoint.manager import CheckpointManager
from repro.core.selection import CostModel
from repro.obs import (MetricsRegistry, RunRecorder, diff_runs, load_run,
                       summarize, summarize_run, validate_file,
                       validate_record)
from repro.shard.telemetry import CollectiveTrace


def _cm():
    return CostModel(oracle_cost=1.0, plane_cost=1e-3)


def _cfg(algo, mesh=None, **kw):
    base = dict(lam=0.05, algo=algo, cap=8, ttl=4, max_iters=5,
                max_approx_passes=8, approx_batch=8, seed=1,
                cost_model=_cm())
    base.update(kw)
    if mesh is not None:
        base["mesh"] = mesh
    return RunConfig(**base)


# ---------------------------------------------------------------------------
# S1: CollectiveTrace misuse is a RuntimeError, not an AttributeError


def test_collective_trace_outside_window_raises():
    import jax.numpy as jnp

    tr = CollectiveTrace()
    with pytest.raises(RuntimeError, match=r"psum\(\) called outside"):
        tr.psum(jnp.ones(3), "data", tag="pass")
    with pytest.raises(RuntimeError, match=r"commit\(\) called outside"):
        tr.commit()
    # ...and again after a completed window (commit clears the program).
    tr.begin("p")
    tr.commit()
    with pytest.raises(RuntimeError, match="outside a begin"):
        tr.commit()


def test_collective_trace_counts_bytes():
    import jax
    import jax.numpy as jnp

    tr = CollectiveTrace()
    tr.begin("p")
    jax.make_jaxpr(
        jax.vmap(lambda x: tr.psum(x, "i", tag="setup"), axis_name="i")
    )(jnp.ones((2, 4), jnp.float32))
    tr.commit()
    assert tr.count("p", "setup") == 1
    assert tr.bytes_of("p", "setup") == 16  # 4 x f32


# ---------------------------------------------------------------------------
# S3: recorder installed => still 1 dispatch + 1 host sync per iteration


@pytest.mark.parametrize("algo", ["mpbcfw", "mpbcfw-gram", "mpbcfw-shard"])
def test_recorder_preserves_sync_contract(tmp_path, multiclass_problem,
                                          data_mesh, algo):
    """The SyncLedger columns must show the fused-program contract with a
    RunRecorder installed: no extra dispatch, sync, or callback from
    observability (approx_batch >= max_approx_passes, so no overflow
    continuations either)."""
    prob = multiclass_problem
    mesh = data_mesh if algo == "mpbcfw-shard" else None
    path = tmp_path / f"{algo}.jsonl"
    with RunRecorder(str(path)) as rec:
        res = Solver(prob, _cfg(algo, mesh=mesh), recorder=rec).run()
    assert len(res.trace) == 5
    for row in res.trace:
        assert row.dispatches == 1
        assert row.host_syncs == 1
    # The same run, bare: the recorder must not perturb the optimization.
    bare = Solver(prob, _cfg(algo, mesh=mesh)).run()
    for ra, rb in zip(res.trace, bare.trace):
        assert ra == rb


def test_on_device_metrics_measure_eviction(multiclass_problem):
    """Small cap + short TTL forces evictions; the counters must drain
    real (nonzero) numbers without changing the sync columns."""
    prob = multiclass_problem
    res = Solver(prob, _cfg("mpbcfw", cap=4, ttl=2, max_iters=8)).run()
    assert all(r.host_syncs == 1 for r in res.trace)
    assert any(r.planes_evicted > 0 for r in res.trace)
    assert all(0.0 <= r.cache_hit_rate <= 1.0 for r in res.trace)
    # Single-block inserts bound the hit rate by occupancy/n.
    assert res.trace[0].cache_hit_rate <= 1.0


# ---------------------------------------------------------------------------
# Recorder output: schema, summary, diff


def test_recorder_jsonl_schema_and_summary(tmp_path, multiclass_problem):
    prob = multiclass_problem
    path = tmp_path / "run.jsonl"
    with RunRecorder(str(path)) as rec:
        Solver(prob, _cfg("mpbcfw"), recorder=rec).run()

    count, errs = validate_file(str(path))
    assert errs == []
    run = load_run(str(path))
    assert run["meta"]["algo"] == "mpbcfw"
    assert "engine_budgets" in run["meta"]
    assert len(run["rows"]) == 5
    assert [sp["name"] for sp in run["spans"]] == ["outer_iteration"] * 5

    s = summarize(run)
    assert s["iterations"] == 5
    assert s["contract"]["host_syncs_per_iter_max"] == 1
    assert s["contract"]["dispatches_per_iter_max"] == 1
    assert s["contract"]["within_budget"]
    assert s["calls_to_gap"]  # relative gap targets always present
    assert s == summarize_run(str(path))

    d = diff_runs(run, run)
    assert d["deltas"]["final_gap"]["delta"] == 0.0


def test_schema_rejects_bad_records():
    errs = validate_record({"type": "row"})
    assert errs and all("missing" in e for e in errs)
    assert validate_record({"type": "meta", "schema": 1, "algo": "mpbcfw",
                            "n": 4, "d": 8, "time_mode": "cost_model",
                            "engine_budgets": {}}) == []
    assert validate_record({"no_type": True}) == ["unknown record type None"]
    errs = validate_record({"type": "event", "name": "x",
                            "t": float("nan")})
    assert errs and "non-finite" in errs[0]


# ---------------------------------------------------------------------------
# Metrics registry


def test_metrics_registry_roundtrip():
    reg = MetricsRegistry()
    reg.counter("oracle_calls").inc(7)
    reg.gauge("gap").set(0.25)
    h = reg.histogram("iteration_time")
    for v in (0.1, 0.2, 0.4, 0.8):
        h.observe(v)
    snap = reg.snapshot()
    # JSON-safe and loadable into a fresh registry, bit for bit.
    snap2 = json.loads(json.dumps(snap))
    reg2 = MetricsRegistry()
    reg2.load(snap2)
    assert reg2.counter("oracle_calls").value == 7
    assert reg2.gauge("gap").value == 0.25
    assert reg2.histogram("iteration_time").count == 4
    assert reg2.snapshot() == snap
    assert 0.1 <= reg2.histogram("iteration_time").quantile(0.5) <= 0.8


def test_registry_observe_row_counts_deltas(tmp_path, multiclass_problem):
    """n_exact/n_approx are cumulative in TraceRow; the registry must
    accumulate per-iteration deltas, not re-add the totals."""
    prob = multiclass_problem
    solver = Solver(prob, _cfg("mpbcfw"))
    res = solver.run()
    last = res.trace[-1]
    snap = solver.metrics.snapshot()
    assert snap["oracle_calls"]["value"] == last.n_exact
    assert snap["approx_calls"]["value"] == last.n_approx
    assert snap["iterations"]["value"] == len(res.trace)
    assert snap["host_syncs"]["value"] == sum(r.host_syncs
                                              for r in res.trace)


# ---------------------------------------------------------------------------
# S2: calibration constants + metrics snapshot survive checkpoint resume


def test_checkpoint_calibration_bitwise_resume(tmp_path,
                                               multiclass_problem):
    """Wall-clock mode fits est_exact/est_plane from measured times —
    arbitrary floats.  The manifest stores them explicitly and restore
    must reproduce them bit for bit (JSON round-trips Python floats
    exactly), along with the wall regression history and the metrics
    registry."""
    prob = multiclass_problem

    def cfg():
        return RunConfig(lam=0.05, algo="mpbcfw", cap=8, max_iters=6,
                         max_approx_passes=4, approx_batch=4, seed=2,
                         cost_model=None)  # wall clock => fitted floats

    mgr = CheckpointManager(str(tmp_path / "ckpt"))
    s1 = Solver(prob, cfg())
    it = s1.iterate()
    for _ in range(4):
        next(it)
    step = s1.save(mgr)

    manifest = mgr.load_manifest(step)
    cal = manifest["extra"]["calibration"]
    assert set(cal) == {"est_exact", "est_plane", "wall_x", "wall_y"}
    assert cal["est_exact"] == s1._est_exact
    assert len(cal["wall_x"]) == len(cal["wall_y"]) == 4
    assert manifest["metrics"]["iterations"]["value"] == 4

    s2 = Solver.restore(prob, cfg(), mgr)
    assert s2._est_exact == s1._est_exact          # bitwise
    assert s2._est_plane == s1._est_plane
    assert s2._wall_x == s1._wall_x
    assert s2._wall_y == s1._wall_y
    assert s2.metrics.snapshot() == s1.metrics.snapshot()


@pytest.mark.parametrize("saved_by", ["this_release", "older_release"])
def test_restore_rebuilds_last_row_from_any_release(tmp_path,
                                                    multiclass_problem,
                                                    saved_by):
    """A manifest's ``last_row`` restores whichever release wrote it: an
    older one stored ``oracle_share`` (since removed) and no ``eval_s``
    or ``compiles`` (since added, which then take their defaults)."""
    prob = multiclass_problem

    def cfg():
        return RunConfig(lam=0.05, algo="mpbcfw", cap=8, max_iters=4,
                         max_approx_passes=4, approx_batch=4, seed=2)

    mgr = CheckpointManager(str(tmp_path / "ckpt"))
    s1 = Solver(prob, cfg())
    it = s1.iterate()
    for _ in range(2):
        next(it)
    step = s1.save(mgr)
    want = dataclasses.asdict(s1._last_row)
    if saved_by == "older_release":
        path = mgr._step_dir(step) / "manifest.json"
        manifest = json.loads(path.read_text())
        row = manifest["extra"]["last_row"]
        del row["eval_s"], row["compiles"]
        row["oracle_share"] = 0.75
        path.write_text(json.dumps(manifest))
        want.update(eval_s=0.0, compiles=0)

    s2 = Solver.restore(prob, cfg(), mgr)
    assert dataclasses.asdict(s2._last_row) == want
    rest = list(s2.iterate())                  # and the run goes on
    assert [r.iteration for r in rest] == [2, 3]


def test_checkpoint_save_restore_spans_recorded(tmp_path,
                                                multiclass_problem):
    prob = multiclass_problem
    mgr = CheckpointManager(str(tmp_path / "ckpt"))
    path = tmp_path / "run.jsonl"
    with RunRecorder(str(path)) as rec:
        s1 = Solver(prob, _cfg("mpbcfw", max_iters=3), recorder=rec)
        it = s1.iterate()
        next(it)
        s1.save(mgr)
    run = load_run(str(path))
    assert any(sp["name"] == "checkpoint_save" for sp in run["spans"])


# ---------------------------------------------------------------------------
# The recorder observes only: with a scripted wall clock, a Solver with a
# recorder and one without calibrate the slope rule identically


def _scripted_clock(script):
    """A ``time`` stand-in whose ``perf_counter`` advances by the
    script's steps in turn (cycling), from 0."""
    state = {"t": 0.0, "k": 0}

    def perf_counter():
        state["t"] += script[state["k"] % len(script)]
        state["k"] += 1
        return state["t"]
    return types.SimpleNamespace(perf_counter=perf_counter)


@pytest.mark.parametrize("script", [(0.01,), (0.002, 0.05, 0.001),
                                    (0.3, 0.001, 0.02, 0.007)],
                         ids=["steady", "bursty", "uneven"])
def test_recorder_leaves_wall_calibration_unchanged(tmp_path, monkeypatch,
                                                    multiclass_problem,
                                                    script):
    """Wall mode: a recorder changes neither the slope rule's cost
    constants nor the pass schedule they drive (approx_batch <
    max_approx_passes, so overflow continuations run too)."""
    from repro.api import solver as solver_mod

    def run(recorder):
        monkeypatch.setattr(solver_mod, "time", _scripted_clock(script))
        solver = Solver(multiclass_problem,
                        _cfg("mpbcfw", cost_model=None, max_iters=4,
                             approx_batch=2, max_approx_passes=8),
                        recorder=recorder)
        rows = solver.run().trace
        return ((solver._est_exact, solver._est_plane),
                [(r.n_exact, r.n_approx, r.approx_passes, r.dual)
                 for r in rows])

    bare = run(None)
    with RunRecorder(str(tmp_path / "wall.jsonl")) as rec:
        recorded = run(rec)
    assert recorded == bare
