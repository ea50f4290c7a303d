"""Training cells: whole MP-BCFW trainings to a duality-gap target.

Set-up makes the data on the device from the seed, builds the problem
and the first :class:`repro.api.Solver`, and drives that solver through
its first ``CHECK_ITERS`` outer iterations with ``Solver.iterate()``,
the call the window uses: that is what the correctness check compares.
It then dispatches the engine's overflow program once, which the window
runs whenever the slope rule asks for more than ``approx_batch`` passes,
so that every program the window runs is compiled (or loaded) before it.

The window then repeats whole trainings, each from a fresh solver built
on the same problem with its own pass orders (its solver seed is drawn
from the run's seed and its index), until ``--seconds`` have passed.
Each training runs until its gap first reaches the traffic file's
``gap_target``; its ``train_s`` is the wall time from the solver's
construction to that crossing, interpolated in log-gap between the rows
that straddle it (evaluation included).  A training the window cuts off
is not counted.

A traced run (``--trace 1``) then profiles one more training's first
``TRACE_ROWS`` iterations: a whole window would hold millions of device
operations, and stopping the profiler takes tens of seconds.  Last the
solver is freed, the peak memory read, and the plain reference
(``benchkit.reference.mpbcfw``, float64 NumPy) follows the checked
iterations' schedule from the same data.
"""
from __future__ import annotations

import dataclasses
import gc
import time
from dataclasses import dataclass, field
from typing import List, Optional

import numpy as np

from .. import device as dev
from .. import trace as tr
from ..outcome import Outcome, judge
from ..reference import F64
from ..reference import mpbcfw as ref_mpbcfw
from ..stats import crossing_time

CHECK_ITERS = 3
TRACE_ROWS = 1


@dataclass
class Training:
    start: float                      # perf_counter at construction
    iter_start: float = 0.0           # perf_counter at the first iterate
    stamps: List[float] = field(default_factory=list)  # since start
    rows: list = field(default_factory=list)
    crossed: Optional[float] = None   # train_s
    cut: bool = False                 # cut off by the window's end


def run_config(cell, seed: int, max_iters: int):
    from repro.api import RunConfig

    c, t = cell.config, cell.traffic
    return RunConfig(lam=float(c["lam_times_n"]) / int(c["n"]),
                     algo=t["algo"], cap=int(c["cap"]), ttl=int(c["ttl"]),
                     max_iters=max_iters,
                     max_approx_passes=int(c["max_approx_passes"]),
                     approx_batch=int(c["approx_batch"]),
                     seed=int(seed) & 0xFFFFFFFF)


def training_seed(seed: int, k: int) -> int:
    """Solver seed of the window's ``k``-th training (0 is set-up's)."""
    return int(np.random.SeedSequence([seed, k]).generate_state(1)[0])


def _instrument(solver) -> None:
    """Host spans around the calls into the engine (the benchmark's own
    spans: they change no behaviour)."""
    eng = solver.engine
    for name in ("outer_iteration", "continue_passes", "read_stats",
                 "evaluate"):
        fn = getattr(eng, name)

        def wrapped(*a, _fn=fn, _name=name, **kw):
            with tr.span(_name):
                return _fn(*a, **kw)
        setattr(eng, name, wrapped)


def one_training(problem, rc, target: float, deadline: float,
                 max_rows: Optional[int] = None) -> Training:
    """One training from a fresh solver, to the target, the deadline or
    ``max_rows`` rows."""
    from repro.api import Solver

    t = Training(start=time.perf_counter())
    with tr.span("solver_init"):
        solver = Solver(problem, rc)
    _instrument(solver)
    gen = solver.iterate()
    t.iter_start = time.perf_counter()
    try:
        while True:
            with tr.span("iteration"):
                row = next(gen, None)
            if row is None:
                break
            now = time.perf_counter()
            t.rows.append(row)
            t.stamps.append(now - t.start)
            if row.gap <= target:
                t.crossed = crossing_time(t.stamps,
                                          [r.gap for r in t.rows], target)
                break
            if now >= deadline or len(t.rows) == max_rows:
                t.cut = True
                break
    finally:
        gen.close()
        del gen, solver
        gc.collect()
    return t


@dataclass
class CheckRun:
    """What the program produced in the checked iterations."""

    rows: list
    first_planes_norm: float
    w_norm: float


def drive_check(problem, rc) -> CheckRun:
    """Set-up's first solver, through its first ``CHECK_ITERS``
    iterations; then its overflow program, once."""
    import jax.numpy as jnp

    from repro.api import Solver

    solver = Solver(problem, rc)
    _instrument(solver)
    eng = solver.engine
    clocks = []
    outer = eng.outer_iteration

    def keep_clock(*a, **kw):
        out = outer(*a, **kw)
        clocks.append(out[1])
        return out
    eng.outer_iteration = keep_clock
    gen = solver.iterate()
    rows, first = [], None
    try:
        for row in gen:
            rows.append(row)
            if len(rows) == 1:
                # After the first iteration slot 0 of every block holds
                # the plane its exact oracle returned.
                first = float(jnp.linalg.norm(
                    solver.state.cache.planes[:, 0]))
            if len(rows) == CHECK_ITERS:
                break
        w_norm = float(jnp.linalg.norm(solver.state.inner.phi[:-1])) \
            / rc.lam
        # The window's trainings dispatch the overflow program whenever
        # an iteration runs more than approx_batch passes; its shapes
        # are fixed by (approx_batch, n).  It donates the state, which
        # is read no more.
        rng = np.random.RandomState(rc.seed)
        perms = jnp.asarray(np.stack([
            rng.permutation(problem.n)
            for _ in range(min(rc.approx_batch, rc.max_approx_passes))]))
        _, _, stats = eng.continue_passes(solver.state, perms, clocks[-1])
        eng.read_stats(stats)
    finally:
        gen.close()
        del gen, solver
        gc.collect()
    return CheckRun(rows=rows, first_planes_norm=first, w_norm=w_norm)


def schedule(n: int, rc, approx_passes: List[int]):
    """The pass orders the solver drew, from its seeded host stream: per
    iteration one exact order and a batch of ``approx_batch`` approximate
    orders (more batches only where a batch ran out)."""
    rng = np.random.RandomState(rc.seed)
    batch = min(rc.approx_batch, rc.max_approx_passes)
    out = []
    for k in approx_passes:
        perm = rng.permutation(n)
        perms = [rng.permutation(n) for _ in range(batch)]
        while len(perms) <= k and len(perms) < rc.max_approx_passes:
            more = min(rc.approx_batch, rc.max_approx_passes - len(perms))
            perms += [rng.permutation(n) for _ in range(more)]
        out.append((perm, perms[:k]))
    return out


def reference_run(cell, host: dict, rc, approx_passes, prec=F64,
                  fault=None):
    task = cell.task.reference(host, cell.config, prec)
    return ref_mpbcfw.run(task, rc.lam, rc.cap, rc.ttl,
                          schedule(task.n, rc, approx_passes), fault=fault)


def rel(a: float, b: float) -> float:
    return abs(a - b) / abs(b) if b != 0 else float("inf")


def compare(got, ref) -> dict:
    """The numbers the check compares, for a program's :class:`CheckRun`
    or a reference run in its place: each a relative gap between what
    was produced and the reference, the worst over the iterations."""
    return {
        "dual": max(rel(r.dual, q.dual) for r, q in zip(got.rows, ref.rows)),
        "primal": max(rel(r.primal, q.primal)
                      for r, q in zip(got.rows, ref.rows)),
        "oracle_planes": rel(got.first_planes_norm, ref.first_planes_norm),
        "w_change": rel(got.w_norm, ref.w_norm),
    }


def run(cell, seed: int, seconds: float, trace: bool, clock0: float,
        devices, counter, trace_dir) -> Outcome:
    import jax

    from repro.api import build_problem

    cfg, traffic = cell.config, cell.traffic
    target = float(traffic["gap_target"])
    rc = run_config(cell, seed, int(traffic["max_iters"]))
    with tr.span("make_data"):
        data = cell.task.make_data(cfg, dev.seed_key(seed))
        jax.block_until_ready(data)
    problem = build_problem(cell.task.spec(cfg), data)
    check = drive_check(problem, rc)

    counter.armed = True
    t_start = time.perf_counter()
    setup_s = t_start - clock0
    deadline = t_start + seconds
    trainings: List[Training] = []
    while time.perf_counter() < deadline:
        rc_k = dataclasses.replace(rc, seed=training_seed(rc.seed,
                                                          len(trainings) + 1))
        trainings.append(one_training(problem, rc_k, target, deadline))
    t_end = time.perf_counter()
    counter.armed = False
    device = dev.describe(devices)
    trace_data, trace_window_s = None, None
    if trace:
        capture = tr.Capture(trace_dir)
        rc_k = dataclasses.replace(rc, seed=training_seed(rc.seed, 0x7FFF))
        t0 = time.perf_counter()
        capture.start()
        one_training(problem, rc_k, target, float("inf"),
                     max_rows=TRACE_ROWS)
        trace_window_s = time.perf_counter() - t0
        trace_data = capture.stop()

    done = [t for t in trainings if not t.cut]
    crossed = [t.crossed for t in done if t.crossed is not None]
    e2e = {"setup_s": setup_s}
    if crossed:
        e2e["train_s"] = float(np.mean(crossed))
    lengths = np.asarray(jax.device_get(data["mask"])).sum(axis=1)
    mean_len = float(lengths.mean())
    # Free the program's data on the device before the reference runs.
    host = {k: np.asarray(v) for k, v in jax.device_get(data).items()}
    del data, problem
    gc.collect()

    t_ref = time.perf_counter()
    ref = reference_run(cell, host, rc,
                        [r.approx_passes for r in check.rows])
    ref_s = time.perf_counter() - t_ref
    values = compare(check, ref)
    compared = judge(values, cell.limits.get("limits", {}))
    ctx = {
        "kind": "train", "config": cfg, "traffic": traffic,
        "trainings": trainings,
        "window_s": t_end - t_start,
        "task_dim": cell.task.dim(cfg),
        "oracle_ops": cell.task.oracle_ops(cfg, mean_len), "device": device,
        "trace": trace_data,
        "trace_window_s": trace_window_s,
    }
    notes = [f"reference_s {ref_s!r} window_s {t_end - t_start!r}",
             counter.note(),
             f"trainings_in_window {len(trainings)} crossed {len(crossed)} "
             f"cut {len(trainings) - len(done)}",
             "train_s_each " + " ".join(repr(c) for c in crossed),
             "check_rows " + " ".join(
                 f"(dual {r.dual!r} primal {r.primal!r} gap {r.gap!r} "
                 f"approx_passes {r.approx_passes})" for r in check.rows),
             "reference_rows " + " ".join(
                 f"(dual {r.dual!r} primal {r.primal!r})" for r in ref.rows)]
    for t in trainings[:1]:
        notes.append("first_training_rows " + " ".join(
            f"({s!r} {r.gap!r} {r.approx_passes})"
            for s, r in zip(t.stamps, t.rows)))
    return Outcome(attempted=len(done),
                   failed=sum(t.crossed is None for t in done),
                   end_to_end=e2e, ctx=ctx, compared=compared, notes=notes,
                   trace=trace_data, trace_window_s=trace_window_s)
