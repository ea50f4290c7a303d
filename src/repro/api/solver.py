"""The :class:`Solver` facade — the engine-generic SSVM control loop.

This is the piece of the paper that is inherently an *online control
loop*: everything it schedules is a compiled JAX program owned by an
:class:`~repro.api.engine.Engine` resolved from the registry by
``RunConfig.algo``.  The loop itself only draws permutations, reads
telemetry, keeps the books, and yields one
:class:`~repro.api.config.TraceRow` per outer iteration through the
streaming :meth:`Solver.iterate` generator.

Sync accounting (multipass engines): exactly **one program dispatch and
one host sync per outer iteration** (more only if an iteration's
approximate passes overflow ``approx_batch``), counted honestly through
:class:`repro.core.selection.SyncLedger` and reported per iteration in
``TraceRow.host_syncs`` / ``TraceRow.dispatches``.  The returned
per-pass telemetry is replayed into the host-side
:class:`~repro.core.selection.IterationTracker`:

  * wall clock (production): the measured iteration time is attributed
    across the batch pro-rata by modeled pass cost, which also
    calibrates the per-plane cost estimate the device rule uses next
    iteration;
  * :class:`repro.core.selection.CostModel` (simulation/CI): a virtual
    clock driven by #oracle-calls and #cached-planes replays the
    per-pass plane counts exactly, reproducing the paper's
    USPS/OCR/HorseSeg regimes deterministically on any host.

Evaluation (:func:`evaluate_objectives`: primal/dual/gap, n — 2n with
averaging — extra oracle calls per iteration) is telemetry, **not** part
of the control loop: its wall time is measured and subtracted from every
clock reading (``_Clock.exclude``), reported in ``TraceRow.eval_s``, and
its device fetch is not charged to the ledger.  It is one jitted program,
cached per oracle, ``n``, ``lam`` and whether averaging is on, whose
three objectives come back in one fetch: after the first call it
dispatches once and compiles nothing.

Tracing (:mod:`repro.obs.spans`) is always on and observes only: each
outer iteration is a profiler step ``repro:iteration`` holding
``repro:dispatch`` (the engine's program dispatches), ``repro:sync``
(``read_stats``) and ``repro:evaluate`` spans, and ``TraceRow.compiles``
counts the executables JAX made during the iteration.

Stopping is pluggable (:mod:`repro.api.stopping`): ``max_iters``, an
optional wall/virtual-time budget, and an optional duality-gap tolerance
come from the config; extra criteria and per-iteration callbacks are
constructor arguments.  Warm start / resume goes through
:class:`repro.checkpoint.manager.CheckpointManager` (:meth:`Solver.save`
/ :meth:`Solver.restore`): under a CostModel a resumed run is bit-for-bit
the uninterrupted one.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from contextlib import contextmanager, nullcontext
from types import SimpleNamespace
from typing import Callable, Iterable, Iterator, List, Optional, TYPE_CHECKING

import jax
import jax.numpy as jnp
import numpy as np

from ..checkpoint.manager import CheckpointManager
from ..core.selection import (CostModel, IterationTracker,
                              attribute_wall_time)
from ..obs.metrics import MetricsRegistry

if TYPE_CHECKING:  # annotation only
    from ..obs.recorder import RunRecorder
from ..core.ssvm import dual_value, primal_value, weights_of
from ..core.averaging import extract as extract_average
from ..obs import spans
from ..core.types import SSVMProblem
from .config import RunConfig, RunResult, TraceRow
from .engine import Engine, engine_entry, validate_config
from .stopping import (MaxIters, StopContext, StopOnGap, StoppingCriterion,
                       WallTimeBudget)

Callback = Callable[["Solver", TraceRow], None]


class _Clock:
    """Wall/virtual time source honoring the "evaluation is not timed"
    contract: durations measured inside :meth:`exclude` are subtracted
    from every reading, so ``TraceRow.time`` never includes the
    n-oracle-call evaluation sweeps.  A :class:`CostModel` clock is
    immune by construction (it only advances through explicit charges)."""

    def __init__(self, cost_model: Optional[CostModel]):
        self.cm = cost_model
        self._wall0 = time.perf_counter()
        self._excluded = 0.0
        self._started = False

    def start(self) -> None:
        """Anchor the wall clock at the first call (no-op afterwards, and
        for CostModel clocks).  The solver calls this when iteration
        begins, so setup time between constructing a Solver and running
        it is never charged to trace rows or the time budget."""
        if not self._started:
            self._started = True
            self._wall0 = time.perf_counter()
            self._excluded = 0.0

    def _wall(self) -> float:
        return time.perf_counter() - self._wall0 - self._excluded

    @contextmanager
    def exclude(self):
        """Context whose wall time never reaches trace rows; on exit the
        object it yields holds that time in ``seconds``."""
        out = SimpleNamespace(seconds=0.0)
        t0 = time.perf_counter()
        try:
            yield out
        finally:
            out.seconds = time.perf_counter() - t0
            self._excluded += out.seconds

    def exact(self, n_calls: int) -> float:
        if self.cm is not None:
            return self.cm.exact_pass(n_calls)
        return self._wall()

    def approx(self, total_planes: int) -> float:
        if self.cm is not None:
            return self.cm.approx_pass(total_planes)
        return self._wall()

    def now(self) -> float:
        if self.cm is not None:
            return self.cm.now
        return self._wall()


@functools.partial(jax.jit, static_argnums=(0, 1),
                   static_argnames=("lam", "certified"))
def _objectives_program(oracle, n, data, x, avg, *, lam: float,
                        certified: bool = True) -> jnp.ndarray:
    """``[primal, dual, primal at the average]`` as one float32 ``(3,)``.

    ``x`` is the dual vector ``phi``; with ``certified=False`` it is a raw
    weight vector (SSG), whose dual is NaN.  ``avg=None`` (averaging
    off) and an :class:`~repro.core.types.AveragingState` are two
    pytree structures, hence two cached traces of this one program.
    """
    if certified:
        w, dual = weights_of(x, lam), dual_value(x, lam)
    else:
        w, dual = x, jnp.full((), jnp.nan, jnp.float32)
    prob = SSVMProblem(n=n, d=w.shape[0], data=data, oracle=oracle)
    primal = primal_value(prob, w, lam)
    primal_avg = primal if avg is None else primal_value(
        prob, weights_of(extract_average(avg, lam), lam), lam)
    return jnp.stack([primal, dual, primal_avg])


def evaluate_objectives(problem: SSVMProblem, phi, avg, lam: float):
    """Primal/dual/gap (+ primal at the averaged iterate).  Not timed:
    callers wrap this in ``clock.exclude()``."""
    out = _objectives_program(problem.oracle, problem.n, problem.data, phi,
                              avg, lam=lam)
    return tuple(float(v) for v in jax.device_get(out))  # the one fetch


def ssg_primal(problem: SSVMProblem, w, lam: float) -> float:
    """Primal objective at a raw weight vector (no dual certificate)."""
    out = _objectives_program(problem.oracle, problem.n, problem.data, w,
                              None, lam=lam, certified=False)
    return float(jax.device_get(out)[0])


def _fit_pass_costs(xs: List[float], ys: List[float]):
    """Least-squares fit of iteration time ~ exact_cost + plane_cost * x.

    ``x`` is the iteration's total approximate plane-steps.  Returns
    ``(exact_cost, plane_cost)`` when the recent window identifies both
    terms (>= 2 distinct x values, positive coefficients), else ``None``.
    """
    if len(xs) < 2:
        return None
    x = np.asarray(xs[-8:], np.float64)
    y = np.asarray(ys[-8:], np.float64)
    var = float(np.var(x))
    if var <= 0.0:
        return None
    b = float(np.mean((x - x.mean()) * (y - y.mean()))) / var
    a = float(y.mean() - b * x.mean())
    if a <= 0.0 or b <= 0.0:
        return None
    return a, b


def _draw_perms(rng, n: int, k: int) -> jnp.ndarray:
    if k == 0:
        return jnp.zeros((0, n), jnp.int32)
    return jnp.asarray(np.stack([rng.permutation(n) for _ in range(k)]))


def _rng_state_to_json(rng: np.random.RandomState) -> list:
    name, keys, pos, has_gauss, cached = rng.get_state()
    return [name, [int(x) for x in keys], int(pos), int(has_gauss),
            float(cached)]


def _rng_state_from_json(state: list):
    name, keys, pos, has_gauss, cached = state
    return (name, np.asarray(keys, np.uint32), int(pos), int(has_gauss),
            float(cached))


class Solver:
    """Engine-generic SSVM training facade.

    ``Solver(problem, cfg)`` resolves ``cfg.algo`` through the engine
    registry, validates the config against the engine's capabilities
    (typed :class:`~repro.api.errors.UnsupportedConfigError` on any
    mismatch), and exposes:

      * :meth:`iterate` — a streaming generator of ``TraceRow``s (the
        control loop; stops when a stopping criterion fires);
      * :meth:`run` — drain :meth:`iterate` and return a
        :class:`~repro.api.config.RunResult`;
      * :meth:`save` / :meth:`restore` — checkpoint & bit-for-bit resume
        through :class:`repro.checkpoint.manager.CheckpointManager`.
    """

    def __init__(self, problem: SSVMProblem, cfg: RunConfig, *,
                 stop: Iterable[StoppingCriterion] = (),
                 callbacks: Iterable[Callback] = (),
                 checkpoint: Optional[CheckpointManager] = None,
                 checkpoint_every: int = 0,
                 recorder: Optional["RunRecorder"] = None):
        entry = engine_entry(cfg.algo)
        validate_config(entry, cfg)
        self.problem = problem
        self.cfg = cfg
        self.engine: Engine = entry.factory(problem, cfg)
        self.caps = entry.capabilities
        self.callbacks = list(callbacks)
        self.checkpoint = checkpoint
        self.checkpoint_every = int(checkpoint_every)
        # Observability: the recorder (when installed) runs as an ordinary
        # row callback and owns the metrics registry; without one the
        # Solver still keeps a registry so checkpoints always carry the
        # metric series.  Neither path adds host syncs, dispatches, or
        # host callbacks to the traced programs — the device-side
        # counters ride the existing per-iteration stats sync.
        self.recorder = recorder
        if recorder is not None:
            self.metrics: MetricsRegistry = recorder.registry
            self.callbacks.append(recorder)
            recorder.open_run(self)
        else:
            self.metrics = MetricsRegistry()
        self.stop_criteria: List[StoppingCriterion] = [
            MaxIters(cfg.max_iters)]
        if cfg.gap_tol is not None:
            self.stop_criteria.append(StopOnGap(cfg.gap_tol))
        if cfg.time_budget is not None:
            self.stop_criteria.append(WallTimeBudget(cfg.time_budget))
        self.stop_criteria.extend(stop)

        self._rng = np.random.RandomState(cfg.seed)
        self._clock = _Clock(cfg.cost_model)
        self._state = self.engine.init_state(cfg.cap)
        self._it = 0
        self._last_row: Optional[TraceRow] = None
        self.trace: List[TraceRow] = []
        # Per-pass cost constants for the on-device slope rule.  CostModel
        # mode uses the model's exact constants (so the device decisions
        # match a host replay verbatim); wall-clock mode starts from
        # defaults and recalibrates from measured iteration times.
        cm = cfg.cost_model
        n = problem.n
        self._est_exact = cm.oracle_cost * n if cm is not None else 1.0
        self._est_plane = cm.plane_cost if cm is not None else 1e-3
        self._wall_x: List[float] = []  # plane-steps per iter (regressor)
        self._wall_y: List[float] = []  # measured iteration seconds
        spans.compile_count()  # the listener counts from here on

    # -- state / results ----------------------------------------------------

    @property
    def state(self):
        """The engine's current optimizer state (device pytree)."""
        return self._state

    @property
    def iteration(self) -> int:
        """Index of the next outer iteration to run."""
        return self._it

    def result(self) -> RunResult:
        """Trace so far + final weights extracted from the live state."""
        w, w_avg = self.engine.extract(self._state)
        return RunResult(trace=list(self.trace), w=w, w_avg=w_avg)

    def run(self) -> RunResult:
        """Drain :meth:`iterate` and return the full result."""
        for _ in self.iterate():
            pass
        return self.result()

    # -- the control loop ---------------------------------------------------

    def _should_stop(self) -> bool:
        ctx = StopContext(iteration=self._it, last_row=self._last_row,
                          elapsed=self._clock.now())
        return any(c.should_stop(ctx) for c in self.stop_criteria)

    def iterate(self) -> Iterator[TraceRow]:
        """Run outer iterations, yielding one ``TraceRow`` each, until a
        stopping criterion fires.  Resumable: iterating again (or after
        :meth:`restore`) continues from the current state."""
        self._clock.start()
        inner = (self._iterate_multipass() if self.caps.multipass
                 else self._iterate_simple())
        ledger = getattr(self.engine, "ledger", None)
        while not self._should_stop():
            coll0 = getattr(ledger, "collectives", 0)
            bytes0 = getattr(ledger, "collective_bytes", 0)
            n_exact0 = (self._last_row.n_exact
                        if self._last_row is not None else 0)
            with spans.step(spans.ITERATION, self._it) as step:
                row = next(inner)
                # The iteration's counts ride its profiler step, so a
                # trace reader can put device time per call and pass.
                step.set_metadata(exact_calls=row.n_exact - n_exact0,
                                  approx_passes=row.approx_passes)
            self.trace.append(row)
            self._last_row = row
            self._it += 1
            if self.recorder is None:
                # With a recorder the registry update happens in its row
                # callback (it also diffs the ledger); avoid double counts.
                self.metrics.observe_row(
                    row,
                    collectives=getattr(ledger, "collectives", 0) - coll0,
                    collective_bytes=getattr(ledger, "collective_bytes",
                                             0) - bytes0)
            for cb in self.callbacks:
                cb(self, row)
            if (self.checkpoint is not None and self.checkpoint_every > 0
                    and self._it % self.checkpoint_every == 0):
                with self._clock.exclude():
                    self.save(self.checkpoint)
            yield row

    def _evaluate(self, state, compiles0: int):
        """``engine.evaluate`` outside the clock, under its span.  Returns
        the objectives and the row's host columns: ``eval_s``, the
        seconds the clock left out for the call, and ``compiles``, the
        executables made since ``compiles0``.  Under a CostModel the row
        holds only what the virtual clock makes deterministic, so both
        stay 0."""
        with self._clock.exclude() as excluded, spans.span(spans.EVALUATE):
            objectives = self.engine.evaluate(state)
        if self._clock.cm is not None:
            return objectives, {}
        return objectives, dict(eval_s=excluded.seconds,
                                compiles=spans.compile_count() - compiles0)

    def _iterate_simple(self) -> Iterator[TraceRow]:
        """One fused program per outer iteration, no approximate phase
        (fw / ssg / bcfw and any registered non-multipass engine)."""
        engine, cfg, clock = self.engine, self.cfg, self._clock
        n = self.problem.n
        while True:
            it = self._it
            compiles0 = spans.compile_count()
            led0 = engine.ledger.counts()
            perm = (jnp.asarray(self._rng.permutation(n))
                    if self.caps.needs_perm else None)
            with spans.span(spans.DISPATCH):
                self._state, _, stats = engine.outer_iteration(
                    self._state, perm, None, None, ttl=cfg.ttl)
            with spans.span(spans.SYNC):
                st = engine.read_stats(stats)  # the iteration's single sync
            t = clock.exact(n)
            (primal, dual, primal_avg), host = self._evaluate(self._state,
                                                              compiles0)
            led1 = engine.ledger.counts()
            yield TraceRow(it, int(st.n_exact), int(st.n_approx), t,
                           primal, dual, primal - dual, primal_avg,
                           0.0, 0, led1[0] - led0[0], led1[2] - led0[2],
                           **host)

    def _iterate_multipass(self) -> Iterator[TraceRow]:
        """The MP-BCFW control loop, generic over the execution engine.

        Per outer iteration the loop dispatches one fused program and
        blocks exactly once on its telemetry; extra (dispatch, sync)
        pairs occur only when the slope rule wants more than
        ``approx_batch`` passes.
        """
        from ..core import mpbcfw

        problem, cfg, engine, clock = (self.problem, self.cfg, self.engine,
                                       self._clock)
        n, lam = problem.n, cfg.lam
        cm = cfg.cost_model
        rng = self._rng
        tracker = IterationTracker()
        f_end = float(dual_value(self._state.inner.phi, lam))
        while True:
            it = self._it
            compiles0 = spans.compile_count()
            mp = self._state
            led0 = engine.ledger.counts()
            # Async engines accumulate modeled oracle-overlap time on the
            # ledger (outside counts()); per-iteration deltas become the
            # TraceRow.oracle_overlap column.  getattr: serial engines'
            # ledgers simply never grow the fields.
            ovl0 = (getattr(engine.ledger, "oracle_time_total", 0.0),
                    getattr(engine.ledger, "oracle_time_hidden", 0.0))
            f_start = f_end     # TTL eviction does not change phi, hence F
            t0 = clock.now()
            tracker.start(t0, f_start)

            plane_cost = cm.plane_cost if cm is not None else self._est_plane
            # Device times are relative to the iteration start (t0 = 0):
            # the slope rule is shift-invariant, and absolute virtual times
            # would outgrow float32 resolution on long runs
            # (t + plane_cost == t).  f0 here is a host-side seed only —
            # the fused program re-seeds it from the on-device dual at
            # iteration entry (bitwise the same value, with no host sync
            # needed to obtain it).
            clock_dev = mpbcfw.make_slope_clock(0.0, f_start,
                                                self._est_exact, plane_cost)
            perm = jnp.asarray(rng.permutation(n))
            # Permutations for passes the device rule skips are drawn but
            # unused, so the schedule is deterministic per (seed,
            # approx_batch); approx_batch=1 reproduces the unbatched
            # loop's RNG stream exactly.
            perms = _draw_perms(rng, n, min(cfg.approx_batch,
                                            cfg.max_approx_passes))
            # Keyed sampling policies (caps.needs_key) get one fresh PRNG
            # key per iteration, drawn from the solver's seeded host RNG
            # stream (checkpointed with it, so resume is bit-for-bit).
            # PRNGKey construction is host-side bookkeeping: no device
            # sync, and engines without the capability keep their exact
            # pre-policy call signature and RNG stream.
            key_kw = ({"key": jax.random.PRNGKey(
                int(rng.randint(0, 2 ** 31 - 1)))}
                if self.caps.needs_key else {})
            with spans.span(spans.DISPATCH):
                mp, clock_dev, stats = engine.outer_iteration(
                    mp, perm, perms, clock_dev, ttl=cfg.ttl, **key_kw)
            # Engines may donate the state they are given (FusedEngine
            # does): rebind at once, so nothing reads a donated buffer.
            self._state = mp
            with spans.span(spans.SYNC):
                st = engine.read_stats(stats)  # the iteration's single sync
            # Device-accumulated obs counters arrive on the same sync.
            # Capture them from the *outer* program's stats: overflow
            # continuations never insert/evict, so their metrics carry
            # zero evictions and the same occupancy.  Third-party stats
            # payloads without the field report defaults.
            met = getattr(st, "metrics", None)
            f_exact = float(st.f_entry)
            ws_total = int(st.ws_total)
            k = int(st.passes_run)
            duals_all = [float(x) for x in st.duals[:k]]
            planes_all = [int(x) for x in st.planes[:k]]
            while bool(st.more) and len(duals_all) < cfg.max_approx_passes:
                batch = min(cfg.approx_batch,
                            cfg.max_approx_passes - len(duals_all))
                perms = _draw_perms(rng, n, batch)
                with spans.span(spans.DISPATCH):
                    mp, clock_dev, stats = engine.continue_passes(
                        mp, perms, clock_dev)
                self._state = mp
                with spans.span(spans.SYNC):
                    st = engine.read_stats(stats)
                k = int(st.passes_run)
                duals_all += [float(x) for x in st.duals[:k]]
                planes_all += [int(x) for x in st.planes[:k]]
            led1 = engine.ledger.counts()
            ovl_total = (getattr(engine.ledger, "oracle_time_total", 0.0)
                         - ovl0[0])
            ovl_hidden = (getattr(engine.ledger, "oracle_time_hidden", 0.0)
                          - ovl0[1])
            oracle_overlap = (ovl_hidden / ovl_total if ovl_total > 0
                              else 0.0)

            # Replay the device-chosen pass schedule through the host
            # clock (the tracker mirrors what the device rule saw —
            # telemetry and validation; the continue decisions themselves
            # happened on device).
            if cm is not None:
                # Sampled schedules run fewer exact-oracle calls than n;
                # charge the virtual clock what the device actually did.
                gs_met = (getattr(met, "gap_sampled", None)
                          if met is not None else None)
                tracker.record(
                    clock.exact(n if gs_met is None else int(gs_met)),
                    f_exact)
                for dv, n_planes in zip(duals_all, planes_all):
                    tracker.record(clock.approx(n_planes), dv)
                # Pipelined engines: the oracle and cache programs ran
                # concurrently, so the modeled iteration time is
                # max(oracle, cache), not their sum — credit back the
                # overlap the engine reported (hidden <= the exact charge
                # above, so the virtual clock stays monotone).  Purely
                # deterministic, hence checkpoint/resume stays
                # bit-for-bit.
                if ovl_hidden > 0.0:
                    cm.now -= ovl_hidden
            else:
                elapsed = clock.now() - t0
                weights = [self._est_exact] + [self._est_plane * max(p, 1)
                                               for p in planes_all]
                durs = attribute_wall_time(elapsed, weights)
                ts, t_cursor = [], t0
                for dur in durs:
                    t_cursor += dur
                    ts.append(t_cursor)
                tracker.record(ts[0], f_exact)
                tracker.record_batch(ts[1:], duals_all)
                # Calibrate the device rule's cost constants: regress
                # elapsed ~ a + b*plane_steps across iterations, falling
                # back to the pro-rata split until the window identifies
                # both terms.  A recorder, attached or not, changes
                # nothing here.  The constants persist through the
                # checkpoint manifest's ``extra["calibration"]``.
                self._wall_x.append(float(sum(max(p, 1)
                                              for p in planes_all)))
                self._wall_y.append(float(elapsed))
                fit = _fit_pass_costs(self._wall_x, self._wall_y)
                if fit is not None:
                    self._est_exact, self._est_plane = fit
                else:
                    self._est_exact = max(durs[0], 1e-9)
                    if planes_all:
                        tot = sum(max(p, 1) for p in planes_all)
                        self._est_plane = max(sum(durs[1:]) / tot, 1e-12)

            n_approx_passes = len(duals_all)
            # One statistic in both branches (Fig. 5): the mean working-
            # set size over the iteration's passes, straight from the
            # synced telemetry — no extra device fetch.  Approximate
            # passes never insert or evict planes, so every pass of the
            # iteration sees the post-exact-pass sets and the per-pass
            # mean is exactly ws_total/n.
            ws_mean = ws_total / n
            if met is not None:
                hit_rate = int(met.nonempty_blocks) / n
                evicted = int(met.ttl_evicted) + int(met.lru_evicted)
            else:
                hit_rate, evicted = 0.0, 0
            # Gap-policy columns ride the same sync; engines without a
            # gap vector report the TraceRow defaults.
            gap_kw = {}
            gt = getattr(met, "gap_total", None) if met is not None else None
            if gt is not None:
                gs = getattr(met, "gap_sampled", None)
                gap_kw = dict(gap_total=float(gt),
                              gap_sampled=int(gs) if gs is not None else 0)
            (primal, dual, primal_avg), host = self._evaluate(mp,
                                                              compiles0)
            f_end = dual
            yield TraceRow(
                it, int(mp.inner.n_exact), int(mp.inner.n_approx),
                clock.now(), primal, dual, primal - dual, primal_avg,
                ws_mean, n_approx_passes,
                led1[0] - led0[0], led1[2] - led0[2],
                cache_hit_rate=hit_rate, planes_evicted=evicted,
                oracle_overlap=oracle_overlap, **gap_kw, **host)

    # -- serving export -----------------------------------------------------

    def servable(self, *, averaged: bool = False,
                 meta: Optional[dict] = None):
        """Export the current weights as a
        :class:`repro.serve.ServableModel` (requires the problem to have
        been built from an :class:`~repro.api.oracle.OracleSpec`).  Lazy
        import keeps training-only processes free of the serving layer.
        """
        from ..serve.export import ServableModel

        return ServableModel.from_solver(self, averaged=averaged,
                                         meta=meta)

    # -- checkpoint / resume ------------------------------------------------

    def save(self, manager: Optional[CheckpointManager] = None,
             step: Optional[int] = None) -> int:
        """Checkpoint the optimizer state + host control-loop state.

        Returns the step saved under (default: the current iteration).
        The manifest carries the CostModel/wall calibration constants
        explicitly (``extra["calibration"]``) and the metrics-registry
        snapshot (top-level ``metrics``), so a resumed run continues both
        the device rule's cost estimates and its metric series exactly.
        """
        manager = manager or self.checkpoint
        if manager is None:
            raise ValueError("no CheckpointManager: pass one to save() or "
                             "to the Solver constructor")
        step = self._it if step is None else int(step)
        pack = getattr(self.engine, "pack_state", None)
        tree = pack(self._state) if pack is not None else self._state

        extra = {
            "algo": self.cfg.algo,
            "iteration": self._it,
            # the previous iteration's row: stopping criteria (e.g.
            # StopOnGap) consult it before the first resumed iteration,
            # so a resumed run stops exactly where the uninterrupted one
            # would have
            "last_row": (dataclasses.asdict(self._last_row)
                         if self._last_row is not None else None),
            "rng_state": _rng_state_to_json(self._rng),
            "clock_now": self._clock.now(),
            # The cost-calibration state, first-class: the slope rule's
            # per-pass constants plus the wall-regression window that
            # produced them.  (JSON round-trips Python floats exactly —
            # repr-based — so resume is bit-for-bit in both clock modes.)
            "calibration": {
                "est_exact": self._est_exact,
                "est_plane": self._est_plane,
                "wall_x": list(self._wall_x),
                "wall_y": list(self._wall_y),
            },
            # legacy flat spellings (one release, pre-obs checkpoints)
            "est_exact": self._est_exact,
            "est_plane": self._est_plane,
            "wall_x": self._wall_x,
            "wall_y": self._wall_y,
        }
        span = (self.recorder.span("checkpoint_save", step=step)
                if self.recorder is not None else nullcontext())
        with span:
            manager.save(step, tree, extra=extra,
                         metrics=self.metrics.snapshot())
        return step

    @classmethod
    def restore(cls, problem: SSVMProblem, cfg: RunConfig,
                manager: CheckpointManager, step: Optional[int] = None,
                **solver_kwargs) -> "Solver":
        """Rebuild a solver from a checkpoint and resume mid-run.

        The restored solver continues at the saved iteration with the
        saved RNG stream and (virtual) clock; under a CostModel the
        remaining trace is bit-for-bit what the uninterrupted run would
        have produced.
        """
        solver = cls(problem, cfg, **solver_kwargs)
        span = (solver.recorder.span("checkpoint_restore")
                if solver.recorder is not None else nullcontext())
        with span:
            return cls._restore_into(solver, cfg, manager, step)

    @classmethod
    def _restore_into(cls, solver: "Solver", cfg: RunConfig,
                      manager: CheckpointManager,
                      step: Optional[int]) -> "Solver":
        # Pin the step once up front: manifest and arrays must come from
        # the same checkpoint even if another process commits a newer
        # step mid-restore.
        if step is None:
            step = manager.latest_step()
        manifest = manager.load_manifest(step)
        extra = manifest.get("extra", {})
        if extra.get("algo") not in (None, cfg.algo):
            raise ValueError(
                f"checkpoint was saved by algo={extra['algo']!r}, "
                f"cannot resume as {cfg.algo!r}")
        pack = getattr(solver.engine, "pack_state", None)
        unpack = getattr(solver.engine, "unpack_state", None)
        template = pack(solver._state) if pack is not None else solver._state
        tree, _ = manager.restore(template, step)
        solver._state = unpack(tree) if unpack is not None else tree
        solver._it = int(extra.get("iteration", manifest["step"]))
        if extra.get("last_row") is not None:
            # Only the columns TraceRow still has: a row saved by an
            # older release may hold columns since removed, and columns
            # added since take their defaults.
            known = {f.name for f in dataclasses.fields(TraceRow)}
            solver._last_row = TraceRow(**{
                k: v for k, v in extra["last_row"].items() if k in known})
        if "rng_state" in extra:
            solver._rng.set_state(_rng_state_from_json(extra["rng_state"]))
        now = float(extra.get("clock_now", 0.0))
        if solver._clock.cm is not None:
            solver._clock.cm.now = now
        else:
            # resume the elapsed wall time; mark started so the first
            # iterate() does not re-anchor over it
            solver._clock._wall0 = time.perf_counter() - now
            solver._clock._excluded = 0.0
            solver._clock._started = True
        # Calibration constants: the explicit manifest entry is the
        # source of truth; pre-obs checkpoints fall back to the legacy
        # flat keys.  No casting games — JSON floats restore bit-for-bit.
        cal = extra.get("calibration") or {
            "est_exact": extra.get("est_exact", solver._est_exact),
            "est_plane": extra.get("est_plane", solver._est_plane),
            "wall_x": extra.get("wall_x", []),
            "wall_y": extra.get("wall_y", []),
        }
        solver._est_exact = float(cal["est_exact"])
        solver._est_plane = float(cal["est_plane"])
        solver._wall_x = [float(x) for x in cal.get("wall_x", [])]
        solver._wall_y = [float(y) for y in cal.get("wall_y", [])]
        # Continue the metric series where the checkpointed run left off.
        solver.metrics.load(manifest.get("metrics"))
        return solver
