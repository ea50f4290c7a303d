"""RunRecorder — structured span/event/row persistence for one run.

A :class:`RunRecorder` is installed as a :class:`repro.api.Solver`
callback (``Solver(..., recorder=RunRecorder(path))``).  Per outer
iteration it receives the finished :class:`~repro.api.config.TraceRow` —
host scalars the control loop already paid one sync for — and appends:

  * the row itself (plus cumulative collective count/bytes off the
    engine's :class:`~repro.core.selection.SyncLedger`),
  * an ``outer_iteration`` span on the run clock,
  * ``cache_evict`` / ``collectives`` events when they carry signal.

The recorder observes only: attaching one changes nothing the Solver
computes.  Where the time of an iteration goes (eviction, the exact
pass, the oracle, the approximate passes, the evaluation) is measured
by the profiler from :mod:`repro.obs.spans`, not split up here.

Everything is written through :func:`repro.obs.schema.sanitize`, so the
file is strict JSONL (NaN/Inf become null).  The recorder never touches
device values: it adds zero host syncs, zero dispatches, and zero host
callbacks to the traced programs — the contract ``repro.analysis``
re-proves statically and ``tests/test_obs.py`` asserts off the ledger.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import time
from typing import Optional

from .metrics import MetricsRegistry
from .schema import SCHEMA_VERSION, sanitize


class RunRecorder:
    """JSONL run recorder + metrics registry owner (one file per run)."""

    def __init__(self, path, *, registry: Optional[MetricsRegistry] = None):
        self.path = str(path)
        self.registry = registry if registry is not None else \
            MetricsRegistry()
        self._fh = open(self.path, "w", encoding="utf-8")
        self._wall0 = time.perf_counter()
        self._closed = False
        self._prev_time = 0.0
        self._led_prev = None  # (collectives, collective_bytes) snapshot

    # -- plumbing -----------------------------------------------------------

    def _write(self, record: dict) -> None:
        if self._closed:
            return
        self._fh.write(json.dumps(sanitize(record),
                                  separators=(",", ":")) + "\n")
        self._fh.flush()

    def _host_now(self) -> float:
        return time.perf_counter() - self._wall0

    # -- lifecycle ----------------------------------------------------------

    def open_run(self, solver) -> None:
        """First record: run metadata + the engine's declared budgets
        (what the CLI later checks the measured ledger against).
        Called by the Solver when the recorder is installed."""
        caps = getattr(solver, "caps", None)
        budgets = {}
        if caps is not None:
            budgets = {
                "collectives_per_pass": caps.collectives_per_pass,
                "collectives_setup": caps.collectives_setup,
                "host_callbacks": caps.host_callbacks,
                "multipass": caps.multipass,
            }
        self._write({
            "type": "meta", "schema": SCHEMA_VERSION,
            "algo": solver.cfg.algo,
            "n": int(solver.problem.n), "d": int(solver.problem.d),
            "time_mode": ("cost_model" if solver.cfg.cost_model is not None
                          else "wall"),
            "engine_budgets": budgets,
        })

    def open_custom(self, *, algo: str, n: int, d: int,
                    time_mode: str = "wall",
                    engine_budgets: Optional[dict] = None,
                    **extra) -> None:
        """Write a schema-valid meta record for a non-Solver run.

        Other subsystems that reuse the run-trace format (e.g. the
        serving loop in :mod:`repro.serve.batcher`) open their file with
        this instead of :meth:`open_run` — same required fields, caller
        supplies the values (``algo`` names the workload, e.g.
        ``"serve:chain"``)."""
        self._write(dict(extra, type="meta", schema=SCHEMA_VERSION,
                         algo=algo, n=int(n), d=int(d),
                         time_mode=time_mode,
                         engine_budgets=dict(engine_budgets or {})))

    def close(self) -> None:
        """Write the summary record (final metrics snapshot) and close."""
        if self._closed:
            return
        self._write({"type": "summary",
                     "metrics": self.registry.snapshot()})
        self._closed = True
        self._fh.close()

    def __enter__(self) -> "RunRecorder":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- the Solver callback ------------------------------------------------

    def __call__(self, solver, row) -> None:
        """Record one finished outer iteration (host scalars only)."""
        ledger = getattr(solver.engine, "ledger", None)
        coll = int(getattr(ledger, "collectives", 0))
        nbytes = int(getattr(ledger, "collective_bytes", 0))
        if self._led_prev is None:
            d_coll, d_bytes = coll, nbytes
        else:
            d_coll = coll - self._led_prev[0]
            d_bytes = nbytes - self._led_prev[1]
        self._led_prev = (coll, nbytes)

        self.registry.observe_row(row, collectives=d_coll,
                                  collective_bytes=d_bytes)
        rec = dict(dataclasses.asdict(row), type="row",
                   collectives=coll, collective_bytes=nbytes)
        self._write(rec)

        t0, t1 = self._prev_time, float(row.time)
        self._prev_time = t1
        it = int(row.iteration)
        self.span_record("outer_iteration", t0, t1, iteration=it)
        evicted = int(getattr(row, "planes_evicted", 0))
        if evicted > 0:
            self.event("cache_evict", t=t0, iteration=it, count=evicted)
        if d_coll > 0:
            self.event("collectives", t=t1, iteration=it, count=d_coll,
                       bytes=d_bytes)

    # -- spans / events (host-side phases) ----------------------------------

    def span_record(self, name: str, t0: float, t1: float,
                    timebase: str = "run", **attrs) -> None:
        self._write(dict(attrs, type="span", name=name,
                         t0=float(t0), t1=float(t1), timebase=timebase))

    def event(self, name: str, t: Optional[float] = None, **attrs) -> None:
        self._write(dict(attrs, type="event", name=name,
                         t=float(t if t is not None else self._host_now())))

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        """Time a host-side phase (checkpoint save/restore) on the
        recorder's wall clock."""
        t0 = self._host_now()
        try:
            yield
        finally:
            self.span_record(name, t0, self._host_now(), timebase="host",
                             **attrs)
