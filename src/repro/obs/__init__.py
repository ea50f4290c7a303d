"""repro.obs — the unified observability layer.

The paper's entire argument is an accounting argument (convergence per
exact-oracle call, and per second when the oracle dominates — Fig. 4-6),
so telemetry is a first-class subsystem, not a side effect:

  * :class:`MetricsRegistry` — counters / gauges / histograms.  The
    hot-path values (cache occupancy, evictions, hit rate) accumulate
    **on device** inside the fused outer-iteration programs
    (:class:`repro.core.types.ObsMetrics` riding in
    ``ApproxBatchStats``) and drain through the *existing* single
    per-iteration host sync — the 1-dispatch + 1-host-sync contract is
    untouched, and ``repro.analysis`` re-proves it statically (rule
    J006 + the collective/host-callback budgets);
  * :mod:`repro.obs.spans` — the program's tracing, always on and
    observe-only: ``repro:*`` host spans (``TraceAnnotation``) in the
    Solver loop and the serving round, device scopes
    (``jax.named_scope``) in the shared pass functions, and a
    process-wide compile counter.  They write to the profiler's own
    trace (``jax.profiler.trace``), on the device's clock;
  * :class:`RunRecorder` — rows, spans and events (outer iteration,
    eviction, checkpoint save/restore, collective totals) written as
    JSONL.  A :class:`repro.api.Solver` installs it as a callback
    (``Solver(..., recorder=RunRecorder(path))``); it observes only;
  * the CLI — ``python -m repro.obs run.jsonl`` summarizes a run
    (oracle calls to target gap, cache hit/evict rates, sync and
    collective budgets vs the engine's declared
    :class:`~repro.api.engine.EngineCapabilities`, evaluation and
    checkpoint time) and ``--diff`` compares two runs for regressions.
"""
from .metrics import Counter, Gauge, Histogram, MetricsRegistry  # noqa: F401
from .recorder import RunRecorder  # noqa: F401
from .schema import SCHEMA_VERSION, validate_file, validate_record  # noqa: F401
from .summary import (diff_runs, load_run, summarize,  # noqa: F401
                      summarize_run)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "RunRecorder",
    "SCHEMA_VERSION", "validate_record", "validate_file",
    "load_run", "summarize", "summarize_run", "diff_runs",
]
