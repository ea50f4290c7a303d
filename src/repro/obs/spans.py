"""The program's spans, device scopes and compile counter.

Everything here writes to the profiler's own trace, so host spans and
device operations share one clock: an idle gap on the device lies
against what the host was doing at the time.  Nothing is switched on or
off.  With no profiler session open, a :func:`span` costs well under a
microsecond and a :func:`scope` only adds HLO op metadata, so the
instrumentation stays in the hot path and changes no number.

* :func:`span` — a host span named ``"repro:" + name``
  (``jax.profiler.TraceAnnotation``); :func:`step` is the same as a
  profiler step (``StepTraceAnnotation``).
* :func:`scope` — a ``jax.named_scope`` around traced code: every HLO
  operation made inside carries the name in its ``op_name`` metadata,
  which is how the device trace tells eviction, the exact pass, the
  oracle and the approximate passes apart.
* :func:`compile_count` — the executables JAX has made in this process
  (compiled, or loaded from the persistent cache), from one
  process-wide ``jax.monitoring`` listener.

The names are the constants below; readers of a trace import them
rather than retype the strings.
"""
from __future__ import annotations

import jax

PREFIX = "repro:"

# Host spans of the Solver's control loop (api/solver.py).
ITERATION = "iteration"   # one outer iteration (a profiler step)
DISPATCH = "dispatch"     # engine.outer_iteration / continue_passes
SYNC = "sync"             # engine.read_stats, and the serving round's fetch
EVALUATE = "evaluate"     # engine.evaluate (primal, dual, gap)

# Host spans of one serving round (serve/batcher.py StructuredServer.step).
ROUND = "round"           # the whole step; metadata bucket=, batch=
PICK = "pick"             # bucket choice and dequeue
PAD = "pad"               # pad rows and filler rows
STACK = "stack"           # engine.stack, the host-to-device transfer
DECODE = "decode"         # the round's one dispatch
ANSWER = "answer"         # unpad, metrics, recorder

# Device scopes (HLO op_name metadata) of the shared pass functions.
EVICT = "evict"                 # core/mpbcfw.begin_iteration
EXACT_PASS = "exact_pass"       # core/mpbcfw.exact_pass
ORACLE = "oracle"               # problem.oracle inside the exact pass
APPROX_PASS = "approx_pass"     # core/mpbcfw.approx_pass, gram pass

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def span(name: str, **meta) -> jax.profiler.TraceAnnotation:
    """Host span ``repro:<name>``; ``meta`` becomes its trace metadata."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **meta)


def step(name: str, n: int) -> jax.profiler.StepTraceAnnotation:
    """Host span ``repro:<name>`` marked as profiler step ``n``."""
    return jax.profiler.StepTraceAnnotation(PREFIX + name, step_num=int(n))


def scope(name: str):
    """Device scope: HLO made inside carries ``name`` in its op_name."""
    return jax.named_scope(name)


_compiles = 0
_listening = False


def _on_duration(event, duration, **_):
    global _compiles
    if event == COMPILE_EVENT:
        _compiles += 1


def compile_count() -> int:
    """Executables made in this process since the first call (the
    listener is registered then, once)."""
    global _listening
    if not _listening:
        _listening = True
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
    return _compiles
