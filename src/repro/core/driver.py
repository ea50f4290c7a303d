"""Compatibility re-exports for the pre-``repro.api`` module layout.

The control loop, the engine implementations, and the config/trace types
all moved to the public protocol layer:

  * :mod:`repro.api.solver`  — the engine-generic control loop
    (:class:`~repro.api.Solver`, streaming ``iterate()``, stopping
    criteria, callbacks, checkpoint/resume);
  * :mod:`repro.api.engine`  — the ``Engine`` protocol,
    ``EngineCapabilities``, and the ``register_engine`` registry that
    replaced the hard-coded ``ALGORITHMS`` tuple and the if/elif ladder
    this module used to dispatch on;
  * :mod:`repro.api.engines` — the built-in engines (fw / ssg / bcfw /
    mpbcfw families and the shard_map engine);
  * :mod:`repro.api.config`  — ``RunConfig`` / ``TraceRow`` /
    ``RunResult`` (re-exported here, so existing imports keep working).

The one-release ``driver.run`` convenience shim is gone: call
``Solver(problem, cfg).run()`` — the identical call, with streaming
iteration, stopping criteria, callbacks, and checkpoint/resume on top.
"""
from __future__ import annotations

from ..api.config import RunConfig, RunResult, TraceRow  # noqa: F401

_MOVED = {
    # name -> (module, attribute); resolved lazily so importing
    # repro.core stays light (the registry loads engines on first use).
    "ALGORITHMS": ("repro.api.engine", "algorithms"),
    "_FusedEngine": ("repro.api.engines", "FusedEngine"),
    "_ShardDriverEngine": ("repro.api.engines", "ShardDriverEngine"),
    "_Clock": ("repro.api.solver", "_Clock"),
    "_evaluate": ("repro.api.solver", "evaluate_objectives"),
    "_fit_pass_costs": ("repro.api.solver", "_fit_pass_costs"),
    "_draw_perms": ("repro.api.solver", "_draw_perms"),
    "batched_oracle": ("repro.core.ssvm", "batched_oracle"),
}


def __getattr__(name: str):
    """PEP-562 compat shims for the pre-``repro.api`` private surface."""
    moved = _MOVED.get(name)
    if moved is None:
        raise AttributeError(f"module {__name__!r} has no attribute "
                             f"{name!r}")
    import importlib

    module, attr = moved
    value = getattr(importlib.import_module(module), attr)
    if name == "ALGORITHMS":
        return value()  # the registry's registration-order name tuple
    return value
