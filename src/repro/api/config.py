"""Run configuration and trace/result value types (canonical home).

These used to live in :mod:`repro.core.driver`; that module still
re-exports them, so ``driver.RunConfig`` / ``driver.TraceRow`` /
``driver.RunResult`` remain valid spellings.  The types themselves are
engine-agnostic: :class:`RunConfig` is consumed by
:class:`repro.api.Solver`, which resolves ``algo`` through the engine
registry and validates the rest of the fields against the engine's
declared capabilities.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional, Tuple, TYPE_CHECKING

import numpy as np
from jax.sharding import Mesh

if TYPE_CHECKING:  # annotation only: keep this module import-cycle-free
    from ..core.selection import CostModel


@dataclass
class RunConfig:
    lam: float
    algo: str = "mpbcfw"
    cap: int = 64           # hard cap N (paper: "very large"; memory bound)
    ttl: int = 10           # T, plane time-to-live in outer iterations
    max_iters: int = 50
    max_approx_passes: int = 1000   # M (paper: large; slope rule governs)
    approx_batch: int = 64  # approximate passes fused per device program
    gram_steps: int = 10    # repeats per block for the Sec-3.5 scheme
    seed: int = 0
    cost_model: Optional["CostModel"] = None  # None => wall clock
    mesh: Optional[Mesh] = None  # mpbcfw-shard*: 1-D data mesh (None =>
    #                              launch.mesh.ensure_data_mesh default)
    tau: Optional[int] = None    # mpbcfw-shard*: tau-nice chunk size
    #                              (None => #shards; must divide n)
    gap_tol: Optional[float] = None   # stop once duality gap <= gap_tol
    #                                   (Osokin et al.-style gap stopping)
    time_budget: Optional[float] = None  # stop once clock.now() >= budget
    #                                      (seconds: wall or CostModel)
    policies: Optional[Tuple[str, ...]] = None  # repro.policy bundle names
    #                              (one sampling + one eviction + one
    #                              oracle policy); None keeps the engine's
    #                              own default bundle
    gap_frac: float = 0.5   # gap-topk sampler: fraction of blocks whose
    #                         exact oracle runs per iteration (resolved to
    #                         a static k = max(1, round(gap_frac * n)))
    gap_temperature: float = 2.0  # gap-topk gumbel temperature: 1 =
    #                         proportional, > 1 flatter (exploration),
    #                         < 1 greedier (static sampler field)
    gap_floor: float = 0.1  # gap-topk min-probability floor, relative
    #                         to the mean gap over seen blocks: keeps
    #                         converged/stale blocks samplable (static
    #                         sampler field)


@dataclass
class TraceRow:
    iteration: int
    n_exact: int
    n_approx: int
    time: float
    primal: float
    dual: float
    gap: float
    primal_avg: float       # primal at the averaged iterate (Sec. 3.6)
    ws_mean: float          # mean working-set size over the iteration's
    #                         passes (Fig. 5) — one statistic in all paths
    approx_passes: int      # approximate passes this iteration (Fig. 6)
    host_syncs: int = 1     # device->host syncs in the control loop
    dispatches: int = 1     # program dispatches in the control loop
    # Obs columns (repro.obs).  Accumulated on device inside the fused
    # outer-iteration program and drained through the iteration's single
    # host sync (ObsMetrics riding in ApproxBatchStats); engines without
    # the multipass cache report the defaults.
    cache_hit_rate: float = 0.0   # fraction of blocks with >= 1 cached
    #                               plane (an approx visit to such a block
    #                               is a cache hit; 0 planes falls back)
    planes_evicted: int = 0       # TTL + LRU evictions this iteration
    oracle_overlap: float = 0.0   # async engines: fraction of the exact
    #                               oracle's modeled time hidden behind the
    #                               concurrently-dispatched cache program
    #                               this iteration (0 for serial engines)
    # Gap-policy columns (engines tracking per-block duality gaps; the
    # defaults are what non-gap engines report):
    gap_total: Optional[float] = None  # sum of visited blocks' gap
    #                               estimates after the exact pass
    gap_sampled: int = 0          # blocks the sampling policy scheduled
    #                               for the exact pass this iteration
    # Host columns, measured in wall-clock mode (a CostModel run keeps
    # its rows deterministic and reports 0):
    eval_s: float = 0.0           # wall seconds of this iteration's
    #                               evaluation (primal, dual, gap), which
    #                               TraceRow.time leaves out
    compiles: int = 0             # executables JAX made during the
    #                               iteration, compiled or loaded from the
    #                               persistent cache (repro.obs.spans)


@dataclass
class RunResult:
    trace: List[TraceRow] = field(default_factory=list)
    w: Optional[np.ndarray] = None
    w_avg: Optional[np.ndarray] = None
