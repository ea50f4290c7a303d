"""Built-in engines and their registry entries.

Importing this module registers the whole algorithm family —
``fw`` / ``ssg`` / ``bcfw`` / ``bcfw-avg`` (single-program engines),
``mpbcfw`` / ``mpbcfw-avg`` / ``mpbcfw-gram`` (:class:`FusedEngine`:
each outer iteration is one fused device program; the gram variant is a
``CacheLayout(gram=True)`` plane cache), ``mpbcfw-gap`` (the
:mod:`repro.policy` gap-proportional bundle on the fused engine, single
device or mesh), and ``mpbcfw-shard`` /
``mpbcfw-shard-avg`` / ``mpbcfw-shard-tau`` / ``mpbcfw-shard-gram``
(:class:`ShardDriverEngine` over :class:`repro.shard.ShardEngine` on a
1-D data mesh; ``mpbcfw-gram`` + ``RunConfig.mesh`` resolves to the
sharded gram path too) — into the :mod:`repro.api.engine` registry.  The
registry loads this module lazily on first lookup, so ``import
repro.core`` stays light.

Each engine implements the :class:`~repro.api.engine.Engine` protocol;
capability differences (mesh, gram, tau, averaging) live in the
registered :class:`~repro.api.engine.EngineCapabilities`, not in string
checks.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..cache import CacheLayout
from ..core import bcfw, mpbcfw, subgradient
from ..core.averaging import extract as extract_average, init_averaging
from ..core.selection import SyncLedger
from ..core.ssvm import init_state as init_bcfw_state, weights_of
from ..core.types import SSVMProblem
from . import solver as solver_mod
from .config import RunConfig
from .engine import EngineCapabilities, register_engine
from .errors import UnsupportedConfigError


class IterStats(NamedTuple):
    """Host telemetry returned by a non-multipass engine's read_stats."""

    n_exact: int
    n_approx: int


# Contract budgets (repro.analysis proves these statically on the traced
# fused programs): single-device engines issue no collectives and no host
# callbacks; the shard engines issue exactly one setup psum per program
# and one psum per approximate pass; every engine accumulates duals in
# float32.
_SINGLE_DEVICE_BUDGET = dict(collectives_per_pass=0, collectives_setup=0,
                             host_callbacks=0)
_SHARD_BUDGET = dict(collectives_per_pass=1, collectives_setup=1,
                     host_callbacks=0)


def _policies(problem: SSVMProblem, cfg: RunConfig, *,
              allow_key: bool = False, default=None):
    """Resolve ``cfg.policies`` (or the engine's ``default`` names) into
    a :class:`repro.policy.PolicyBundle`, or ``None`` for the baked-in
    pre-policy behaviour."""
    from ..policy import make_bundle
    names = cfg.policies if cfg.policies is not None else default
    if names is None:
        return None
    bundle = make_bundle(names, cfg, problem.n)
    if bundle.needs_key and not allow_key:
        raise UnsupportedConfigError(
            f"policy bundle {tuple(names)} contains a keyed sampler "
            f"({bundle.sampling.name!r}), but {cfg.algo!r} does not "
            "thread per-iteration PRNG keys; use algo='mpbcfw-gap'.")
    return bundle


class _EngineBase:
    """Shared plumbing: ledger + default checkpoint pack/unpack hooks."""

    def __init__(self, problem: SSVMProblem, lam: float):
        self.problem = problem
        self.lam = float(lam)
        self.ledger = SyncLedger()

    def pack_state(self, state):
        """Checkpointable pytree for ``state`` (identity by default)."""
        return state

    def unpack_state(self, tree):
        """Inverse of :meth:`pack_state` (restores engine-held caches)."""
        return tree

    def continue_passes(self, state, perms, clock):
        raise NotImplementedError(
            f"{type(self).__name__} is not a multipass engine")


# ---------------------------------------------------------------------------
# MP-BCFW execution engines (multipass: the full slope-ruled control loop)


class FusedEngine(_EngineBase):
    """Single-device engine: each outer iteration is one fused program
    (:func:`repro.core.mpbcfw.outer_iteration`).  The Sec-3.5 Gram
    configuration is a :class:`~repro.cache.CacheLayout` choice — the
    gram blocks live inside the state's :class:`~repro.cache.PlaneCache`,
    so there is no engine-held cache to thread or checkpoint
    separately."""

    capabilities = EngineCapabilities(multipass=True,
                                      supports_averaging=True,
                                      policy_capable=True,
                                      policies=("uniform", "ttl-lru",
                                                "slope"),
                                      **_SINGLE_DEVICE_BUDGET)

    def __init__(self, problem: SSVMProblem, lam: float, *,
                 use_gram: bool = False, gram_steps: int = 10,
                 averaged: bool = False, policies=None):
        super().__init__(problem, lam)
        self.use_gram, self.gram_steps = use_gram, gram_steps
        self.averaged = averaged
        self.policies = policies
        self.track_gap = policies is not None and policies.needs_gap
        if self.track_gap and use_gram:
            raise UnsupportedConfigError(
                "gap-tracking policies are unsupported with the Sec-3.5 "
                "gram scheme (the gram pass body exposes no per-visit "
                "scores to fold into the gap vector)")

    def init_state(self, cap: int):
        return mpbcfw.init_mp_state(
            self.problem, CacheLayout(cap=cap, gram=self.use_gram,
                                      track_gap=self.track_gap))

    def outer_iteration(self, mp, perm, perms, clock, *, ttl: int,
                        key=None):
        """Dispatch one fused outer iteration (no blocking).  ``mp`` is
        donated: its buffers become the returned state's."""
        self.ledger.dispatched()
        return mpbcfw.jit_outer_iteration(
            self.problem, mp, perm, perms, clock,
            lam=self.lam, ttl=ttl, steps=self.gram_steps,
            policies=self.policies, key=key, donate=True)

    def continue_passes(self, mp, perms, clock):
        """Overflow batch of approximate passes (rare: only when an
        iteration runs more than ``approx_batch`` passes).  ``mp`` is
        donated, as in :meth:`outer_iteration`."""
        self.ledger.dispatched()
        return mpbcfw.jit_multi_approx_pass(
            self.problem, mp, perms, clock, lam=self.lam,
            steps=self.gram_steps, policies=self.policies, donate=True)

    def read_stats(self, stats):
        return self.ledger.sync(stats)

    def evaluate(self, mp):
        return solver_mod.evaluate_objectives(
            self.problem, mp.inner.phi, mp.avg if self.averaged else None,
            self.lam)

    def extract(self, mp):
        w = np.asarray(weights_of(mp.inner.phi, self.lam))
        w_avg = np.asarray(weights_of(extract_average(mp.avg, self.lam),
                                      self.lam))
        return w, w_avg


class AsyncEngine(FusedEngine):
    """Pipelined single-device engine (``mpbcfw-async``): TWO programs
    dispatched per outer iteration without a host sync between them —
    the exact max-oracle over the next iteration's blocks at the stale
    iteration-entry ``w`` (:func:`repro.core.mpbcfw.async_oracle_program`)
    and the eviction + fold-in + approximate batch on the current state
    (:func:`repro.core.mpbcfw.async_cache_program`).  JAX async dispatch
    overlaps their device execution; the contract is <= 2 dispatches +
    1 host sync per iteration, and the ledger carries the
    oracle-overlap accounting (modeled oracle time hidden behind the
    cache program) behind ``TraceRow.oracle_overlap``."""

    capabilities = EngineCapabilities(multipass=True,
                                      supports_averaging=True,
                                      policy_capable=True,
                                      async_oracle=True,
                                      policies=("uniform", "ttl-lru",
                                                "slope"),
                                      **_SINGLE_DEVICE_BUDGET)

    def __init__(self, problem: SSVMProblem, lam: float, *,
                 gram_steps: int = 10, averaged: bool = False,
                 policies=None, fold_scatter: str = "per-elem"):
        super().__init__(problem, lam, averaged=averaged,
                         gram_steps=gram_steps, policies=policies)
        self.fold_scatter = fold_scatter
        # Straggler-injection hook (repro.ft tests): ``(iteration, k) ->
        # (k,) bool`` arrival mask for the k dispatched oracles; None
        # means every result arrives in time.
        self.outcome_fn = None
        self._overlap_pending = None
        self._it = 0

    def init_state(self, cap: int):
        return mpbcfw.init_async_state(
            self.problem, CacheLayout(cap=cap, track_gap=self.track_gap,
                                      fold_scatter=self.fold_scatter))

    def _done_mask(self, k: int):
        self._it += 1
        if self.outcome_fn is None:
            return jnp.ones((k,), bool)
        return jnp.asarray(self.outcome_fn(self._it, k)).astype(bool)

    def outer_iteration(self, state, perm, perms, clock, *, ttl: int,
                        key=None):
        """Dispatch the oracle and cache programs back to back (no
        blocking, no data dependence between them)."""
        mp, pending = state.mp, state.pending
        self.ledger.dispatched()
        ids, planes = mpbcfw.jit_async_oracle(
            self.problem, mp.inner.phi, mp.cache, perm, key,
            lam=self.lam, policies=self.policies)
        self.ledger.dispatched()
        mp2, clock2, stats = mpbcfw.jit_async_cache(
            mp, pending, perms, clock, lam=self.lam, ttl=ttl,
            steps=self.gram_steps, policies=self.policies,
            scatter=self.fold_scatter)
        new_pending = mpbcfw.PendingOracle(
            ids=ids, planes=planes, done=self._done_mask(perm.shape[0]),
            live=jnp.ones((), bool))
        # Overlap accounting, still on device: the oracle program's
        # modeled duration is the slope clock's exact-pass constant
        # (clock.t); the cache program's is the approximate phase's clock
        # advance.  min(oracle, cache) of it is hidden by the pipeline.
        # Synced — once — in read_stats.
        self._overlap_pending = (
            clock.t, jnp.minimum(clock.t, clock2.t - clock.t))
        return (mpbcfw.AsyncMPState(mp=mp2, pending=new_pending),
                clock2, stats)

    def continue_passes(self, state, perms, clock):
        self.ledger.dispatched()
        mp2, clock2, stats = mpbcfw.jit_multi_approx_pass(
            self.problem, state.mp, perms, clock, lam=self.lam,
            steps=self.gram_steps, policies=self.policies)
        return state._replace(mp=mp2), clock2, stats

    def read_stats(self, stats):
        pend, self._overlap_pending = self._overlap_pending, None
        if pend is None:
            return self.ledger.sync(stats)
        st, total, hidden = self.ledger.sync((stats, pend[0], pend[1]))
        self.ledger.overlapped(float(total), float(hidden))
        return st

    def evaluate(self, state):
        return super().evaluate(state.mp)

    def extract(self, state):
        return super().extract(state.mp)


class ShardDriverEngine(FusedEngine):
    """Adapter driving :class:`repro.shard.ShardEngine` through the same
    protocol: the exact pass is the tau-nice epoch, fused with the
    approximate batch into one program on the mesh."""

    capabilities = EngineCapabilities(multipass=True, supports_mesh=True,
                                      supports_averaging=True,
                                      uses_tau=True, policy_capable=True,
                                      policies=("uniform", "ttl-lru",
                                                "slope"),
                                      **_SHARD_BUDGET)

    def __init__(self, problem: SSVMProblem, lam: float, mesh,
                 tau: Optional[int], *, averaged: bool = False,
                 use_gram: bool = False, gram_steps: int = 10,
                 policies=None):
        from ..shard import ShardEngine  # lazy: keep core importable alone
        super().__init__(problem, lam, averaged=averaged,
                         use_gram=use_gram, gram_steps=gram_steps,
                         policies=policies)
        self.eng = ShardEngine(problem, mesh, lam=lam, use_gram=use_gram,
                               gram_steps=gram_steps, policies=policies)
        self.tau = int(tau) if tau is not None else self.eng.n_shards
        self.ledger = self.eng.ledger

    def init_state(self, cap: int):
        return self.eng.init_state(cap)

    def outer_iteration(self, mp, perm, perms, clock, *, ttl: int,
                        key=None):
        return self.eng.outer_iteration(mp, perm, perms, clock,
                                        tau=self.tau, ttl=ttl, key=key)

    def continue_passes(self, mp, perms, clock):
        return self.eng.multi_approx_pass(mp, perms, clock)

    def read_stats(self, stats):
        return self.eng.read_stats(stats)

    def unpack_state(self, tree):
        return self.eng.place(tree)


class ShardAsyncDriverEngine(AsyncEngine):
    """Pipelined mesh engine (``mpbcfw-shard-async``): the per-shard
    oracle compute of :meth:`repro.shard.ShardEngine.async_oracle_pass`
    (zero collectives) overlaps the psum-synchronized cache passes of
    :meth:`~repro.shard.ShardEngine.async_cache_pass` — same <= 2
    dispatches + 1 host sync contract as the single-device pipeline,
    same one-setup-psum + one-psum-per-pass collective budget as the
    serial shard family (all of it inside the cache program)."""

    capabilities = EngineCapabilities(multipass=True, supports_mesh=True,
                                      supports_averaging=True,
                                      policy_capable=True,
                                      async_oracle=True,
                                      policies=("uniform", "ttl-lru",
                                                "slope"),
                                      **_SHARD_BUDGET)

    def __init__(self, problem: SSVMProblem, lam: float, mesh, *,
                 gram_steps: int = 10, policies=None,
                 fold_scatter: str = "per-elem"):
        from ..shard import ShardEngine  # lazy: keep core importable alone
        super().__init__(problem, lam, gram_steps=gram_steps,
                         policies=policies, fold_scatter=fold_scatter)
        if policies is not None and policies.sampling.name != "uniform":
            raise UnsupportedConfigError(
                "mpbcfw-shard-async runs the uniform exact schedule (the "
                "pipelined oracle program shards the whole permutation); "
                f"sampler {policies.sampling.name!r} is unsupported — use "
                "mpbcfw-async for sampled schedules.")
        self.eng = ShardEngine(problem, mesh, lam=lam,
                               gram_steps=gram_steps, policies=policies)
        self.ledger = self.eng.ledger

    def init_state(self, cap: int):
        return mpbcfw.AsyncMPState(
            mp=self.eng.init_state(cap),
            pending=mpbcfw.init_pending(self.problem.n, self.problem.d))

    def outer_iteration(self, state, perm, perms, clock, *, ttl: int,
                        key=None):
        del key
        ids, planes = self.eng.async_oracle_pass(state.mp.inner.phi, perm)
        mp2, clock2, stats = self.eng.async_cache_pass(
            state.mp, state.pending, perms, clock, ttl=ttl,
            scatter=self.fold_scatter)
        new_pending = mpbcfw.PendingOracle(
            ids=ids, planes=planes, done=self._done_mask(perm.shape[0]),
            live=jnp.ones((), bool))
        self._overlap_pending = (
            clock.t, jnp.minimum(clock.t, clock2.t - clock.t))
        return (mpbcfw.AsyncMPState(mp=mp2, pending=new_pending),
                clock2, stats)

    def continue_passes(self, state, perms, clock):
        mp2, clock2, stats = self.eng.multi_approx_pass(state.mp, perms,
                                                        clock)
        return state._replace(mp=mp2), clock2, stats

    def read_stats(self, stats):
        pend, self._overlap_pending = self._overlap_pending, None
        if pend is None:
            return self.eng.read_stats(stats)
        st, (total, hidden) = self.eng.read_stats(stats, extra=pend)
        self.ledger.overlapped(float(total), float(hidden))
        return st

    def unpack_state(self, tree):
        return tree._replace(mp=self.eng.place(tree.mp))


# ---------------------------------------------------------------------------
# Single-program engines (one exact pass per outer iteration)


class FWEngine(_EngineBase):
    """Batch Frank-Wolfe (paper Alg. 1): n oracle calls per iteration,
    no per-block state, no permutation.  The oracle-call counter rides
    in the state tuple so checkpoints resume it exactly."""

    capabilities = EngineCapabilities(needs_perm=False,
                                      **_SINGLE_DEVICE_BUDGET)

    def __init__(self, problem: SSVMProblem, lam: float):
        super().__init__(problem, lam)
        # The counter rides through the jitted pass so syncing it blocks
        # on the pass itself (wall-clock mode times the real compute).
        self._step = jax.jit(
            lambda p, c: (bcfw.fw_pass(problem, p, lam), c + problem.n))

    def init_state(self, cap: int):
        del cap
        return (jnp.zeros((self.problem.d + 1,), jnp.float32),
                jnp.zeros((), jnp.int32))

    def outer_iteration(self, state, perm, perms, clock, *, ttl: int):
        del perm, perms, clock, ttl
        phi, calls = state
        self.ledger.dispatched()
        phi, calls = self._step(phi, calls)
        return (phi, calls), None, calls

    def read_stats(self, stats):
        return IterStats(n_exact=int(self.ledger.sync(stats)), n_approx=0)

    def evaluate(self, state):
        return solver_mod.evaluate_objectives(self.problem, state[0], None,
                                              self.lam)

    def extract(self, state):
        return np.asarray(weights_of(state[0], self.lam)), None


class SSGEngine(_EngineBase):
    """Stochastic subgradient baseline: no dual certificate (dual/gap
    are reported as NaN).  ``t_ctr`` (the 1/(lam t) schedule counter,
    starting at 1) doubles as the oracle-call counter."""

    capabilities = EngineCapabilities(needs_perm=True,
                                      **_SINGLE_DEVICE_BUDGET)

    def init_state(self, cap: int):
        del cap
        return (jnp.zeros((self.problem.d,), jnp.float32),
                jnp.ones((), jnp.int32))

    def outer_iteration(self, state, perm, perms, clock, *, ttl: int):
        del perms, clock, ttl
        w, t_ctr = state
        self.ledger.dispatched()
        w, t_ctr = subgradient.jit_ssg_pass(self.problem, w, t_ctr, perm,
                                            lam=self.lam)
        return (w, t_ctr), None, t_ctr

    def read_stats(self, stats):
        return IterStats(n_exact=int(self.ledger.sync(stats)) - 1,
                         n_approx=0)

    def evaluate(self, state):
        primal = solver_mod.ssg_primal(self.problem, state[0], self.lam)
        return primal, float("nan"), primal

    def extract(self, state):
        return np.asarray(state[0]), None


class BCFWEngine(_EngineBase):
    """Block-coordinate Frank-Wolfe (paper Alg. 2), with the Sec-3.6
    averaging tracks maintained (reported when ``averaged=True``)."""

    capabilities = EngineCapabilities(needs_perm=True,
                                      supports_averaging=True,
                                      **_SINGLE_DEVICE_BUDGET)

    def __init__(self, problem: SSVMProblem, lam: float, *,
                 averaged: bool = False):
        super().__init__(problem, lam)
        self.averaged = averaged

    def init_state(self, cap: int):
        del cap
        return (init_bcfw_state(self.problem),
                init_averaging(self.problem.d))

    def outer_iteration(self, state, perm, perms, clock, *, ttl: int):
        del perms, clock, ttl
        st, avg = state
        self.ledger.dispatched()
        st, avg = bcfw.jit_exact_pass(self.problem, st, avg, perm,
                                      lam=self.lam)
        return (st, avg), None, st.n_exact

    def read_stats(self, stats):
        return IterStats(n_exact=int(self.ledger.sync(stats)), n_approx=0)

    def evaluate(self, state):
        st, avg = state
        return solver_mod.evaluate_objectives(
            self.problem, st.phi, avg if self.averaged else None, self.lam)

    def extract(self, state):
        st, avg = state
        w = np.asarray(weights_of(st.phi, self.lam))
        w_avg = np.asarray(weights_of(extract_average(avg, self.lam),
                                      self.lam))
        return w, w_avg


# ---------------------------------------------------------------------------
# Registration (order defines driver.ALGORITHMS for backward compat).
# overwrite=True keeps registration idempotent: if this module's first
# import fails partway (registry half-populated), the retry re-executes
# it from scratch and must not trip the duplicate guard.


def _register(name, factory, capabilities):
    def make(problem, cfg, _factory=factory, _caps=capabilities):
        engine = _factory(problem, cfg)
        # One source of truth: the instance's `capabilities` always
        # equals its registry entry's, even where the entry refines the
        # class default (mpbcfw-gram, mpbcfw-shard-tau).
        engine.capabilities = _caps
        return engine

    register_engine(name, make, capabilities, overwrite=True)


def _shard_factory(problem: SSVMProblem, cfg: RunConfig,
                   averaged: bool = False,
                   use_gram: bool = False) -> ShardDriverEngine:
    from ..launch.mesh import ensure_data_mesh
    return ShardDriverEngine(problem, cfg.lam, ensure_data_mesh(cfg.mesh),
                             cfg.tau, averaged=averaged, use_gram=use_gram,
                             gram_steps=cfg.gram_steps,
                             policies=_policies(problem, cfg))


def _gram_factory(problem: SSVMProblem, cfg: RunConfig):
    """``mpbcfw-gram`` resolves by configuration: single-device fused
    program without a mesh, the sharded gram engine with one — the
    capability check (supports_mesh) admits both instead of raising the
    pre-cache ``UnsupportedConfigError`` for gram+mesh."""
    if cfg.mesh is not None:
        return _shard_factory(problem, cfg, use_gram=True)
    return FusedEngine(problem, cfg.lam, use_gram=True,
                       gram_steps=cfg.gram_steps,
                       policies=_policies(problem, cfg))


def _shard_async_factory(problem: SSVMProblem,
                         cfg: RunConfig) -> "ShardAsyncDriverEngine":
    from ..launch.mesh import ensure_data_mesh
    return ShardAsyncDriverEngine(problem, cfg.lam,
                                  ensure_data_mesh(cfg.mesh),
                                  gram_steps=cfg.gram_steps,
                                  policies=_policies(problem, cfg))


def _gap_factory(problem: SSVMProblem, cfg: RunConfig):
    """``mpbcfw-gap``: gap-proportional gumbel-top-k sampling + gap-aware
    eviction (default bundle ``GAP_POLICIES``; override via
    ``RunConfig.policies``).  With a mesh the sampled schedule needs the
    sequential exact path, so tau is pinned to 1 (``RunConfig.tau`` is
    rejected by the capability check: ``uses_tau=False``)."""
    from ..policy import GAP_POLICIES
    bundle = _policies(problem, cfg, allow_key=True, default=GAP_POLICIES)
    if cfg.mesh is not None:
        from ..launch.mesh import ensure_data_mesh
        return ShardDriverEngine(problem, cfg.lam,
                                 ensure_data_mesh(cfg.mesh), 1,
                                 gram_steps=cfg.gram_steps,
                                 policies=bundle)
    return FusedEngine(problem, cfg.lam, gram_steps=cfg.gram_steps,
                       policies=bundle)


_register(
    "fw", lambda p, cfg: FWEngine(p, cfg.lam), FWEngine.capabilities)
_register(
    "ssg", lambda p, cfg: SSGEngine(p, cfg.lam), SSGEngine.capabilities)
_register(
    "bcfw", lambda p, cfg: BCFWEngine(p, cfg.lam),
    BCFWEngine.capabilities)
_register(
    "bcfw-avg", lambda p, cfg: BCFWEngine(p, cfg.lam, averaged=True),
    BCFWEngine.capabilities)
_register(
    "mpbcfw",
    lambda p, cfg: FusedEngine(p, cfg.lam, policies=_policies(p, cfg)),
    FusedEngine.capabilities)
_register(
    "mpbcfw-avg",
    lambda p, cfg: FusedEngine(p, cfg.lam, averaged=True,
                               policies=_policies(p, cfg)),
    FusedEngine.capabilities)
_register(
    "mpbcfw-gap", _gap_factory,
    EngineCapabilities(
        multipass=True, supports_averaging=True, supports_mesh=True,
        mesh_optional=True, policy_capable=True, needs_key=True,
        policies=("gap-topk", "gap-ttl", "slope"), **_SHARD_BUDGET,
        note="Gap-proportional sampling (gumbel-top-k over per-block "
             "duality gaps) with gap-aware eviction; RunConfig.gap_frac "
             "sets the exact-pass fraction.  With RunConfig.mesh the "
             "sampled schedule runs the sequential (tau=1) exact path; "
             "a 1-device mesh is bit-for-bit equal to the single-device "
             "program."))
_register(
    "mpbcfw-gram", _gram_factory,
    EngineCapabilities(
        multipass=True, supports_gram=True, supports_averaging=True,
        supports_mesh=True, uses_tau=True, tau_requires_mesh=True,
        mesh_optional=True, policy_capable=True,
        policies=("uniform", "ttl-lru", "slope"), **_SHARD_BUDGET,
        note="mpbcfw-gram with RunConfig.mesh resolves to the sharded "
             "gram engine (the mpbcfw-shard-gram path: PlaneCache.gram "
             "shards with the blocks), which also consumes "
             "RunConfig.tau."))
_register(
    "mpbcfw-async",
    lambda p, cfg: AsyncEngine(p, cfg.lam, gram_steps=cfg.gram_steps,
                               policies=_policies(p, cfg)),
    dataclasses.replace(
        AsyncEngine.capabilities,
        note="Pipelined oracle: two programs dispatched per outer "
             "iteration (exact oracles for the next iteration at stale "
             "w, eviction + monotone fold-in + approximate batch on the "
             "current state), <= 2 dispatches + 1 host sync, proven by "
             "analysis rule J009; TraceRow.oracle_overlap reports the "
             "hidden fraction of the modeled oracle time."))
_register(
    "mpbcfw-shard-async",
    lambda p, cfg: _shard_async_factory(p, cfg),
    dataclasses.replace(
        ShardAsyncDriverEngine.capabilities,
        note="Pipelined oracle on the 1-D data mesh: the per-shard "
             "oracle program (zero collectives) overlaps the "
             "psum-synchronized cache passes; collective budgets match "
             "the serial shard family."))
_register(
    "mpbcfw-shard", _shard_factory, ShardDriverEngine.capabilities)
_register(
    "mpbcfw-shard-avg",
    lambda p, cfg: _shard_factory(p, cfg, averaged=True),
    ShardDriverEngine.capabilities)
_register(
    "mpbcfw-shard-tau", _shard_factory,
    dataclasses.replace(ShardDriverEngine.capabilities,
                        requires_tau=True))
_register(
    "mpbcfw-shard-gram",
    lambda p, cfg: _shard_factory(p, cfg, use_gram=True),
    dataclasses.replace(ShardDriverEngine.capabilities,
                        supports_gram=True,
                        note="Sec-3.5 Gram scheme on the mesh-sharded "
                             "plane cache; bit-for-bit equal to "
                             "mpbcfw-gram on a 1-device mesh."))
