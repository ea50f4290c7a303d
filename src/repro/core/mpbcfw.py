"""Multi-Plane Block-Coordinate Frank-Wolfe (paper Alg. 3).

The algorithm interleaves

  * **exact passes** — one true max-oracle call per block; the returned
    plane is added to the block's working set (LRU-capped), and
  * **approximate passes** — BCFW steps against the *cached* planes only
    (``H~_i(w) = max_{phi in W_i} <phi, [w 1]>``), costing O(|W_i| d) each.

All cache state rides in one :class:`repro.cache.PlaneCache` inside
:class:`MPState`, and every mutation/scoring goes through the
:mod:`repro.cache` API.  When the cache is built with
``CacheLayout(gram=True)``, the Sec-3.5 scheme is on: insertions refresh
the per-block Gram rows (inside :func:`repro.cache.insert`) and the
approximate phase runs the O(cap)-per-step recurrences of
:mod:`repro.core.gram` — no separate gram state is threaded through any
pass.

Both passes are single jitted ``lax.scan`` programs, and the *sequence* of
approximate passes per exact pass is itself one jitted program:
:func:`multi_approx_pass` runs up to ``B`` passes inside a
``lax.while_loop`` with the paper's geometric slope rule (Sec. 3.4,
parameter ``M``) evaluated **on device** from ``dual_value`` deltas — so
the host never round-trips between approximate passes.  The host-side
:mod:`repro.core.selection` tracker replays the returned per-pass telemetry
through its own clock; the TTL rule resolves ``N``.

:func:`outer_iteration` fuses the whole outer iteration — TTL eviction,
the exact pass, on-device slope-clock seeding, and the batched
approximate phase — into **one** program, which is what lets
:class:`repro.api.Solver` dispatch once and sync once per outer iteration
for the entire MP-BCFW family.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from .. import cache as plane_cache
from ..cache import CacheLayout, PlaneCache
from ..kernels import ops as kops
from ..obs import spans
from .averaging import update_average
from .bcfw import block_update
from .selection import slope_continue_jnp
from .ssvm import dual_value, weights_of
from .types import (ApproxBatchStats, AveragingState, BCFWState, ObsMetrics,
                    SlopeClock, SSVMProblem)


class MPState(NamedTuple):
    """Full MP-BCFW state: dual state + plane cache + averaging."""

    inner: BCFWState
    cache: PlaneCache
    avg: AveragingState
    outer_it: jnp.ndarray  # () int32, outer-iteration counter (for TTL)


def _example(problem: SSVMProblem, i: jnp.ndarray):
    return jax.tree_util.tree_map(lambda a: a[i], problem.data)


def exact_pass(problem: SSVMProblem, mp: MPState, perm: jnp.ndarray,
               lam: float) -> MPState:
    """Paper Alg. 3 step 3: BCFW pass with the real oracle + plane caching.

    :func:`repro.cache.insert` refreshes the Gram rows when the cache
    materializes them, so this one pass body serves both the plain and
    the Sec-3.5 configurations.
    """

    track_gap = mp.cache.gap is not None

    def body(carry, i):
        st, c, av = carry
        w = weights_of(st.phi, lam)
        with spans.scope(spans.ORACLE):
            phi_hat = problem.oracle(w, _example(problem, i))
        if track_gap:
            # True block duality gap at the pre-update iterate: the exact
            # oracle's score minus the current convex combination's.
            phi_old = st.phi_i[i]
            g = ((phi_hat[:-1] @ w + phi_hat[-1])
                 - (phi_old[:-1] @ w + phi_old[-1]))
        st, _ = block_update(st, i, phi_hat, lam)
        st = st._replace(n_exact=st.n_exact + 1)
        c = plane_cache.insert(c, i, phi_hat, mp.outer_it)
        if track_gap:
            c = plane_cache.update_gap(c, i, g)
        av = update_average(av, st.phi, exact=True)
        return (st, c, av), None

    with spans.scope(spans.EXACT_PASS):
        (inner, cache, avg), _ = jax.lax.scan(
            body, (mp.inner, mp.cache, mp.avg), perm)
    return MPState(inner=inner, cache=cache, avg=avg, outer_it=mp.outer_it)


def approx_kernel_runs(cache: PlaneCache, steps: int) -> bool:
    """Whether an approximate pass of ``steps`` block steps runs as the
    ``approx_block_steps`` kernel: a cache without Gram blocks whose
    shapes fit the kernel's plan, on a TPU (``kernels.ops``)."""
    return cache.gram is None and kops.approx_kernel_fits(cache.planes,
                                                          steps)


def approx_pass(problem: Optional[SSVMProblem], mp: MPState,
                perm: jnp.ndarray, lam: float, *,
                occupancy=None) -> MPState:
    """Paper Alg. 3 step 4: BCFW pass against the cached planes only.

    Each step is monotone in F because the cached planes are genuine data
    planes (so the line search is valid), even though H~_i may locally sit
    below the convex combination phi_i (paper footnote 2).

    Where :func:`approx_kernel_runs` holds, the pass is one
    ``approx_block_steps`` launch doing the scan body's arithmetic in
    float32; ``occupancy`` is its ``kernels.ops.approx_occupancy`` of the
    cache, derived here unless a caller running several passes over one
    cache passes it in.  Elsewhere the scan below runs.
    """
    del problem  # the approximate pass never touches the data
    if approx_kernel_runs(mp.cache, perm.shape[0]):
        return approx_pass_kernel(mp, perm, lam, occupancy)
    return approx_pass_scan(mp, perm, lam)


def approx_pass_scan(mp: MPState, perm: jnp.ndarray, lam: float) -> MPState:
    """:func:`approx_pass` as a ``lax.scan`` of block steps (every
    backend, every cache layout)."""
    track_gap = mp.cache.gap is not None

    def body(carry, i):
        st, c, av = carry
        w = weights_of(st.phi, lam)
        phi_hat, slot, score = plane_cache.approx_oracle(c, i, w)
        if track_gap:
            # The cache's gap *underestimate* (H~_i <= H_i): score of the
            # best cached plane minus the current iterate's.
            phi_old = st.phi_i[i]
            g = score - (phi_old[:-1] @ w + phi_old[-1])
        st, gamma = block_update(st, i, phi_hat, lam)
        st = st._replace(n_approx=st.n_approx + 1)
        # A plane is "active" if the (approximate) oracle returned it.
        c = plane_cache.mark_active(c, i, slot, mp.outer_it)
        if track_gap:
            c = plane_cache.update_gap(c, i, g)
        av = update_average(av, st.phi, exact=False)
        return (st, c, av), None

    with spans.scope(spans.APPROX_PASS):
        (inner, cache, avg), _ = jax.lax.scan(
            body, (mp.inner, mp.cache, mp.avg), perm)
    return MPState(inner=inner, cache=cache, avg=avg, outer_it=mp.outer_it)


def approx_pass_kernel(mp: MPState, perm: jnp.ndarray, lam: float,
                       occupancy=None) -> MPState:
    """:func:`approx_pass` as one ``approx_block_steps`` launch (a TPU,
    where :func:`approx_kernel_runs` holds)."""
    st, c, av = mp.inner, mp.cache, mp.avg
    steps = perm.shape[0]
    with spans.scope(spans.APPROX_PASS):
        if occupancy is None:
            occupancy = kops.approx_occupancy(c.valid)
        phi, phi_i, bar, gap, slots = kops.approx_block_steps(
            c.planes, c.valid, perm, st.phi, st.phi_i, av.bar_approx,
            av.k_approx, c.gap, lam=lam, occupancy=occupancy)
        # Every step stamps the same iteration, so repeated blocks make
        # one scatter order-free.
        c = plane_cache.mark_active(c, perm, slots, mp.outer_it)
    return MPState(
        inner=st._replace(phi=phi, phi_i=phi_i,
                          n_approx=st.n_approx + steps),
        cache=c if gap is None else c._replace(gap=gap),
        avg=av._replace(bar_approx=bar, k_approx=av.k_approx + steps),
        outer_it=mp.outer_it)


def begin_iteration(mp: MPState, ttl: int, eviction=None) -> MPState:
    """Eviction + outer-iteration increment (paper Sec. 3.4, param N/T).

    ``eviction`` is an optional :class:`repro.policy.EvictionPolicy`;
    ``None`` keeps the paper's TTL rule with the explicit ``ttl``.
    """
    with spans.scope(spans.EVICT):
        it = mp.outer_it + 1
        cache = (plane_cache.evict_stale(mp.cache, it, ttl)
                 if eviction is None else eviction.evict(mp.cache, it))
    return mp._replace(cache=cache, outer_it=it)


@functools.partial(jax.jit, static_argnums=(0, 1), static_argnames=("lam",))
def _jit_exact_pass(oracle, n, data, mp: MPState, perm: jnp.ndarray,
                    *, lam: float) -> MPState:
    prob = SSVMProblem(n=n, d=mp.inner.phi.shape[0] - 1, data=data,
                       oracle=oracle)
    return exact_pass(prob, mp, perm, lam)


def jit_exact_pass(problem: SSVMProblem, mp: MPState, perm: jnp.ndarray,
                   *, lam: float) -> MPState:
    return _jit_exact_pass(problem.oracle, problem.n, problem.data, mp,
                           perm, lam=lam)


@functools.partial(jax.jit, static_argnames=("lam",))
def jit_approx_pass_impl(mp: MPState, perm: jnp.ndarray,
                         *, lam: float) -> MPState:
    return approx_pass(None, mp, perm, lam)


def jit_approx_pass(problem: SSVMProblem, mp: MPState, perm: jnp.ndarray,
                    *, lam: float) -> MPState:
    del problem  # the approximate pass never touches the data
    return jit_approx_pass_impl(mp, perm, lam=lam)


def make_slope_clock(t0, f0, t, plane_cost) -> SlopeClock:
    """Build the device timing state for :func:`multi_approx_pass`."""
    f32 = lambda x: jnp.asarray(x, jnp.float32)  # noqa: E731
    return SlopeClock(t0=f32(t0), f0=f32(f0), t=f32(t),
                      plane_cost=f32(plane_cost))


def slope_batched_loop(carry, perms: jnp.ndarray, clock: SlopeClock, *,
                       step, f_entry: jnp.ndarray, cost: jnp.ndarray,
                       planes_per_pass: jnp.ndarray, run_all: bool = False,
                       continue_fn=None):
    """Generic batched pass loop governed by the on-device slope rule.

    ``step(carry, perm) -> (carry, f_new)`` runs one pass and reports the
    dual afterwards.  The loop itself — ``lax.while_loop`` with
    :func:`repro.core.selection.slope_continue_jnp` on dual deltas, true
    early exit, zero-filled telemetry tail — is shared between the
    single-device :func:`multi_approx_pass` and the mesh-sharded twin
    (:mod:`repro.shard.engine`), so both make bit-identical stopping
    decisions given bit-identical duals.  ``continue_fn`` swaps the
    stopping rule (an :class:`repro.policy.OraclePolicy`'s traced
    decision); ``None`` keeps the paper's slope rule.

    Returns ``(carry, t_end, stats)`` with ``stats`` an
    :class:`~repro.core.types.ApproxBatchStats`.
    """
    cont_fn = slope_continue_jnp if continue_fn is None else continue_fn
    n_batch = perms.shape[0]
    if n_batch == 0:
        # Zero-pass budget (the driver's max_approx_passes=0 path): no
        # loop to run, but the telemetry — f_entry, ws_total, and the
        # "batch cap reached" more flag — is still produced on device.
        stats = ApproxBatchStats(
            duals=jnp.zeros((0,), jnp.float32),
            times=jnp.zeros((0,), jnp.float32),
            planes=jnp.zeros((0,), jnp.int32),
            ran=jnp.zeros((0,), bool),
            passes_run=jnp.zeros((), jnp.int32), f_entry=f_entry,
            more=jnp.asarray(True),
            ws_total=jnp.asarray(planes_per_pass, jnp.int32))
        return carry, clock.t, stats

    def cond(state):
        _, k, _, _, cont, *_ = state
        return cont & (k < n_batch)

    def body(state):
        carry, k, t, f, _, duals, times, planes = state
        carry, f_new = step(carry, perms[k])
        t_new = t + cost
        cont = cont_fn(clock.f0, clock.t0, f, t, f_new, t_new)
        if run_all:
            cont = jnp.asarray(True)
        duals = duals.at[k].set(f_new)
        times = times.at[k].set(t_new)
        planes = planes.at[k].set(planes_per_pass)
        return (carry, k + 1, t_new, f_new, cont, duals, times, planes)

    init = (carry, jnp.zeros((), jnp.int32), clock.t, f_entry,
            jnp.asarray(True),
            jnp.zeros((n_batch,), jnp.float32),
            jnp.zeros((n_batch,), jnp.float32),
            jnp.zeros((n_batch,), jnp.int32))
    carry, k, t, _, cont, duals, times, planes = jax.lax.while_loop(
        cond, body, init)
    stats = ApproxBatchStats(
        duals=duals, times=times, planes=planes,
        ran=jnp.arange(n_batch) < k, passes_run=k, f_entry=f_entry,
        more=cont, ws_total=jnp.asarray(planes_per_pass, jnp.int32))
    return carry, t, stats


def multi_approx_pass(mp: MPState, perms: jnp.ndarray, clock: SlopeClock,
                      *, lam: float, steps: int = 10,
                      run_all: bool = False, policies=None
                      ) -> Tuple[MPState, SlopeClock, ApproxBatchStats]:
    """Up to ``B = perms.shape[0]`` approximate passes in one device program.

    Replaces the host loop "run a pass, sync, evaluate the slope rule,
    maybe run another" with a ``lax.while_loop`` whose stopping criterion —
    :func:`repro.core.selection.slope_continue_jnp` on ``dual_value``
    deltas, timed by ``clock.plane_cost`` per cached plane — is computed on
    device.  A stopped loop never executes the remaining passes (true early
    exit, not masking), so the returned state equals exactly
    ``passes_run`` sequential :func:`approx_pass` applications.

    A gram-carrying cache (``CacheLayout(gram=True)``) switches the pass
    body to the Sec-3.5 multi-step scheme (``steps`` inner repeats per
    block); ``run_all`` disables the stopping rule (used by equivalence
    tests and fixed-budget callers).  Chunked callers thread the returned
    clock into the next batch; the dual on entry (= after the caller's
    exact pass) is recomputed on device into ``stats.f_entry``, so no host
    sync is needed to seed the rule.
    """
    from . import gram as gram_ops

    f_entry = dual_value(mp.inner.phi, lam)
    # Approximate passes never insert/evict planes, so the per-pass cost —
    # Theta(sum_i |W_i|) — is constant across the batch.
    total_planes = jnp.sum(plane_cache.sizes(mp.cache)).astype(jnp.int32)
    cost = clock.plane_cost * jnp.maximum(total_planes, 1).astype(jnp.float32)
    use_gram = mp.cache.gram is not None
    occupancy = None
    if perms.shape[0] and approx_kernel_runs(mp.cache, perms.shape[1]):
        with spans.scope(spans.APPROX_PASS):
            occupancy = kops.approx_occupancy(mp.cache.valid)

    def step(state: MPState, perm: jnp.ndarray):
        if use_gram:
            inner, cache, avg = gram_ops.approx_pass_gram(
                state.inner, state.cache, state.avg, perm, state.outer_it,
                lam, steps)
            state = state._replace(inner=inner, cache=cache, avg=avg)
        else:
            state = approx_pass(None, state, perm, lam,
                                occupancy=occupancy)
        return state, dual_value(state.inner.phi, lam)

    mp, t, stats = slope_batched_loop(
        mp, perms, clock, step=step, f_entry=f_entry, cost=cost,
        planes_per_pass=total_planes, run_all=run_all,
        continue_fn=None if policies is None else policies.oracle.continue_fn)
    # Obs counters ride the stats payload through the existing single host
    # sync.  A standalone multi-pass program (the driver's overflow
    # continuation) never inserts or evicts, so both eviction counters are
    # zero; :func:`outer_iteration` overwrites them with the fused
    # iteration's true deltas.
    zero = jnp.zeros((), jnp.int32)
    metrics = ObsMetrics(ttl_evicted=zero, lru_evicted=zero,
                         occupancy=total_planes,
                         nonempty_blocks=mp.cache.nonempty_blocks)
    return mp, clock._replace(t=t), stats._replace(metrics=metrics)


def _multi_approx_program(mp, perms, clock, *, lam, steps, run_all,
                          policies=None):
    return multi_approx_pass(mp, perms, clock, lam=lam, steps=steps,
                             run_all=run_all, policies=policies)


_MULTI_STATIC = ("lam", "steps", "run_all", "policies")
_jit_multi_approx_pass = jax.jit(_multi_approx_program,
                                 static_argnames=_MULTI_STATIC)
_jit_multi_approx_pass_donating = jax.jit(
    _multi_approx_program, static_argnames=_MULTI_STATIC,
    donate_argnames=("mp",))


def jit_multi_approx_pass(problem: Optional[SSVMProblem], mp: MPState,
                          perms: jnp.ndarray, clock: SlopeClock, *,
                          lam: float, steps: int = 10,
                          run_all: bool = False, policies=None,
                          donate: bool = False):
    """Jitted :func:`multi_approx_pass`.

    ``donate=True`` hands ``mp``'s buffers to the program, which then
    updates the plane cache in place: the caller must not read ``mp``
    afterwards.
    """
    del problem  # approximate passes never touch the data
    fn = _jit_multi_approx_pass_donating if donate else _jit_multi_approx_pass
    return fn(mp, perms, clock, lam=lam, steps=steps, run_all=run_all,
              policies=policies)


def outer_iteration(problem: SSVMProblem, mp: MPState, perm: jnp.ndarray,
                    perms: jnp.ndarray, clock: SlopeClock, *, lam: float,
                    ttl: int, steps: int = 10, run_all: bool = False,
                    policies=None, key: Optional[jnp.ndarray] = None):
    """One *fused* MP-BCFW outer iteration (paper Alg. 3, one device program).

    TTL eviction, the exact pass (oracle scan + plane insertion +
    averaging; gram rows refreshed inside :func:`repro.cache.insert` when
    the cache carries them), and the slope-ruled batch of approximate
    passes run back to back inside a single program — the driver
    dispatches once and syncs once per outer iteration, with no dispatch
    boundary left between the exact and approximate phases.

    The slope clock is seeded **on device**: ``clock.f0`` is replaced by
    the dual at iteration entry (TTL eviction never changes ``phi``, so
    this is the paper's F at the start of the iteration) — the host only
    supplies the cost constants ``clock.t`` (modeled exact-pass cost) and
    ``clock.plane_cost``.  Returns ``(mp, clock, stats)``.

    ``policies`` is an optional (jit-static) :class:`repro.policy
    .PolicyBundle` replacing the baked-in decisions: its eviction policy
    runs instead of the plain TTL rule, its sampler rewrites ``perm``
    into the exact pass's visit schedule (``key`` is the per-iteration
    PRNG key samplers that declared ``needs_key`` receive), and its
    oracle policy replaces the slope rule.  ``None`` — and the default
    uniform/ttl-lru/slope bundle — trace exactly the pre-policy program.
    """
    eviction = None if policies is None else policies.eviction
    occ0 = mp.cache.occupancy                 # before eviction
    mp = begin_iteration(mp, ttl, eviction=eviction)
    occ1 = mp.cache.occupancy                 # after eviction
    clock = clock._replace(f0=dual_value(mp.inner.phi, lam))
    if policies is not None:
        perm = policies.sampling.schedule(mp.cache, perm, key)
    mp = exact_pass(problem, mp, perm, lam)
    occ2 = mp.cache.occupancy                 # after the insert scan
    gap_fields = {}
    if mp.cache.gap is not None:
        # Post-exact-pass gap mass over visited blocks (unseen blocks
        # hold the GAP_UNSEEN sentinel and are excluded).  Computed here
        # — not after the approximate phase — to match the shard engine,
        # which folds the per-shard partial into its setup collective.
        seen = mp.cache.gap < plane_cache.GAP_UNSEEN
        gap_fields = dict(
            gap_total=jnp.sum(jnp.where(seen, mp.cache.gap, 0.0)),
            gap_sampled=jnp.asarray(perm.shape[0], jnp.int32))
    mp, clock, stats = multi_approx_pass(mp, perms, clock, lam=lam,
                                         steps=steps, run_all=run_all,
                                         policies=policies)
    # Eviction accounting, still on device: TTL dropped occ0-occ1 planes;
    # the exact pass inserted one plane per visited block, so the LRU
    # overwrites are the inserts that did *not* grow the cache.
    n_inserts = jnp.asarray(perm.shape[0], jnp.int32)
    metrics = stats.metrics._replace(ttl_evicted=occ0 - occ1,
                                     lru_evicted=occ1 + n_inserts - occ2,
                                     **gap_fields)
    return mp, clock, stats._replace(metrics=metrics)


def _outer_program(oracle, n, data, mp, perm, perms, clock, key,
                   *, lam, ttl, steps, run_all, policies=None):
    prob = SSVMProblem(n=n, d=mp.inner.phi.shape[0] - 1, data=data,
                       oracle=oracle)
    return outer_iteration(prob, mp, perm, perms, clock, lam=lam,
                           ttl=ttl, steps=steps, run_all=run_all,
                           policies=policies, key=key)


_OUTER_STATIC = dict(static_argnums=(0, 1),
                     static_argnames=("lam", "ttl", "steps", "run_all",
                                      "policies"))
_jit_outer_iteration = jax.jit(_outer_program, **_OUTER_STATIC)
_jit_outer_iteration_donating = jax.jit(_outer_program,
                                        donate_argnames=("mp",),
                                        **_OUTER_STATIC)


def jit_outer_iteration(problem: SSVMProblem, mp: MPState,
                        perm: jnp.ndarray, perms: jnp.ndarray,
                        clock: SlopeClock, *, lam: float, ttl: int,
                        steps: int = 10, run_all: bool = False,
                        policies=None, key: Optional[jnp.ndarray] = None,
                        donate: bool = False):
    """Jitted :func:`outer_iteration` (cached per oracle/shape/flags).

    ``policies`` is jit-static (frozen bundle); ``key`` is a traced PRNG
    key (or ``None`` — an empty pytree — when no policy needs one).
    ``donate=True`` hands ``mp``'s buffers to the program so the new
    state reuses them: without it the program holds the input and the
    output plane cache at once, which at the paper's OCR size (n=6877,
    d=4004, cap=64: 7.05 GB of planes) takes nearly all of a 16 GB chip.
    The caller must not read ``mp`` afterwards.
    """
    fn = _jit_outer_iteration_donating if donate else _jit_outer_iteration
    return fn(problem.oracle, problem.n, problem.data, mp, perm, perms,
              clock, key, lam=lam, ttl=ttl, steps=steps, run_all=run_all,
              policies=policies)


def init_mp_state(problem: SSVMProblem,
                  cap: Union[int, CacheLayout]) -> MPState:
    """Fresh MP-BCFW state; ``cap`` is an int or a full
    :class:`~repro.cache.CacheLayout` (gram on/off, dtype, mesh axis)."""
    from .averaging import init_averaging
    from .ssvm import init_state

    layout = cap if isinstance(cap, CacheLayout) else CacheLayout(cap=int(cap))
    return MPState(
        inner=init_state(problem),
        cache=plane_cache.init(layout, problem.n, problem.d),
        avg=init_averaging(problem.d),
        outer_it=jnp.zeros((), jnp.int32),
    )


# ---------------------------------------------------------------------------
# Async oracle pipelining (ROADMAP item 4, the ``mpbcfw-async`` family).
#
# The fused :func:`outer_iteration` serializes the exact max-oracle scan
# with the approximate cache passes inside one program — the oracle's
# latency is paid in full every iteration.  The async split dispatches TWO
# programs per outer iteration without a host sync between them:
#
#   * :func:`async_oracle_program` — the exact max-oracle over the *next*
#     iteration's sampled blocks at the iteration-entry (stale) ``w``;
#   * :func:`async_cache_program`  — eviction, the damped monotone fold-in
#     of the *previous* iteration's oracle results (the tau-nice trick of
#     ``core/distributed``: every returned plane is a genuine data plane,
#     so folding with exact line search at the current phi is monotone no
#     matter which ``w`` produced it), and the slope-ruled batch of
#     approximate passes.
#
# Neither program consumes the other's outputs, so JAX async dispatch
# lets device execution of the costly oracle overlap the cache passes
# (statically proven by analysis rule J009); results meet again only in
# the *next* iteration's pending buffer.
# ---------------------------------------------------------------------------


class PendingOracle(NamedTuple):
    """In-flight oracle results: dispatched at iteration t, folded at t+1.

    Attributes:
      ids:    (k,) int32 — blocks whose exact oracles were dispatched.
      planes: (k, d+1)   — their oracle planes at the dispatch-time
              (stale) ``w``.
      done:   (k,) bool  — result arrived by the straggler deadline;
              missed blocks fold their batched cached fallback instead
              (``repro.ft``).
      live:   () bool    — False until the first dispatch (iteration 0
              has nothing to fold); gates the whole fold shape-stably.
    """

    ids: jnp.ndarray
    planes: jnp.ndarray
    done: jnp.ndarray
    live: jnp.ndarray


class AsyncMPState(NamedTuple):
    """Pipelined MP-BCFW state: dual/cache state + the pending buffer.

    One pytree so the Solver's checkpoint/resume path (``pack_state`` /
    ``unpack_state`` identity) snapshots the in-flight oracle results
    bit-for-bit alongside the optimizer state.
    """

    mp: MPState
    pending: PendingOracle

    @property
    def inner(self):
        """Passthrough to the wrapped dual state — the Solver's generic
        reads (``state.inner.phi``, ``state.inner.n_exact``) hold for
        every multipass engine state, pipelined or not."""
        return self.mp.inner


def init_pending(n: int, d: int) -> PendingOracle:
    """Empty pending buffer (``live=False``: nothing folds)."""
    return PendingOracle(
        ids=jnp.zeros((n,), jnp.int32),
        planes=jnp.zeros((n, d + 1), jnp.float32),
        done=jnp.zeros((n,), bool),
        live=jnp.zeros((), bool),
    )


def init_async_state(problem: SSVMProblem,
                     cap: Union[int, CacheLayout]) -> AsyncMPState:
    return AsyncMPState(mp=init_mp_state(problem, cap),
                        pending=init_pending(problem.n, problem.d))


def async_oracle_program(oracle, data, phi: jnp.ndarray, cache: PlaneCache,
                         perm: jnp.ndarray, key: Optional[jnp.ndarray],
                         *, lam: float, policies=None):
    """The oracle half of the pipelined iteration.

    Evaluates the exact max-oracle for every block the sampling policy
    schedules out of ``perm``, all at the single stale ``w`` derived from
    the iteration-entry dual iterate ``phi`` — exactly the tau-nice
    parallel-oracle shape of :func:`repro.core.distributed.tau_chunk`,
    lifted to its own dispatch.  Reads only iteration-*entry* state
    (``phi``, ``cache``, ``perm``), never the concurrent cache program's
    outputs.  Returns ``(ids, planes)``.
    """
    w = weights_of(phi, lam)
    ids = perm if policies is None else policies.sampling.schedule(
        cache, perm, key)
    batch = jax.tree_util.tree_map(lambda a: a[ids], data)
    planes = jax.vmap(lambda ex: oracle(w, ex))(batch)
    return ids, planes


@functools.partial(jax.jit, static_argnums=(0,),
                   static_argnames=("lam", "policies"))
def _jit_async_oracle(oracle, data, phi, cache, perm, key, *, lam,
                      policies=None):
    return async_oracle_program(oracle, data, phi, cache, perm, key,
                                lam=lam, policies=policies)


def jit_async_oracle(problem: SSVMProblem, phi, cache, perm, key, *,
                     lam: float, policies=None):
    return _jit_async_oracle(problem.oracle, problem.data, phi, cache,
                             perm, key, lam=lam, policies=policies)


def async_cache_program(mp: MPState, pending: PendingOracle,
                        perms: jnp.ndarray, clock: SlopeClock, *,
                        lam: float, ttl: int, steps: int = 10,
                        run_all: bool = False, policies=None,
                        scatter: str = "per-elem"):
    """The cache half of the pipelined iteration.

    Eviction, the monotone fold-in of the previous iteration's pending
    oracle results (straggler blocks fall back to their best cached plane
    at the *current* ``w``, batched — the ``repro.ft`` path), and the
    slope-ruled approximate multi-pass batch, as one program.  Mirrors
    :func:`outer_iteration` with the exact-pass scan replaced by the
    fold; the slope clock still charges the modeled oracle time
    ``clock.t`` so the continue rule prices passes identically to the
    serial engines.  Returns ``(mp, clock, stats)``.
    """
    from .distributed import fallback_planes, fold_planes

    eviction = None if policies is None else policies.eviction
    occ0 = mp.cache.occupancy                 # before eviction
    mp = begin_iteration(mp, ttl, eviction=eviction)
    occ1 = mp.cache.occupancy                 # after eviction
    # Seed f0 *before* the fold: the fold is this iteration's exact-pass
    # equivalent, so the slope rule's chord must include its gain.
    clock = clock._replace(f0=dual_value(mp.inner.phi, lam))
    w = weights_of(mp.inner.phi, lam)
    fbp, fbs, _ = fallback_planes(mp.cache, pending.ids, w)
    mp = fold_planes(mp, pending.ids, pending.planes, fbp, fbs,
                     pending.done, lam, live=pending.live, scatter=scatter)
    occ2 = mp.cache.occupancy                 # after the fold's inserts
    mp, clock, stats = multi_approx_pass(mp, perms, clock, lam=lam,
                                         steps=steps, run_all=run_all,
                                         policies=policies)
    # Eviction accounting (cf. outer_iteration): the fold inserts one
    # plane per *arrived* block (fallbacks only refresh activity), and
    # only when the pending buffer is live.
    n_inserts = jnp.where(pending.live,
                          jnp.sum(pending.done.astype(jnp.int32)),
                          jnp.zeros((), jnp.int32))
    metrics = stats.metrics._replace(ttl_evicted=occ0 - occ1,
                                     lru_evicted=occ1 + n_inserts - occ2)
    return mp, clock, stats._replace(metrics=metrics)


@functools.partial(jax.jit,
                   static_argnames=("lam", "ttl", "steps", "run_all",
                                    "policies", "scatter"))
def _jit_async_cache(mp, pending, perms, clock, *, lam, ttl, steps,
                     run_all, policies=None, scatter="per-elem"):
    return async_cache_program(mp, pending, perms, clock, lam=lam, ttl=ttl,
                               steps=steps, run_all=run_all,
                               policies=policies, scatter=scatter)


def jit_async_cache(mp: MPState, pending: PendingOracle, perms, clock, *,
                    lam: float, ttl: int, steps: int = 10,
                    run_all: bool = False, policies=None,
                    scatter: str = "per-elem"):
    return _jit_async_cache(mp, pending, perms, clock, lam=lam, ttl=ttl,
                            steps=steps, run_all=run_all, policies=policies,
                            scatter=scatter)
