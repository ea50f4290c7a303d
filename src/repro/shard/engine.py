"""The shard_map MP-BCFW engine: sharded approximate and tau-nice passes.

See the package docstring for the layout and communication pattern.  The
engine owns the compiled programs and their telemetry; it never blocks on
the device except in :meth:`ShardEngine.read` /
:meth:`ShardEngine.read_stats`, so a caller can assert "at most one host
sync per outer iteration" directly off the :class:`~repro.core.selection.
SyncLedger`.

Module-level ``sharded_*`` functions mirror the single-device API
(:func:`repro.core.mpbcfw.multi_approx_pass`, the late
``core.distributed`` host loop) for drop-in use; they cache one
:class:`ShardEngine` per (problem, mesh, lam).  ``ShardEngine`` itself is
the primary API.
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from .. import cache as plane_cache
from ..cache import CacheLayout, PlaneCache
from ..core import distributed, gram as gram_ops, mpbcfw
from ..core.bcfw import line_search_gamma
from ..core.mpbcfw import MPState
from ..core.selection import SyncLedger
from ..core.ssvm import dual_value, weights_of
from ..core.types import (ApproxBatchStats, ObsMetrics, SlopeClock,
                          SSVMProblem)
from . import layout
from .telemetry import CollectiveTrace


def _local_schedule(perm: jnp.ndarray, lo, n_local: int) -> jnp.ndarray:
    """This shard's subsequence of a global visit order, as local ids.

    ``perm`` is a permutation of all ``n`` blocks; exactly ``n_local`` of
    its entries fall into this shard's contiguous id range
    ``[lo, lo + n_local)``.  They are extracted *in visit order* (stable:
    sort the masked positions), so a 1-shard mesh walks exactly ``perm``.
    """
    n = perm.shape[0]
    mask = (perm >= lo) & (perm < lo + n_local)
    pos = jnp.where(mask, jnp.arange(n), n)
    order = jnp.sort(pos)[:n_local]
    return perm[order] - lo


class ShardEngine:
    """Compiled multi-device MP-BCFW passes over one (problem, mesh, lam).

    All state tensors follow :mod:`repro.shard.layout`; use
    :meth:`init_state` (or :meth:`place` on an existing state) before the
    first pass.  Programs are built lazily and cached; telemetry lives in
    ``self.ledger`` (host syncs / dispatches / runtime collectives) and
    ``self.collectives`` (trace-time psum sites per program).
    """

    def __init__(self, problem: SSVMProblem, mesh: Mesh, *, lam: float,
                 axis: str = "data", use_gram: bool = False,
                 gram_steps: int = 10, policies=None):
        self.problem = problem
        self.mesh = mesh
        self.lam = float(lam)
        self.axis = axis
        self.use_gram = bool(use_gram)
        self.gram_steps = int(gram_steps)
        # Optional repro.policy.PolicyBundle (jit-static): swaps the
        # eviction rule, the exact pass's visit schedule, and the
        # approximate-phase stopping rule inside the fused programs.
        self.policies = policies
        self.track_gap = policies is not None and policies.needs_gap
        if self.track_gap and self.use_gram:
            raise ValueError(
                "gap-tracking policies are not supported with the gram "
                "(Sec-3.5) pass body: the multi-step scheme does not "
                "expose per-visit scores to fold into the gap vector")
        self.n_shards = layout.validate_layout(problem.n, mesh, axis)
        self.n_local = problem.n // self.n_shards
        self.ledger = SyncLedger()
        self.collectives = CollectiveTrace()
        self._multi_sm: Dict[bool, callable] = {}   # shard_map'd (unjitted)
        self._multi: Dict[bool, callable] = {}      # standalone jits
        self._epoch_fn = None                       # tau epoch (unjitted)
        self._tau_prog = None                       # standalone jit
        self._outer: Dict[tuple, callable] = {}     # fused outer programs
        self._async_oracle_prog = None              # async oracle program
        self._async_cache_progs: Dict[tuple, callable] = {}
        self._begin = jax.jit(mpbcfw.begin_iteration, static_argnums=(1,))

    # -- state management ---------------------------------------------------

    def init_state(self, cap: int) -> MPState:
        return self.place(mpbcfw.init_mp_state(
            self.problem,
            CacheLayout(cap=cap, gram=self.use_gram, axis=self.axis,
                        track_gap=self.track_gap)))

    def place(self, mp: MPState) -> MPState:
        return layout.place_mp_state(mp, self.mesh, self.axis)

    def begin_iteration(self, mp: MPState, ttl: int) -> MPState:
        self.ledger.dispatched()
        return self._begin(mp, ttl)

    # -- sync points (the only blocking calls) ------------------------------

    def read(self, tree):
        """Fetch any device value(s) to host — one counted sync."""
        return self.ledger.sync(tree)

    def read_stats(self, stats: ApproxBatchStats, extra=None):
        """Fetch multi-pass telemetry (the iteration's single sync) and
        charge the program's runtime collectives to the ledger.

        ``extra`` (optional pytree of device values) rides the *same*
        blocking round-trip — the async driver fetches its overlap
        scalars this way without a second sync.  Returns ``stats`` alone,
        or ``(stats, extra)`` when ``extra`` was given.
        """
        got = self.ledger.sync(stats if extra is None else (stats, extra))
        st = got if extra is None else got[0]
        passes = int(st.passes_run)
        self.ledger.collected(
            self.collectives.count("multi_approx", "setup")
            + passes * self.collectives.count("multi_approx", "pass"),
            nbytes=self.collectives.bytes_of("multi_approx", "setup")
            + passes * self.collectives.bytes_of("multi_approx", "pass"))
        return st if extra is None else got

    @property
    def psums_per_approx_pass(self) -> int:
        """Per-pass collective count of the compiled multi-pass program."""
        return self.collectives.count("multi_approx", "pass")

    @property
    def setup_psums(self) -> int:
        return self.collectives.count("multi_approx", "setup")

    # -- approximate passes -------------------------------------------------

    def _build_multi(self, run_all: bool):
        mesh, axis, lam = self.mesh, self.axis, self.lam
        S, n_local = self.n_shards, self.n_local
        n = self.problem.n
        use_gram, steps = self.use_gram, self.gram_steps
        track_gap, policies = self.track_gap, self.policies
        trace = self.collectives

        def local_prog(mp: MPState, perms, clock: SlopeClock, blk_evt):
            # Runs per shard: mp leaves are the LOCAL slices of the layout
            # (phi_i (n_local, d+1), cache (n_local, cap, .)), O(d) state
            # is replicated.  Exactly one psum per pass, one for setup.
            #
            # ``blk_evt`` is this shard's (n_local, 2) i32 slice of the
            # per-block [ttl_evicted, lru_evicted] counters the fused
            # outer program computes around eviction + the exact epoch
            # (all zeros for a standalone multi-pass program).  Its
            # per-shard partial sums ride the *existing* setup psum as a
            # packed i32 4-vector together with the occupancy counters —
            # the obs drain adds zero collective sites and zero host
            # callbacks (repro.analysis rule J006 + the H-layer budgets
            # re-prove this statically).
            trace.begin("multi_approx")
            lo = jax.lax.axis_index(axis) * n_local
            f_entry = dual_value(mp.inner.phi, lam)
            local_planes = jnp.sum(mp.cache.valid).astype(jnp.int32)
            local_nonempty = jnp.sum(
                jnp.any(mp.cache.valid, axis=1)).astype(jnp.int32)
            evt_local = jnp.sum(blk_evt, axis=0).astype(jnp.int32)
            if track_gap:
                # Gap engines widen the packed setup reduction to a float32
                # 5-vector so the per-shard gap partial rides the same one
                # collective (i32 counts stay exact in f32 far below 2^24);
                # the default engines keep their i32 4-vector bit for bit.
                gap_local = jnp.sum(jnp.where(
                    mp.cache.gap < plane_cache.GAP_UNSEEN,
                    mp.cache.gap, 0.0))
                packed = trace.psum(
                    jnp.stack([local_planes.astype(jnp.float32),
                               local_nonempty.astype(jnp.float32),
                               evt_local[0].astype(jnp.float32),
                               evt_local[1].astype(jnp.float32),
                               gap_local]),
                    axis, tag="setup")
                counts = packed[:4].astype(jnp.int32)
                total_planes = counts[0]
                metrics = ObsMetrics(ttl_evicted=counts[2],
                                     lru_evicted=counts[3],
                                     occupancy=counts[0],
                                     nonempty_blocks=counts[1],
                                     gap_total=packed[4])
            else:
                packed = trace.psum(
                    jnp.stack([local_planes, local_nonempty,
                               evt_local[0], evt_local[1]]),
                    axis, tag="setup")
                total_planes = packed[0]
                metrics = ObsMetrics(ttl_evicted=packed[2],
                                     lru_evicted=packed[3],
                                     occupancy=packed[0],
                                     nonempty_blocks=packed[1])
            cost = (clock.plane_cost
                    * jnp.maximum(total_planes, 1).astype(jnp.float32))
            # Approximate passes never insert/evict planes: the cache
            # tensors (incl. the local Gram blocks in the Sec-3.5
            # configuration — they shard with the blocks, which is why
            # this engine can run the gram variant at all) are loop
            # constants; only last_active is carried.
            planes_c, valid_c = mp.cache.planes, mp.cache.valid
            gram_c = mp.cache.gram

            def step(carry, perm):
                phi, phi_i, last_active, bar, k, gap = carry
                phi_i0 = phi_i  # pass-entry blocks, for damped recombine
                sched = _local_schedule(perm, lo, n_local)

                def body(c, i):
                    phi_run, phi_i, last_active, bar, k, gap = c
                    phi_i_old = phi_i[i]
                    # Local view over the loop-constant cache tensors:
                    # every mutation goes through the repro.cache API,
                    # and only the mutated last_active is carried.
                    view = PlaneCache(planes=planes_c, valid=valid_c,
                                      last_active=last_active)
                    if use_gram:
                        # Sec-3.5 multi-step scheme on the local gram
                        # block: `steps` O(cap) inner updates, same body
                        # as the single-device gram pass.
                        phi_i_new, phi_run, won = \
                            gram_ops.multi_step_block_update(
                                planes_c[i], valid_c[i], gram_c[i],
                                phi_run, phi_i_old, lam, steps)
                        last_active = plane_cache.mark_active_where(
                            view, i, won, mp.outer_it).last_active
                    else:
                        w = weights_of(phi_run, lam)
                        plane, slot, score = plane_cache.approx_oracle(
                            view, i, w)
                        if track_gap:
                            # Same fold-in expression as the single-device
                            # approx_pass body (bitwise on a 1-shard mesh).
                            g = score - (phi_i_old[:-1] @ w
                                         + phi_i_old[-1])
                            gap = gap.at[i].set(jnp.maximum(g, 0.0))
                        gamma = line_search_gamma(phi_run, phi_i_old,
                                                  plane, lam)
                        phi_i_new = (1.0 - gamma) * phi_i_old + gamma * plane
                        phi_run = phi_run + (phi_i_new - phi_i_old)
                        last_active = plane_cache.mark_active(
                            view, i, slot, mp.outer_it).last_active
                    phi_i = phi_i.at[i].set(phi_i_new)
                    kf = k.astype(jnp.float32)
                    bar = (kf / (kf + 2.0)) * bar + (2.0 / (kf + 2.0)) * phi_run
                    # k counts *global* block visits: each local step runs
                    # concurrently with S-1 peers, so advance by S — after
                    # a pass k has moved by n, matching the stored
                    # k_approx += n below (and the sequential schedule on
                    # one shard).
                    return (phi_run, phi_i, last_active, bar, k + S,
                            gap), None

                (phi_run, phi_i, last_active, bar, k, gap), _ = jax.lax.scan(
                    body, (phi, phi_i, last_active, bar, k, gap), sched)
                delta = phi_run - phi
                # THE per-pass collective: dual delta + pmean'd averaging
                # track ride one reduction.
                red = trace.psum(jnp.stack([delta, bar / S]), axis,
                                 tag="pass")
                if S == 1:
                    # psum is exact identity on one shard (red[0] == delta,
                    # so red[0] - delta == 0 elementwise): keep the
                    # collective live but return the bitwise sequential
                    # running phi.
                    phi_new = phi_run + (red[0] - delta)
                else:
                    # Damped (1/S convex-average) recombination.  Each
                    # shard's sequential walk is monotone in F from the
                    # shared stale phi; scaling every block step by 1/S
                    # makes the recombined state the *mean* of the S
                    # per-shard iterates (phi stays == sum_i phi_i, each
                    # phi_i a convex combination), and F is concave, so
                    # F(mean) >= mean F >= F(entry): the sharded pass
                    # never decreases the dual either.  Every shard adds
                    # the same reduced total to the same stale phi, so the
                    # slope-rule scalars below are bitwise equal across
                    # devices and the while_loop trip count cannot
                    # diverge (collective deadlock safety).
                    phi_new = phi + red[0] / S
                    phi_i = phi_i0 + (phi_i - phi_i0) / S
                bar_new = red[1]
                return ((phi_new, phi_i, last_active, bar_new, k, gap),
                        dual_value(phi_new, lam))

            carry0 = (mp.inner.phi, mp.inner.phi_i, mp.cache.last_active,
                      mp.avg.bar_approx, mp.avg.k_approx, mp.cache.gap)
            carry, t_end, stats = mpbcfw.slope_batched_loop(
                carry0, perms, clock, step=step, f_entry=f_entry,
                cost=cost, planes_per_pass=total_planes, run_all=run_all,
                continue_fn=(None if policies is None
                             else policies.oracle.continue_fn))
            trace.commit()
            phi, phi_i, last_active, bar_a, _, gap = carry
            # Block visits per executed pass is n in both configurations;
            # each visit is `steps` approximate oracle calls under the
            # gram scheme, 1 otherwise (matching the single-device
            # accounting: n_approx counts calls, k_approx counts the
            # per-visit averaging updates).
            done_blocks = stats.passes_run * n
            inner = mp.inner._replace(
                phi=phi, phi_i=phi_i,
                n_approx=mp.inner.n_approx
                + done_blocks * (steps if use_gram else 1))
            avg = mp.avg._replace(bar_approx=bar_a,
                                  k_approx=mp.avg.k_approx + done_blocks)
            cache = mp.cache._replace(last_active=last_active, gap=gap)
            return (mp._replace(inner=inner, cache=cache, avg=avg),
                    clock._replace(t=t_end),
                    stats._replace(metrics=metrics))

        mp_specs = layout.mp_state_specs(self.axis, gram=self.use_gram,
                                         track_gap=track_gap)
        clock_specs = SlopeClock(t0=P(), f0=P(), t=P(), plane_cost=P())
        stats_specs = ApproxBatchStats(
            duals=P(None), times=P(None), planes=P(None), ran=P(None),
            passes_run=P(), f_entry=P(), more=P(), ws_total=P(),
            metrics=ObsMetrics(ttl_evicted=P(), lru_evicted=P(),
                               occupancy=P(), nonempty_blocks=P(),
                               gap_total=P() if track_gap else None))
        return jax.shard_map(
            local_prog, mesh=mesh,
            in_specs=(mp_specs, P(None, None), clock_specs, P(axis, None)),
            out_specs=(mp_specs, clock_specs, stats_specs),
            check_vma=False)

    def _multi_stage(self, run_all: bool):
        """The shard_map'd multi-pass callable (traceable, unjitted) —
        shared by the standalone program and the fused outer program."""
        if run_all not in self._multi_sm:
            self._multi_sm[run_all] = self._build_multi(run_all)
        return self._multi_sm[run_all]

    def multi_approx_pass(self, mp: MPState, perms: jnp.ndarray,
                          clock: SlopeClock, *, run_all: bool = False
                          ) -> Tuple[MPState, SlopeClock, ApproxBatchStats]:
        """shard_map twin of :func:`repro.core.mpbcfw.multi_approx_pass`.

        Dispatches without blocking; pair with :meth:`read_stats` for the
        iteration's single host sync.
        """
        if run_all not in self._multi:
            sm = self._multi_stage(run_all)
            n = self.problem.n

            def prog(mp, perms, clock):
                # Standalone multi-pass programs never insert or evict:
                # the per-block eviction counters are identically zero
                # (the fused outer program supplies the real ones).
                return sm(mp, perms, clock, jnp.zeros((n, 2), jnp.int32))

            self._multi[run_all] = jax.jit(prog)
        self.ledger.dispatched()
        return self._multi[run_all](mp, perms, clock)

    def approx_pass(self, mp: MPState, perm: jnp.ndarray) -> MPState:
        """One sharded approximate pass (fixed budget, no stopping rule)."""
        clock = mpbcfw.make_slope_clock(0.0, 0.0, 0.0, 0.0)
        mp, _, _ = self.multi_approx_pass(mp, perm[None], clock,
                                          run_all=True)
        return mp

    # -- tau-nice (exact) pass ----------------------------------------------

    def _build_tau(self):
        mesh, axis, lam = self.mesh, self.axis, self.lam
        oracle = self.problem.oracle
        data_specs = jax.tree_util.tree_map(lambda _: P(),
                                            self.problem.data)

        def local_oracles(data, w, ids_loc):
            # Per shard: tau/S max-oracles at the shared stale w, examples
            # gathered from the replicated data copy — zero communication.
            batch = jax.tree_util.tree_map(lambda a: a[ids_loc], data)
            return jax.vmap(lambda ex: oracle(w, ex))(batch)

        oracle_stage = jax.shard_map(
            local_oracles, mesh=mesh,
            in_specs=(data_specs, P(None), P(axis)),
            out_specs=P(axis, None), check_vma=False)

        def epoch(data, mp: MPState, chunk_ids, done):
            # done=None: no stragglers, so no fallback is scored.  That
            # also keeps the Pallas score-and-select kernel out of this
            # GSPMD-partitioned scan, where a multi-chip mesh cannot
            # partition it.
            def chunk(mp_c, inp):
                ids, ok = inp
                return distributed.tau_chunk(
                    oracle, data, mp_c, ids, ok, lam,
                    oracle_stage=oracle_stage), None

            mp, _ = jax.lax.scan(chunk, mp, (chunk_ids, done))
            return mp

        return epoch

    def _epoch(self):
        """The tau-nice epoch callable (traceable, unjitted) — shared by
        the standalone program and the fused outer program."""
        if self._epoch_fn is None:
            self._epoch_fn = self._build_tau()
        return self._epoch_fn

    def _chunk_args(self, perm: jnp.ndarray, tau: int,
                    done: Optional[jnp.ndarray]):
        n = self.problem.n
        if n % tau:
            raise ValueError(f"n={n} not divisible by tau={tau}")
        if tau % self.n_shards:
            raise ValueError(
                f"tau={tau} not divisible by {self.n_shards} shards")
        chunk_ids = perm.reshape(-1, tau)
        if done is not None:
            done = done.reshape(chunk_ids.shape)
        return chunk_ids, done

    def tau_nice_pass(self, mp: MPState, perm: jnp.ndarray, tau: int,
                      done: Optional[jnp.ndarray] = None) -> MPState:
        """One epoch of tau-nice MP-BCFW as a single fused device program.

        ``perm`` is split into ``n // tau`` chunks; per chunk the tau
        max-oracles run in parallel at the chunk's stale ``w`` (sharded
        over the mesh), stragglers (``done`` False) fall back to their
        cached plane from the batched scoring, and the planes fold in
        sequentially with exact line search — monotone in F per fold.
        Dispatch only; no host sync.
        """
        chunk_ids, done = self._chunk_args(perm, tau, done)
        if self._tau_prog is None:
            self._tau_prog = jax.jit(self._epoch())
        self.ledger.dispatched()
        return self._tau_prog(self.problem.data, mp, chunk_ids, done)

    # -- one outer iteration: one program, one dispatch ---------------------

    def _build_outer(self, run_all: bool, ttl: int, sequential: bool):
        """One fused program for a whole outer iteration: TTL eviction,
        on-device slope-clock seeding, the exact epoch, and the
        shard_map'd approximate batch — a single dispatch boundary.

        ``sequential`` lowers the tau=1, no-straggler epoch to the plain
        sequential exact pass (:func:`repro.core.mpbcfw.exact_pass`):
        semantically identical (a 1-block chunk *is* a sequential BCFW
        step at the current ``w``), it skips the per-chunk fallback
        scoring that tau=1 would never consume, and it traces the same
        scan body as the single-device fused program — which is what
        makes a 1-device-mesh Solver run bit-for-bit equal to ``mpbcfw``.
        """
        multi = self._multi_stage(run_all)
        epoch = self._epoch()
        problem, lam = self.problem, self.lam
        policies = self.policies
        sampled = policies is not None and policies.sampling.needs_key
        if sampled and not sequential:
            raise ValueError(
                "sampling policies need the sequential (tau=1, no "
                "straggler) exact pass: the sampled schedule replaces "
                "the uniform chunk permutation")

        def prog(data, mp: MPState, chunk_ids, done, perms,
                 clock: SlopeClock, key):
            # Per-block working-set sizes around eviction and the exact
            # epoch feed the obs counters.  All three are axis=1
            # reductions — elementwise in the (sharded) block dimension,
            # so GSPMD keeps them shard-local; the only cross-shard
            # reduction is the packed setup psum inside the multi stage.
            sz0 = jnp.sum(mp.cache.valid, axis=1).astype(jnp.int32)
            mp = mpbcfw.begin_iteration(
                mp, ttl,
                eviction=None if policies is None else policies.eviction)
            sz1 = jnp.sum(mp.cache.valid, axis=1).astype(jnp.int32)
            # Seed the slope rule from the on-device dual at iteration
            # entry (eviction never changes phi, hence F).
            clock = clock._replace(f0=dual_value(mp.inner.phi, lam))
            if sampled:
                # Gap-proportional (or any keyed) schedule: k sampled
                # block ids replace the uniform permutation; the exact
                # pass stays the sequential scan body.
                ids = policies.sampling.schedule(
                    mp.cache, chunk_ids.reshape(-1), key)
            else:
                ids = chunk_ids.reshape(-1)
            if sequential:
                prob = SSVMProblem(n=problem.n, d=problem.d, data=data,
                                   oracle=problem.oracle)
                mp = mpbcfw.exact_pass(prob, mp, ids, lam)
            else:
                mp = epoch(data, mp, chunk_ids, done)
            sz2 = jnp.sum(mp.cache.valid, axis=1).astype(jnp.int32)
            # One insert per visited block (every block appears once per
            # epoch; straggler fallbacks — reachable only through direct
            # tau_nice_pass calls, never this fused program — would count
            # as LRU-neutral inserts).  Matches the single-device
            # occ1 + n - occ2 accounting bit for bit.  A sampled schedule
            # visits only its k (distinct) ids, so the per-block insert
            # count is their scatter instead of the all-ones vector.
            if sampled:
                inserted = jnp.zeros((problem.n,), jnp.int32).at[ids].add(1)
                blk_evt = jnp.stack([sz0 - sz1, sz1 + inserted - sz2],
                                    axis=1)
            else:
                blk_evt = jnp.stack([sz0 - sz1, sz1 + 1 - sz2], axis=1)
            out = multi(mp, perms, clock, blk_evt)
            if sampled:
                # gap_sampled is a static property of the schedule shape;
                # stamping it outside shard_map adds no collective.
                mp2, clock2, stats = out
                metrics = stats.metrics._replace(
                    gap_sampled=jnp.asarray(ids.shape[0], jnp.int32))
                out = (mp2, clock2, stats._replace(metrics=metrics))
            return out

        return jax.jit(prog)

    def outer_iteration(self, mp: MPState, perm: jnp.ndarray,
                        approx_perms: jnp.ndarray, clock: SlopeClock, *,
                        tau: int, ttl: int,
                        done: Optional[jnp.ndarray] = None,
                        run_all: bool = False,
                        key: Optional[jnp.ndarray] = None):
        """Eviction + tau-nice exact epoch + slope-ruled approximate
        batch as **one** fused device program (a single dispatch).
        ``clock.f0`` is re-seeded on device from the dual at iteration
        entry; the caller reads the returned stats with
        :meth:`read_stats` — that is the iteration's one and only host
        sync.  ``key`` is the per-iteration PRNG key consumed by keyed
        sampling policies (``None`` otherwise)."""
        chunk_ids, done_arr = self._chunk_args(perm, tau, done)
        sequential = (tau == 1 and done is None)
        cache_key = (bool(run_all), int(ttl), sequential)
        if cache_key not in self._outer:
            self._outer[cache_key] = self._build_outer(run_all, ttl,
                                                       sequential)
        self.ledger.dispatched()
        return self._outer[cache_key](self.problem.data, mp, chunk_ids,
                                      done_arr, approx_perms, clock, key)

    # -- async oracle pipelining (the mpbcfw-shard-async split) --------------

    def _build_async_oracle(self):
        """The oracle half of the pipelined iteration, as its own program.

        The tau-nice oracle stage (``local_oracles`` under ``shard_map``:
        per-shard max-oracles at the shared stale ``w``, examples gathered
        from the replicated data copy) over the *whole* permutation —
        zero collectives, so its per-shard compute is free to overlap the
        cache program's psum-synchronized passes.
        """
        mesh, axis, lam = self.mesh, self.axis, self.lam
        oracle = self.problem.oracle
        data_specs = jax.tree_util.tree_map(lambda _: P(),
                                            self.problem.data)

        def local_oracles(data, w, ids_loc):
            batch = jax.tree_util.tree_map(lambda a: a[ids_loc], data)
            return jax.vmap(lambda ex: oracle(w, ex))(batch)

        oracle_stage = jax.shard_map(
            local_oracles, mesh=mesh,
            in_specs=(data_specs, P(None), P(axis)),
            out_specs=P(axis, None), check_vma=False)

        def shard_async_oracle(data, phi, perm):
            w = weights_of(phi, lam)
            return perm, oracle_stage(data, w, perm)

        return jax.jit(shard_async_oracle)

    def async_oracle_pass(self, phi: jnp.ndarray, perm: jnp.ndarray):
        """Dispatch the next iteration's exact oracles at stale ``phi``.

        Returns ``(ids, planes)`` without blocking; the results fold in
        at the start of the *next* cache program.
        """
        if self._async_oracle_prog is None:
            self._async_oracle_prog = self._build_async_oracle()
        self.ledger.dispatched()
        return self._async_oracle_prog(self.problem.data, phi, perm)

    def _build_async_cache(self, run_all: bool, ttl: int, scatter: str):
        """The cache half: eviction, the monotone fold-in of the pending
        oracle results (GSPMD-level, like the tau epoch's fold), and the
        shard_map'd approximate batch — same per-block eviction
        accounting as the fused outer program, same one-setup-psum +
        one-psum-per-pass collective contract (the fold itself issues no
        explicit collective)."""
        multi = self._multi_stage(run_all)
        lam, policies, n = self.lam, self.policies, self.problem.n

        def shard_async_cache(mp: MPState, pending, perms,
                              clock: SlopeClock):
            sz0 = jnp.sum(mp.cache.valid, axis=1).astype(jnp.int32)
            mp = mpbcfw.begin_iteration(
                mp, ttl,
                eviction=None if policies is None else policies.eviction)
            sz1 = jnp.sum(mp.cache.valid, axis=1).astype(jnp.int32)
            clock = clock._replace(f0=dual_value(mp.inner.phi, lam))
            w = weights_of(mp.inner.phi, lam)
            fbp, fbs, _ = distributed.fallback_planes(mp.cache,
                                                      pending.ids, w)
            mp = distributed.fold_planes(
                mp, pending.ids, pending.planes, fbp, fbs, pending.done,
                lam, live=pending.live, scatter=scatter)
            sz2 = jnp.sum(mp.cache.valid, axis=1).astype(jnp.int32)
            # The fold inserts one plane per *arrived* block (fallbacks
            # only refresh activity); nothing folds while the pending
            # buffer is dead (iteration 0).
            inserted = jnp.where(
                pending.live,
                jnp.zeros((n,), jnp.int32).at[pending.ids].add(
                    pending.done.astype(jnp.int32)),
                jnp.zeros((n,), jnp.int32))
            blk_evt = jnp.stack([sz0 - sz1, sz1 + inserted - sz2], axis=1)
            return multi(mp, perms, clock, blk_evt)

        return jax.jit(shard_async_cache)

    def async_cache_pass(self, mp: MPState, pending, perms,
                         clock: SlopeClock, *, ttl: int,
                         run_all: bool = False,
                         scatter: str = "per-elem"):
        """Dispatch one cache-program iteration (no blocking)."""
        cache_key = (bool(run_all), int(ttl), str(scatter))
        if cache_key not in self._async_cache_progs:
            self._async_cache_progs[cache_key] = self._build_async_cache(
                run_all, ttl, scatter)
        self.ledger.dispatched()
        return self._async_cache_progs[cache_key](mp, pending, perms,
                                                  clock)


# -- module-level API (engine cache) ----------------------------------------

# Identity-keyed LRU of recently used engines.  Bounded: each entry pins a
# problem (data included), a mesh, and compiled programs, so an unbounded
# cache would leak across hyper-parameter sweeps.  Long-lived callers
# should hold a ShardEngine themselves.
_ENGINE_CACHE_SIZE = 8
_ENGINES: "OrderedDict[tuple, ShardEngine]" = OrderedDict()


def _engine(problem: SSVMProblem, mesh: Mesh, lam: float,
            axis: str) -> ShardEngine:
    key = (id(problem.oracle), id(problem.data), id(mesh),
           float(lam),  # repro: allow[R004] host float, cache key only
           axis)
    eng = _ENGINES.get(key)
    if eng is None:
        eng = _ENGINES[key] = ShardEngine(problem, mesh, lam=lam, axis=axis)
    _ENGINES.move_to_end(key)
    while len(_ENGINES) > _ENGINE_CACHE_SIZE:
        _ENGINES.popitem(last=False)
    return eng


def sharded_approx_pass(problem: SSVMProblem, mp: MPState,
                        perm: jnp.ndarray, *, lam: float, mesh: Mesh,
                        axis: str = "data") -> MPState:
    """One approximate pass over all blocks, sharded over ``mesh``."""
    return _engine(problem, mesh, lam, axis).approx_pass(mp, perm)


def sharded_multi_approx_pass(problem: SSVMProblem, mp: MPState,
                              perms: jnp.ndarray, clock: SlopeClock, *,
                              lam: float, mesh: Mesh,
                              run_all: bool = False, axis: str = "data"):
    """Slope-ruled batch of approximate passes, sharded over ``mesh``."""
    return _engine(problem, mesh, lam, axis).multi_approx_pass(
        mp, perms, clock, run_all=run_all)


def sharded_tau_nice_pass(problem: SSVMProblem, mp: MPState,
                          perm: jnp.ndarray, *, lam: float, tau: int,
                          mesh: Mesh, done: Optional[jnp.ndarray] = None,
                          axis: str = "data") -> MPState:
    """One fused tau-nice epoch, oracles sharded over ``mesh``."""
    return _engine(problem, mesh, lam, axis).tau_nice_pass(mp, perm, tau,
                                                           done)
