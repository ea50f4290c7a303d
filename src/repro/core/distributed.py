"""Distributed (tau-nice) MP-BCFW: parallel oracles, sequential combining.

The paper's Alg. 3 is strictly sequential (each block update changes ``w``
before the next oracle call).  At cluster scale the oracle is the expensive
part, so we adapt: sample ``tau`` distinct blocks, evaluate their
max-oracles **in parallel at the same (stale) w**, then fold the returned
planes in **sequentially** with exact line search.  Every returned plane is
a genuine data plane regardless of which ``w`` produced it, so each fold is
monotone in F and all convergence guarantees are kept; staleness only costs
step quality (tau-nice analysis, Lacoste-Julien et al.).  tau =
#data-shards gives linear oracle throughput scaling.

Straggler mitigation (ft/): a ``done`` mask marks oracle results that
arrived in time; missing blocks transparently fall back to their cached
working set — i.e. the paper's approximate oracle doubles as the
fault-tolerance path.  The fallback is *batched*: every sampled block's
cache is scored at the chunk's shared stale ``w`` in one
``repro.cache.approx_oracle_all`` call (one fused score-and-select
launch), not one
launch per missing block.

This module holds the single-host *reference* implementation
(:func:`host_tau_nice_pass`): a Python chunk loop dispatching one oracle
program and one fold program per chunk.  The production path is the fused
device-resident engine in :mod:`repro.shard` (``sharded_tau_nice_pass``),
which runs the whole epoch — oracles under ``shard_map``, batched fallback,
sequential fold-in — as one program with at most one host sync per outer
iteration.  On a 1-device mesh the two are bit-for-bit identical; the
reference exists for exactly that equivalence test and for debugging.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .averaging import update_average
from .bcfw import block_update
from .mpbcfw import MPState
from .types import SSVMProblem
from .ssvm import weights_of
from .. import cache as plane_cache


def gather_examples(problem: SSVMProblem, block_ids: jnp.ndarray):
    return jax.tree_util.tree_map(lambda a: a[block_ids], problem.data)


def parallel_oracles(problem: SSVMProblem, w: jnp.ndarray,
                     block_ids: jnp.ndarray,
                     mesh: Optional[Mesh] = None,
                     data_axis: str = "data") -> jnp.ndarray:
    """Evaluate tau oracles at a shared w.  (tau, d+1) planes.

    With a mesh, the example batch is sharded over ``data_axis`` and ``w``
    is replicated; each shard runs its oracles locally with zero
    communication (the fold-in afterwards is O(tau d) on the host path).
    """
    batch = gather_examples(problem, block_ids)
    fn = jax.vmap(lambda ex: problem.oracle(w, ex))
    if mesh is None:
        return fn(batch)
    in_shardings = (
        jax.tree_util.tree_map(
            lambda _: NamedSharding(mesh, P(data_axis)), batch),
        NamedSharding(mesh, P()),
    )
    out_shardings = NamedSharding(mesh, P(data_axis))
    return jax.jit(lambda b, w: jax.vmap(lambda ex: problem.oracle(w, ex))(b),
                   in_shardings=in_shardings,
                   out_shardings=out_shardings)(batch, w)


def fallback_planes(ws, block_ids: jnp.ndarray, w: jnp.ndarray):
    """Best cached plane of every sampled block at one shared stale ``w``.

    Returns ``(planes (tau, d+1), slots (tau,), scores (tau,))`` — the
    tau-nice straggler fallback for a whole chunk in one batched
    ``repro.cache.approx_oracle_all`` scoring call over the gathered
    sub-cache.  Blocks with an empty cache get the zero (ground-truth)
    plane, which still yields a valid monotone fold step.  Re-exported as
    ``repro.ft.fallback_planes`` (the fault-tolerance API surface).
    """
    return plane_cache.approx_oracle_all(plane_cache.gather(ws, block_ids), w)


def fold_planes(mp: MPState, block_ids: jnp.ndarray, planes: jnp.ndarray,
                fb_planes: jnp.ndarray, fb_slots: jnp.ndarray,
                done: jnp.ndarray, lam: float, *,
                live: Optional[jnp.ndarray] = None,
                scatter: str = "per-elem") -> MPState:
    """Sequentially fold tau candidate planes into the dual state.

    ``done[b]`` False means block b's oracle result is missing (straggler /
    failure): the block's *precomputed* fallback — its best cached plane at
    the chunk's shared stale ``w``, from ``repro.cache.approx_oracle_all`` over
    the gathered sub-cache — is folded instead.  Folding is a cheap
    O(tau d) scan; each step uses exact line search at the *current* phi,
    hence monotone in F no matter which ``w`` produced the candidate.

    ``live`` is an optional ``()`` bool gating the whole fold: ``False``
    returns ``mp`` unchanged (shape-stably — the async pipeline's first
    iteration has no pending oracle results yet).

    ``scatter`` picks the cache/``phi_i`` update strategy:

      * ``"per-elem"`` — dynamic per-element scatters into the full
        arrays from inside the scan (the original path);
      * ``"chunked"`` — gather the sampled blocks' cache rows and
        ``phi_i`` rows up front, fold with *local* indices, scatter each
        sub-array back once after the scan.  Bit-identical for distinct
        ``block_ids`` (tau-nice chunks and async pipelines fold
        permutation slices, so ids are always distinct); on a sharded
        cache this trades tau dynamic-update-slices for one gather + one
        scatter per chunk (the ROADMAP fold-in question, measured by
        ``benchmarks/async_bench.py``).
    """
    if scatter not in ("per-elem", "chunked"):
        raise ValueError(f"fold_planes: unknown scatter strategy "
                         f"{scatter!r} (use 'per-elem' or 'chunked')")
    chunked = scatter == "chunked"
    if chunked:
        ws0 = plane_cache.gather(mp.cache, block_ids)
        st0 = mp.inner._replace(phi_i=mp.inner.phi_i[block_ids])
        idx = jnp.arange(block_ids.shape[0], dtype=block_ids.dtype)
    else:
        ws0, st0, idx = mp.cache, mp.inner, block_ids

    def body(carry, inp):
        st, ws, av = carry
        i, plane, fbp, fbs, ok = inp
        phi_hat = jnp.where(ok, plane, fbp)
        st, _ = block_update(st, i, phi_hat, lam)
        st = st._replace(n_exact=st.n_exact + ok.astype(jnp.int32),
                         n_approx=st.n_approx + (~ok).astype(jnp.int32))
        # Cache the fresh plane; on fallback just refresh activity.
        ws_new = plane_cache.insert(ws, i, phi_hat, mp.outer_it)
        ws_fb = plane_cache.mark_active(ws, i, fbs, mp.outer_it)
        ws = jax.tree_util.tree_map(
            lambda a, b: jnp.where(ok, a, b), ws_new, ws_fb)
        av = update_average(av, st.phi, exact=True)
        return (st, ws, av), None

    (inner, ws, avg), _ = jax.lax.scan(
        body, (st0, ws0, mp.avg),
        (idx, planes, fb_planes, fb_slots, done))
    if chunked:
        inner = inner._replace(
            phi_i=mp.inner.phi_i.at[block_ids].set(inner.phi_i))
        ws = jax.tree_util.tree_map(
            lambda full, sub: full.at[block_ids].set(sub), mp.cache, ws)
    out = mp._replace(inner=inner, cache=ws, avg=avg)
    if live is None:
        return out
    return jax.tree_util.tree_map(
        lambda a, b: jnp.where(live, a, b), out, mp)


@functools.partial(jax.jit, static_argnames=("lam", "scatter"))
def jit_fold_planes(mp: MPState, block_ids, planes, fb_planes, fb_slots,
                    done, *, lam: float, scatter: str = "per-elem"):
    return fold_planes(mp, block_ids, planes, fb_planes, fb_slots, done,
                       lam, scatter=scatter)


def tau_chunk(oracle, data, mp: MPState, ids: jnp.ndarray,
              ok: Optional[jnp.ndarray], lam: float, oracle_stage=None,
              scatter: str = "per-elem") -> MPState:
    """One tau-nice chunk: parallel oracles at the chunk's stale ``w``,
    batched cached fallback at the same ``w``, sequential fold-in.

    ``ok=None`` means every oracle result arrived: no fallback is scored,
    and the fold is the one an all-true mask gives, bit for bit.

    This is the shared chunk body: the host reference jits it once per
    chunk shape and loops on the host; the :mod:`repro.shard` engine scans
    it inside one fused epoch program, passing its ``shard_map``'d oracle
    sharding as ``oracle_stage(data, w, ids) -> (tau, d+1)``.  Keeping one
    definition is what makes the two paths bit-for-bit comparable on a
    1-device mesh.
    """
    w = weights_of(mp.inner.phi, lam)
    if oracle_stage is None:
        batch = jax.tree_util.tree_map(lambda a: a[ids], data)
        planes = jax.vmap(lambda ex: oracle(w, ex))(batch)
    else:
        planes = oracle_stage(data, w, ids)
    if ok is None:
        ok = jnp.ones(ids.shape, bool)
        fbp, fbs = planes, jnp.zeros(ids.shape, jnp.int32)
    else:
        fbp, fbs, _ = fallback_planes(mp.cache, ids, w)
    return fold_planes(mp, ids, planes, fbp, fbs, ok, lam, scatter=scatter)


@functools.partial(jax.jit, static_argnums=(0,), static_argnames=("lam",))
def _jit_tau_chunk(oracle, data, mp, ids, ok, *, lam: float):
    return tau_chunk(oracle, data, mp, ids, ok, lam)


def host_tau_nice_pass(problem: SSVMProblem, mp: MPState, perm: jnp.ndarray,
                       lam: float, tau: int,
                       done: Optional[jnp.ndarray] = None) -> MPState:
    """Single-host reference for one tau-nice epoch over ``perm``.

    A Python loop over ``n // tau`` chunks, each dispatching one jitted
    :func:`tau_chunk` program — i.e. O(n/tau) dispatches per epoch.
    Semantically identical to :func:`repro.shard.engine`'s fused
    ``sharded_tau_nice_pass`` (which runs the whole epoch as one device
    program); kept as the comparison oracle for its equivalence tests and
    as a mesh-free debugging path.
    """
    n = perm.shape[0]
    assert n % tau == 0, "perm length must be divisible by tau"
    for c in range(n // tau):
        ids = perm[c * tau:(c + 1) * tau]
        ok = None if done is None else done[c]
        mp = _jit_tau_chunk(problem.oracle, problem.data, mp, ids, ok,
                            lam=lam)
    return mp


def tau_nice_pass(*args, **kwargs):
    """Removed host chunk loop — kept only to fail loudly with directions."""
    raise RuntimeError(
        "repro.core.distributed.tau_nice_pass was removed: the host chunk "
        "loop paid one dispatch per chunk and scored straggler fallbacks "
        "one block at a time.  Use repro.shard.sharded_tau_nice_pass (the "
        "fused shard_map engine; one device program per epoch, batched "
        "fallback, <=1 host sync per outer iteration) or, for mesh-free "
        "debugging, repro.core.distributed.host_tau_nice_pass.")
