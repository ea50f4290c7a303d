"""Layer 1: jaxpr-level program-contract checking (no execution).

Every registered engine is instantiated on a canonical tiny problem and
its fused outer-iteration program(s) are traced with
:func:`jax.make_jaxpr` — tracing only, nothing runs.  The checker then
walks the closed jaxpr (recursing into ``jit`` / ``shard_map`` /
``while`` / ``scan`` sub-jaxprs, tracking loop depth) and statically
counts:

  * collective primitives (``psum`` / ``all_gather`` / ``all_to_all`` /
    ``ppermute`` / ...) split into *setup* (loop depth 0: once per fused
    program) vs *per-pass* (inside the pass ``while``/``scan`` loop);
  * host-callback primitives (``pure_callback`` / ``io_callback`` /
    ``debug_callback``) — each is a hidden host sync;
  * ``float64`` avals (the fp32 dual-accumulation discipline) and the
    dtypes of the dual telemetry / accumulator outputs.

The counts are compared against the budgets the engine *declares* on its
:class:`~repro.api.engine.EngineCapabilities`
(``collectives_per_pass`` / ``collectives_setup`` / ``host_callbacks`` /
``accum_dtype``); any mismatch is a finding (rules J001-J005).  Engines
with ``mesh_optional`` capabilities (``mpbcfw-gram``) are traced in both
configurations; the no-mesh program must contain zero collectives.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.extend import core as jex_core

from .findings import Finding

# Primitive-name fragments that identify cross-device communication.
# (Matched as substrings: "psum" also covers the "psum2" primitive
# shard_map emits.  "pbroadcast" is deliberately absent — it is
# shard_map's replication-tracking annotation, not a transfer.)
COLLECTIVE_PRIMS = ("psum", "pmax", "pmin", "all_gather", "all_to_all",
                    "ppermute", "reduce_scatter")
# Host-callback primitives: a hidden host round-trip inside the program.
CALLBACK_PRIMS = ("pure_callback", "io_callback", "debug_callback",
                  "outside_call", "host_callback")
# Primitives whose sub-jaxprs execute once per trip.
LOOP_PRIMS = ("while", "scan")


def _sub_jaxprs(value: Any):
    """Yield jaxprs hiding in one eqn param value (jaxpr, closed jaxpr,
    or (nested) sequences thereof — jit, shard_map, custom_*, cond)."""
    if isinstance(value, jex_core.ClosedJaxpr):
        yield value.jaxpr
    elif isinstance(value, jex_core.Jaxpr):
        yield value
    elif isinstance(value, (list, tuple)):
        for v in value:
            yield from _sub_jaxprs(v)


@dataclass
class ProgramFacts:
    """Static facts of one traced program."""

    setup_collectives: int = 0
    pass_collectives: int = 0
    callbacks: int = 0
    f64_avals: int = 0
    #: primitive name -> count at each placement, for reporting
    detail: Dict[str, int] = field(default_factory=dict)

    @property
    def total_collectives(self) -> int:
        return self.setup_collectives + self.pass_collectives


def count_program(closed) -> ProgramFacts:
    """Walk a (closed or raw) jaxpr and collect the Layer-1 static
    facts."""
    facts = ProgramFacts()

    def visit(eqn, depth: int) -> None:
        name = eqn.primitive.name
        if any(tok in name for tok in CALLBACK_PRIMS):
            facts.callbacks += 1
            facts.detail[f"callback:{name}"] = (
                facts.detail.get(f"callback:{name}", 0) + 1)
        elif any(tok in name for tok in COLLECTIVE_PRIMS):
            where = "pass" if depth > 0 else "setup"
            if depth > 0:
                facts.pass_collectives += 1
            else:
                facts.setup_collectives += 1
            key = f"{where}:{name}"
            facts.detail[key] = facts.detail.get(key, 0) + 1
        for v in eqn.invars:
            _check_aval(v)
        for v in eqn.outvars:
            _check_aval(v)

    def _check_aval(v) -> None:
        aval = getattr(v, "aval", None)
        dtype = getattr(aval, "dtype", None)
        if dtype is not None and dtype == jnp.float64:
            facts.f64_avals += 1

    def walk(jaxpr: jex_core.Jaxpr, depth: int) -> None:
        for eqn in jaxpr.eqns:
            visit(eqn, depth)
            d = depth + 1 if eqn.primitive.name in LOOP_PRIMS else depth
            for v in eqn.params.values():
                for sub in _sub_jaxprs(v):
                    walk(sub, d)

    walk(closed.jaxpr if isinstance(closed, jex_core.ClosedJaxpr)
         else closed, 0)
    return facts


# ---------------------------------------------------------------------------
# Canonical trace cases: every registered engine on a tiny problem


@dataclass
class ProgramTrace:
    """One traced program: the callable + concrete args (reused by the
    HLO layer for lowering) and its jaxpr + output shape tree."""

    name: str                     # "outer" | "continue"
    fn: Callable
    args: Tuple
    jaxpr: jex_core.ClosedJaxpr
    out_shape: Any
    facts: ProgramFacts


@dataclass
class EngineTrace:
    """All traced programs of one engine configuration."""

    engine: str
    label: str                    # e.g. "mpbcfw-gram[mesh]"
    caps: Any                     # EngineCapabilities
    on_mesh: bool
    programs: List[ProgramTrace]

    def expected_budgets(self) -> Tuple[Optional[int], Optional[int]]:
        """(per-pass, setup) collective budget for this configuration.

        Off-mesh programs are single-device by construction: the budget
        is 0 regardless of what the engine declares for its mesh path.
        """
        if not self.on_mesh:
            return 0, 0
        return self.caps.collectives_per_pass, self.caps.collectives_setup


def _tiny_problem():
    """The canonical trace problem — small enough that tracing every
    registered engine stays cheap, structured enough (multiclass, n not
    a multiple of anything interesting) to exercise the real programs."""
    from ..core.oracles import multiclass
    from ..data import synthetic

    x, y = synthetic.usps_like(n=8, f=6, num_classes=3, seed=0)
    return multiclass.make_problem(jnp.asarray(x), jnp.asarray(y), 3)


def _trace_config(name: str, caps, on_mesh: bool):
    from ..api.config import RunConfig

    mesh = None
    if on_mesh:
        from ..launch.mesh import make_data_mesh

        mesh = make_data_mesh(1)
    tau = 1 if (on_mesh and caps.requires_tau) else None
    return RunConfig(lam=0.01, algo=name, cap=4, ttl=10, max_iters=1,
                     approx_batch=2, max_approx_passes=4, seed=0,
                     mesh=mesh, tau=tau)


def trace_engine(name: str, *, on_mesh: Optional[bool] = None,
                 problem=None) -> EngineTrace:
    """Instantiate engine ``name`` on the tiny problem and trace its
    fused program(s) without executing them."""
    from ..api.engine import engine_entry
    from ..core import mpbcfw

    entry = engine_entry(name)
    caps = entry.capabilities
    if on_mesh is None:
        on_mesh = bool(caps.supports_mesh and not caps.mesh_optional)
    problem = _tiny_problem() if problem is None else problem
    cfg = _trace_config(name, caps, on_mesh)
    engine = entry.factory(problem, cfg)
    state = engine.init_state(cfg.cap)
    n = problem.n

    label = f"{name}[{'mesh' if on_mesh else 'single'}]" \
        if caps.mesh_optional else name
    programs: List[ProgramTrace] = []

    def add(prog_name: str, fn: Callable, args: Tuple) -> None:
        jaxpr, out_shape = jax.make_jaxpr(fn, return_shape=True)(*args)
        programs.append(ProgramTrace(prog_name, fn, args, jaxpr, out_shape,
                                     count_program(jaxpr)))

    perm = jnp.arange(n, dtype=jnp.int32) if caps.needs_perm else None
    if caps.multipass:
        k = min(cfg.approx_batch, cfg.max_approx_passes)
        perms = jnp.tile(jnp.arange(n, dtype=jnp.int32), (k, 1))
        clock = mpbcfw.make_slope_clock(0.0, 0.0, 1.0, 1e-3)
        if caps.needs_key:
            # Keyed sampling policies: the per-iteration PRNG key is a
            # traced input of the fused outer program.
            add("outer",
                lambda s, p, ps, c, ky: engine.outer_iteration(
                    s, p, ps, c, ttl=cfg.ttl, key=ky),
                (state, perm, perms, clock, jax.random.PRNGKey(0)))
        else:
            add("outer",
                lambda s, p, ps, c: engine.outer_iteration(s, p, ps, c,
                                                           ttl=cfg.ttl),
                (state, perm, perms, clock))
        add("continue",
            lambda s, ps, c: engine.continue_passes(s, ps, c),
            (state, perms, clock))
    else:
        add("outer",
            lambda s, p: engine.outer_iteration(s, p, None, None,
                                                ttl=cfg.ttl),
            (state, perm))
    return EngineTrace(name, label, caps, on_mesh, programs)


def trace_cases(engines: Optional[Iterable[str]] = None,
                problem=None) -> List[EngineTrace]:
    """Trace every requested engine (default: all registered), tracing
    ``mesh_optional`` engines in both configurations."""
    from ..api.engine import algorithms, engine_entry

    names = list(engines) if engines is not None else algorithms()
    problem = _tiny_problem() if problem is None else problem
    traces: List[EngineTrace] = []
    for name in names:
        caps = engine_entry(name).capabilities
        if caps.mesh_optional:
            traces.append(trace_engine(name, on_mesh=False,
                                       problem=problem))
            traces.append(trace_engine(name, on_mesh=True,
                                       problem=problem))
        else:
            traces.append(trace_engine(name, problem=problem))
    return traces


# ---------------------------------------------------------------------------
# The checks (rules J001-J007)


def _float_leaf_dtypes(tree) -> List[str]:
    leaves = jax.tree_util.tree_leaves(tree)
    return [str(leaf.dtype) for leaf in leaves
            if hasattr(leaf, "dtype")
            and jnp.issubdtype(leaf.dtype, jnp.inexact)]


def check_trace(et: EngineTrace) -> Tuple[List[Finding],
                                          Dict[str, object]]:
    """Compare one traced engine configuration against its declared
    budgets.  Returns (findings, per-engine facts for the report)."""
    findings: List[Finding] = []
    caps = et.caps
    exp_pass, exp_setup = et.expected_budgets()
    facts: Dict[str, object] = {"on_mesh": et.on_mesh,
                                "programs": len(et.programs)}

    if caps.supports_mesh and (caps.collectives_per_pass is None
                               or caps.collectives_setup is None):
        findings.append(Finding(
            "J004", et.label,
            "mesh-capable engine must declare collectives_per_pass and "
            "collectives_setup budgets on its EngineCapabilities"))

    for prog in et.programs:
        f = prog.facts
        where = f"{et.label}:{prog.name}"
        facts[f"{prog.name}_setup"] = f.setup_collectives
        facts[f"{prog.name}_pass"] = f.pass_collectives
        facts[f"{prog.name}_callbacks"] = f.callbacks
        if exp_pass is not None and f.pass_collectives != exp_pass:
            findings.append(Finding(
                "J001", where,
                f"{f.pass_collectives} collective(s) inside the pass "
                f"loop, budget declares {exp_pass} "
                f"(detail: {prog.facts.detail})"))
        if exp_setup is not None and f.setup_collectives != exp_setup:
            findings.append(Finding(
                "J002", where,
                f"{f.setup_collectives} setup collective(s) outside the "
                f"pass loop, budget declares {exp_setup} "
                f"(detail: {prog.facts.detail})"))
        if f.callbacks > caps.host_callbacks:
            findings.append(Finding(
                "J003", where,
                f"{f.callbacks} host-callback primitive(s) in the fused "
                f"program, budget allows {caps.host_callbacks}"))
        if f.f64_avals:
            findings.append(Finding(
                "J005", where,
                f"{f.f64_avals} float64 aval(s) in the traced program "
                f"(accum_dtype={caps.accum_dtype})"))
        findings.extend(_check_accum_dtype(et, prog))
        findings.extend(_check_obs_drain(et, prog))
        findings.extend(_check_policy_contract(et, prog))
        findings.extend(_check_async_pipeline(et, prog))
    return findings, facts


def _check_async_pipeline(et: EngineTrace,
                          prog: ProgramTrace) -> List[Finding]:
    """Rule J009: async engines really are a two-program pipeline.

    For engines declaring ``EngineCapabilities.async_oracle``, the traced
    outer iteration must contain exactly two top-level ``jit`` dispatches
    — one whose name carries ``async_oracle`` (the exact max-oracle over
    the next iteration's blocks) and one carrying ``async_cache`` (the
    eviction + fold-in + approximate batch).  Statically proven on the
    jaxpr:

      * both programs present, exactly once each (J001-J003 already hold
        the *combined* trace to the collective/callback budgets);
      * zero host callbacks and zero collectives inside the oracle
        program — its per-shard compute is what overlaps the cache
        program's psum-synchronized passes, so a collective (or hidden
        host round-trip) inside it would serialize the pipeline;
      * no read-after-write hazard: the cache program must not consume
        any output of the concurrently-dispatched oracle program (and
        vice versa) — a data dependence between the two jit eqns would
        force XLA to run them back to back, silently voiding the
        overlap the ``oracle_overlap`` column reports.
    """
    if not getattr(et.caps, "async_oracle", False) or prog.name != "outer":
        return []
    where = f"{et.label}:{prog.name}"
    out: List[Finding] = []
    oracle_eqns, cache_eqns = [], []
    for eqn in prog.jaxpr.jaxpr.eqns:
        if eqn.primitive.name != "jit":
            continue
        nm = str(eqn.params.get("name", ""))
        if "async_oracle" in nm:
            oracle_eqns.append(eqn)
        elif "async_cache" in nm:
            cache_eqns.append(eqn)
    if len(oracle_eqns) != 1 or len(cache_eqns) != 1:
        out.append(Finding(
            "J009", where,
            f"expected exactly one async_oracle and one async_cache "
            f"jit dispatch at the top level, found "
            f"{len(oracle_eqns)} oracle / {len(cache_eqns)} cache"))
        return out
    o_eqn, c_eqn = oracle_eqns[0], cache_eqns[0]
    for sub in _sub_jaxprs(o_eqn.params.get("jaxpr")):
        f = count_program(sub)
        if f.callbacks or f.total_collectives:
            out.append(Finding(
                "J009", where,
                f"async_oracle program contains {f.callbacks} host "
                f"callback(s) and {f.total_collectives} collective(s) "
                f"(detail: {f.detail}); it must be communication-free "
                "to overlap the cache program"))
    o_out = set(o_eqn.outvars)
    c_in = {v for v in c_eqn.invars if isinstance(v, jex_core.Var)}
    if o_out & c_in:
        out.append(Finding(
            "J009", where,
            f"read-after-write hazard: the async_cache program reads "
            f"{len(o_out & c_in)} output(s) of the concurrent "
            "async_oracle program — the two dispatches would serialize"))
    c_out = set(c_eqn.outvars)
    o_in = {v for v in o_eqn.invars if isinstance(v, jex_core.Var)}
    if c_out & o_in:
        out.append(Finding(
            "J009", where,
            "read-after-write hazard: the async_oracle program reads "
            "output(s) of the async_cache program"))
    return out


def _check_policy_contract(et: EngineTrace,
                           prog: ProgramTrace) -> List[Finding]:
    """Rule J007: the policy layer must not loosen the program contract.

    For engines that declare ``EngineCapabilities.policies``, the
    declared names must resolve in the :mod:`repro.policy` registry to
    exactly one sampling + one eviction + one oracle policy (the static
    shape of a :class:`~repro.policy.PolicyBundle`).  Engines that also
    declare ``needs_key`` run a keyed gap sampler, so their fused outer
    program must drain the gap telemetry — ``stats.metrics.gap_total``
    (() float32) and ``stats.metrics.gap_sampled`` (() int32) — through
    the same stats payload as every other counter.  The budgets
    themselves (1 dispatch, 1 host sync, declared collectives) are the
    J001-J003 checks, which run unchanged on the policy-carrying
    programs traced here.
    """
    caps = et.caps
    if not getattr(caps, "policy_capable", False) or prog.name != "outer":
        return []
    where = f"{et.label}:{prog.name}"
    out: List[Finding] = []
    names = getattr(caps, "policies", None) or ()
    if names:
        from ..api.errors import UnsupportedConfigError
        from ..policy import policy_kind

        kinds: Dict[str, int] = {}
        for nm in names:
            try:
                kind = policy_kind(nm)
            except UnsupportedConfigError:
                out.append(Finding(
                    "J007", where,
                    f"capability-declared policy {nm!r} is not "
                    "registered in the repro.policy registry"))
                continue
            kinds[kind] = kinds.get(kind, 0) + 1
        if not out and (sorted(kinds) != ["eviction", "oracle", "sampling"]
                        or any(v != 1 for v in kinds.values())):
            out.append(Finding(
                "J007", where,
                f"capability-declared policies {tuple(names)} resolve to "
                f"kinds {kinds}; a bundle is exactly one sampling + one "
                "eviction + one oracle policy"))
    if getattr(caps, "needs_key", False):
        stats_shape = prog.out_shape[2]
        metrics = getattr(stats_shape, "metrics", None)
        want = {"gap_total": "float32", "gap_sampled": "int32"}
        for fld, dtype in want.items():
            leaf = getattr(metrics, fld, None) if metrics is not None \
                else None
            if leaf is None:
                out.append(Finding(
                    "J007", where,
                    f"keyed gap engine does not drain "
                    f"stats.metrics.{fld} (gap telemetry must ride the "
                    "existing single host sync)"))
            elif leaf.shape != () or str(leaf.dtype) != dtype:
                out.append(Finding(
                    "J007", where,
                    f"stats.metrics.{fld} is {leaf.dtype}"
                    f"{list(leaf.shape)}, expected a () {dtype} scalar"))
    return out


def _check_obs_drain(et: EngineTrace, prog: ProgramTrace) -> List[Finding]:
    """Rule J006: multipass engines must drain the on-device obs
    counters (:class:`repro.core.types.ObsMetrics`) through the stats
    payload of the fused outer program — the *existing* single
    per-iteration host sync.  Together with the J003 host-callback
    budget (0 for the whole family) this statically proves the obs
    layer adds zero host callbacks and zero extra syncs.

    Only the built-in mpbcfw family is held to this (its engines all
    return ApproxBatchStats); a third-party multipass engine with its
    own stats type is exempt unless it adopts the field.
    """
    if not et.caps.multipass or prog.name != "outer":
        return []
    where = f"{et.label}:{prog.name}"
    stats_shape = prog.out_shape[2]
    if not hasattr(stats_shape, "metrics"):
        return []  # third-party stats payload: not under this contract
    metrics = stats_shape.metrics
    if metrics is None:
        return [Finding(
            "J006", where,
            "stats.metrics is None: the fused outer program does not "
            "accumulate the ObsMetrics counters on device, so the obs "
            "layer would need a second host sync to report them")]
    out: List[Finding] = []
    for fld in ("ttl_evicted", "lru_evicted", "occupancy",
                "nonempty_blocks"):
        leaf = getattr(metrics, fld, None)
        if leaf is None:
            out.append(Finding(
                "J006", where,
                f"stats.metrics.{fld} missing from the drained counters"))
        elif leaf.shape != () or str(leaf.dtype) != "int32":
            out.append(Finding(
                "J006", where,
                f"stats.metrics.{fld} is {leaf.dtype}{list(leaf.shape)}, "
                "expected a () int32 scalar (one fixed-size rider on the "
                "existing sync)"))
    return out


def _check_accum_dtype(et: EngineTrace,
                       prog: ProgramTrace) -> List[Finding]:
    """The dual accumulators and per-pass dual telemetry must carry the
    declared ``accum_dtype`` (fp32 discipline, paper Sec. 2)."""
    want = et.caps.accum_dtype
    where = f"{et.label}:{prog.name}"
    out: List[Finding] = []
    state_shape = prog.out_shape[0]
    stats_shape = prog.out_shape[2]
    if et.caps.multipass:
        phi = state_shape.inner.phi
        if str(phi.dtype) != want:
            out.append(Finding(
                "J005", where,
                f"dual accumulator phi is {phi.dtype}, declared "
                f"accum_dtype is {want}"))
        for fld in ("duals", "f_entry"):
            leaf = getattr(stats_shape, fld, None)
            if leaf is not None and str(leaf.dtype) != want:
                out.append(Finding(
                    "J005", where,
                    f"stats.{fld} telemetry is {leaf.dtype}, declared "
                    f"accum_dtype is {want}"))
    else:
        bad = sorted({d for d in _float_leaf_dtypes(state_shape)
                      if d != want})
        if bad:
            out.append(Finding(
                "J005", where,
                f"float state leaves with dtype(s) {bad}, declared "
                f"accum_dtype is {want}"))
    return out


def check_serve_engines() -> Tuple[List[Finding],
                                   Dict[str, Dict[str, object]]]:
    """Rule J008: the serving round programs are clean single dispatches.

    Every :class:`repro.serve.engine.DecodeEngine` registered with a
    canonical trace case has its per-round batched decode traced (via
    ``engine.program`` — ``jax.make_jaxpr``, nothing runs) and walked
    with the same :func:`count_program` the training engines use.
    Serving is single-device and the batcher performs exactly one
    dispatch + one sync per round, so the program must contain zero
    host-callback primitives, zero collectives, and zero float64 avals —
    otherwise a round would hide extra host traffic the
    :class:`~repro.serve.metrics.ServeLedger` cannot see.
    """
    from ..serve.engine import serve_trace_cases

    findings: List[Finding] = []
    facts: Dict[str, Dict[str, object]] = {}
    for label, engine, batch in serve_trace_cases():
        where = f"serve:{label}"
        jaxpr, _ = engine.program(batch)
        f = count_program(jaxpr)
        facts[where] = {"collectives": f.total_collectives,
                        "callbacks": f.callbacks,
                        "f64_avals": f.f64_avals}
        if f.callbacks:
            findings.append(Finding(
                "J008", where,
                f"{f.callbacks} host-callback primitive(s) in the "
                f"per-round decode program (detail: {f.detail}); a "
                "serving round must be one clean dispatch"))
        if f.total_collectives:
            findings.append(Finding(
                "J008", where,
                f"{f.total_collectives} collective(s) in the per-round "
                f"decode program (detail: {f.detail}); serving is "
                "single-device"))
        if f.f64_avals:
            findings.append(Finding(
                "J008", where,
                f"{f.f64_avals} float64 aval(s) in the per-round decode "
                "program (fp32 serving discipline)"))
    return findings, facts


def run_jaxpr_layer(engines: Optional[Iterable[str]] = None
                    ) -> Tuple[List[Finding], Dict[str, Dict[str, object]],
                               List[EngineTrace]]:
    """Trace + check all requested engines (training engines against
    their declared budgets, serving decode engines against J008).
    Returns the training traces too so the HLO layer can lower the same
    programs without re-tracing."""
    findings: List[Finding] = []
    facts: Dict[str, Dict[str, object]] = {}
    traces = trace_cases(engines)
    for et in traces:
        fs, fx = check_trace(et)
        findings.extend(fs)
        facts[et.label] = fx
    serve_findings, serve_facts = check_serve_engines()
    findings.extend(serve_findings)
    facts.update(serve_facts)
    return findings, facts, traces


# ---------------------------------------------------------------------------
# Registration-time guard


def _registration_guard(entry) -> None:
    caps = entry.capabilities
    if caps.supports_mesh and (caps.collectives_per_pass is None
                               or caps.collectives_setup is None):
        raise ValueError(
            f"engine {entry.name!r}: mesh-capable engines must declare "
            "collectives_per_pass and collectives_setup budgets "
            "(repro.analysis proves them statically; see README "
            "'Program contracts')")


def install_registration_guard() -> Callable:
    """Require collective budgets on every mesh-capable engine at
    registration time (retroactively over already-registered engines).
    Returns the hook so callers can
    :func:`repro.api.engine.remove_registration_hook` it."""
    from ..api.engine import add_registration_hook

    add_registration_hook(_registration_guard, retroactive=True)
    return _registration_guard
