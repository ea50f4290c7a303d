#!/usr/bin/env python3
"""Run the system's main path once on a TPU and check what comes out.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # the mesh-sharded trainer, 4 chips

One chip: train MP-BCFW (``repro.api.Solver``, ``algo="mpbcfw"``) on the
paper's OCR chain task at its published size (n=6877, f=128, 26 labels,
d=4004, cap=64; data generated from a fixed seed), check that the dual
never falls and the duality gap shrinks, check that the fused program the
engine dispatches holds a Pallas kernel and that the kernels agree with
their ``repro.kernels.ref`` references on the chip, run one approximate
pass of the trained cache as the ``approx_block_steps`` kernel against a
float64 replay on the host, then serve 64 OCR
examples through ``StructuredServer`` and check every labeling against the
per-example ``spec.decode``.

``--four-chips``: HorseSeg at its published size (n=2376, which divides
by 4) trained with ``mpbcfw-shard`` on a 4-device data mesh, against
``mpbcfw`` on one chip; no other phase runs.

Every check prints ``ok`` or ``FAIL``.  When any check fails the script
exits 1; without a TPU it exits 2 and names the platform it found.  Only
when every check passed is the last line of standard output the JSON
object ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

CAP = 64              # RunConfig's default plane-cache capacity
ITERS = 10            # OCR outer iterations: the gap of early BCFW
#                       iterations is not monotone, so a 3-iteration run
#                       can end above its first gap
FOUR_CHIP_ITERS = 3   # HorseSeg iterations per run with --four-chips (an
#                       mpbcfw-shard iteration takes ~21 s there)
APPROX_PASSES = 5     # approximate passes allowed per outer iteration
# The dual is float32 state updated block by block, so near the optimum
# rounding alone moves it by a few ulps per step, and by ~sqrt(n) ulps
# over a pass: a fall of less than this fraction of |dual| is rounding.
DUAL_RTOL = 1e-5
SERVE_REQUESTS = 64
SERVE_BATCH = 8
# Plane scores vs the float32 reference, relative to sum_j |p_j w_j| + |b|:
# room for bf16 rounding of the operands inside the MXU (2^-8), far below
# what a dropped or misplaced tile would cost.
SCORE_RTOL = 1e-2
# One approximate pass over this many blocks of the trained cache, as the
# approx_block_steps kernel, against a float64 replay on the host: the
# kernel scores in float32 on the VPU, so only rounding separates them.
APPROX_STEPS = 512
APPROX_RTOL = 1e-4


class Checks:
    """Prints every check and remembers the ones that failed."""

    def __init__(self):
        self.failed = []

    def __call__(self, ok: bool, what: str) -> None:
        print(f"{'ok  ' if ok else 'FAIL'} {what}", flush=True)
        if not ok:
            self.failed.append(what)


def build(sc, label: str):
    """The scenario's problem, data generated from its fixed seed."""
    import jax

    from repro.trainer.ssvm_head import build_problem

    t0 = time.perf_counter()
    problem = build_problem(sc)
    jax.block_until_ready(problem.data)
    print(f"[{label}] n={problem.n} d={problem.d} "
          f"data_s={time.perf_counter() - t0!r}", flush=True)
    return problem


def train(problem, check: Checks, label: str, iters: int, *,
          algo: str = "mpbcfw", mesh=None):
    """Train ``iters`` outer iterations in wall-clock mode, print the
    trace and check that the dual never falls.  Returns ``(solver, rows,
    collectives)`` with the ledger's collective count per iteration."""
    import numpy as np

    from repro.api import RunConfig, Solver
    from repro.core.ssvm import dual_value

    t0 = time.perf_counter()
    cfg = RunConfig(lam=1.0 / problem.n, algo=algo, cap=CAP,
                    max_iters=iters, max_approx_passes=APPROX_PASSES,
                    mesh=mesh)
    solver = Solver(problem, cfg)
    dual0 = float(dual_value(solver.state.inner.phi, cfg.lam))
    print(f"[{label}] cap={cfg.cap} setup_s={time.perf_counter() - t0!r} "
          f"dual0={dual0!r}", flush=True)
    ledger = solver.engine.ledger
    rows, collectives = [], []
    t_prev, wall0 = 0.0, time.perf_counter()
    coll = ledger.collectives
    for row in solver.iterate():
        rows.append(row)
        collectives.append(ledger.collectives - coll)
        coll = ledger.collectives
        what = ("first_iter_s (compile included)" if row.iteration == 0
                else "iter_s")
        # The same dual in float64 on the host: how far the device's
        # float32 evaluation is from it.
        phi = np.asarray(solver.state.inner.phi, np.float64)
        dual64 = float(phi[-1] - phi[:-1] @ phi[:-1] / (2.0 * cfg.lam))
        print(f"[{label}] iter {row.iteration} dual {row.dual!r} "
              f"(float64 {dual64!r}) gap {row.gap!r} n_exact {row.n_exact} "
              f"n_approx {row.n_approx} approx_passes {row.approx_passes} "
              f"{what} {row.time - t_prev!r} "
              f"wall_s (with evaluation) {time.perf_counter() - wall0!r}",
              flush=True)
        t_prev = row.time
    duals = [dual0] + [r.dual for r in rows]
    check(len(rows) == iters
          and all(b >= a - DUAL_RTOL * abs(a)
                  for a, b in zip(duals, duals[1:])),
          f"[{label}] dual never decreases (beyond {DUAL_RTOL} relative): "
          f"{duals}")
    return solver, rows, collectives


def print_memory(label: str) -> None:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    print(f"[{label}] peak_bytes_in_use {stats.get('peak_bytes_in_use')} "
          f"bytes_limit {stats.get('bytes_limit')}", flush=True)


def check_dispatched_program(solver, check: Checks, label: str) -> None:
    """Lower the engine's fused outer iteration for the trained state's
    shapes and look for the Pallas kernel in it."""
    import jax
    import jax.numpy as jnp

    from repro.core import mpbcfw

    n, engine = solver.problem.n, solver.engine
    perm = jnp.arange(n, dtype=jnp.int32)
    perms = jnp.zeros((APPROX_PASSES, n), jnp.int32)
    clock = mpbcfw.make_slope_clock(0.0, 0.0, 1.0, 1e-3)
    text = jax.jit(
        lambda s, p, ps, c: engine.outer_iteration(
            s, p, ps, c, ttl=solver.cfg.ttl)).lower(
        solver.state, perm, perms, clock).as_text()
    check("tpu_custom_call" in text,
          f"[{label}] the dispatched fused program holds tpu_custom_call")


def check_kernels(solver, check: Checks, label: str) -> None:
    """Kernels against their references on the trained run's own data:
    ``plane_scores`` at (cap, d), batched Viterbi at serving batches."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.ssvm import weights_of
    from repro.kernels import ops, ref, viterbi

    state, lam = solver.state, solver.cfg.lam
    w = weights_of(state.inner.phi, lam)
    # Slot 0 of the first CAP blocks: each a plane the exact oracle
    # returned in the first iteration.
    planes = state.cache.planes[:CAP, 0]
    p, b = planes[:, :-1], planes[:, -1]
    got = ops.plane_scores(p, w, b)
    with jax.default_matmul_precision("highest"):
        want = ref.plane_scores_ref(p, w, b)
        scale = jnp.abs(p) @ jnp.abs(w) + jnp.abs(b)
    err = float(jnp.max(jnp.abs(got - want)) / jnp.max(scale))
    check(err <= SCORE_RTOL,
          f"[{label}] plane_scores {tuple(p.shape)} vs reference: "
          f"max error / score scale {err!r} <= {SCORE_RTOL}")

    spec, data = solver.problem.spec, solver.problem.data
    C, f = spec.num_labels, data["x"].shape[-1]
    wu, wp = w[: C * f].reshape(C, f), w[C * f:].reshape(C, C)
    for batch in (SERVE_BATCH, 32):
        x, y, m = (data[k][:batch] for k in ("x", "y", "mask"))
        length = jnp.maximum(jnp.sum(m, axis=1), 1)[:, None, None]
        unary = (jnp.einsum("blf,cf->blc", x, wu)
                 + (1.0 - jax.nn.one_hot(y, C)) / length)
        got = np.asarray(ops.viterbi_decode_batch(unary, wp, m))
        want = np.asarray(viterbi.viterbi_decode_batch(
            unary, wp, m, step_fn=ref.viterbi_step_ref))
        check(np.array_equal(got, want),
              f"[{label}] viterbi_decode_batch {tuple(unary.shape)} labels "
              f"equal the reference ({int(np.sum(got != want))} differ)")


def replay_approx_pass(planes, valid, phi, phi_i, perm, lam):
    """The approximate pass in float64 on the host over blocks ``perm``
    (rows of ``planes``, ``valid`` and ``phi_i``, indexed by position in
    ``perm``'s distinct blocks): returns ``(phi, phi_i, slots)``."""
    import numpy as np

    phi, phi_i = phi.astype(np.float64), phi_i.astype(np.float64)
    slots = []
    for i in perm:
        w = -phi[:-1] / lam
        scores = np.where(valid[i], planes[i, :, :-1] @ w + planes[i, :, -1],
                          -np.inf)
        slot = int(np.argmax(scores))
        hat = planes[i, slot] if valid[i].any() else np.zeros_like(phi)
        diff = phi_i[i] - hat
        den = diff[:-1] @ diff[:-1]
        gamma = (np.clip((diff[:-1] @ phi[:-1] - lam * diff[-1]) / den, 0, 1)
                 if den > 0 else 0.0)
        new = (1 - gamma) * phi_i[i] + gamma * hat
        phi, phi_i[i] = phi + new - phi_i[i], new
        slots.append(slot)
    return phi, phi_i, slots


def check_approx_kernel(solver, check: Checks, label: str) -> None:
    """One approximate pass over ``APPROX_STEPS`` blocks of the trained
    state (one block taken twice in a row) through ``approx_pass``,
    which runs the ``approx_block_steps`` kernel on the chip, against
    :func:`replay_approx_pass`."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import mpbcfw

    mp, lam = solver.state, solver.cfg.lam
    perm = jax.random.permutation(jax.random.PRNGKey(7), solver.problem.n)
    perm = perm[:APPROX_STEPS].at[1].set(perm[0]).astype(jnp.int32)
    check(mpbcfw.approx_kernel_runs(mp.cache, APPROX_STEPS),
          f"[{label}] approximate passes run the approx_block_steps kernel")
    stamp = jnp.asarray(1 << 30, jnp.int32)
    phi, rows, stamped = jax.jit(
        lambda mp, p: (lambda s: (s.inner.phi, s.inner.phi_i[p],
                                  s.cache.last_active[p] == stamp))(
            mpbcfw.approx_pass(None, mp._replace(outer_it=stamp), p, lam)))(
        mp, perm)
    blocks, pos = np.unique(np.asarray(perm), return_inverse=True)
    idx = jnp.asarray(blocks)
    want_phi, want_rows, slots = replay_approx_pass(
        np.asarray(mp.cache.planes[idx]), np.asarray(mp.cache.valid[idx]),
        np.asarray(mp.inner.phi), np.asarray(mp.inner.phi_i[idx]), pos, lam)
    chosen = np.zeros((len(blocks), mp.cache.valid.shape[1]), bool)
    chosen[pos, slots] = True
    err = max(float(np.max(np.abs(np.asarray(a, np.float64) - b))
                    / np.max(np.abs(b)))
              for a, b in ((phi, want_phi), (rows, want_rows[pos])))
    differ = int(np.sum(np.asarray(stamped)[np.unique(pos, True)[1]]
                        != chosen))
    check(err <= APPROX_RTOL and differ == 0,
          f"[{label}] approx_block_steps over {APPROX_STEPS} blocks vs a "
          f"float64 replay: max error / scale {err!r} <= {APPROX_RTOL}, "
          f"{differ} chosen slots differ")


def serve(solver, check: Checks, label: str) -> None:
    """Serve the first ``SERVE_REQUESTS`` examples at their true lengths
    and compare each labeling with the per-example decode."""
    import jax.numpy as jnp
    import numpy as np

    from repro.serve import StructuredServer

    model = solver.servable()
    server = StructuredServer(model, batch_size=SERVE_BATCH)
    X, Y, M = (np.asarray(solver.problem.data[k][:SERVE_REQUESTS])
               for k in ("x", "y", "mask"))
    requests = []
    for x, y, m in zip(X, Y, M):
        L = int(m.sum())
        requests.append({"x": x[:L], "y": y[:L], "mask": m[:L]})
    t0 = time.perf_counter()
    served = server.serve(requests)
    serve_s = time.perf_counter() - t0
    rounds, dispatches, syncs = server.ledger.counts()
    print(f"[{label}] served {len(served)} requests in {rounds} rounds "
          f"({dispatches} dispatches, {syncs} syncs), serve_s {serve_s!r} "
          "(compile included)", flush=True)
    differ = 0
    for ex, labels in zip(requests, served):
        want = np.asarray(model.decode(
            {k: jnp.asarray(v) for k, v in ex.items()}))
        differ += int(not np.array_equal(labels, want))
    check(len(served) == SERVE_REQUESTS and differ == 0,
          f"[{label}] served labelings equal the per-example decode "
          f"({differ} of {len(served)} differ)")


def one_chip(sc, check: Checks) -> None:
    label = f"{sc.name} mpbcfw"
    solver, rows, _ = train(build(sc, label), check, label, ITERS)
    check(rows[-1].gap < rows[0].gap,
          f"[{label}] gap falls: first {rows[0].gap!r} "
          f"last {rows[-1].gap!r}")
    print_memory(label)
    check_dispatched_program(solver, check, label)
    check_kernels(solver, check, label)
    check_approx_kernel(solver, check, label)
    serve(solver, check, label)


def four_chips(sc, check: Checks) -> None:
    import jax

    from repro.launch.mesh import make_data_mesh

    check(len(jax.devices()) >= 4, f"4 devices present: {jax.devices()}")
    label = f"{sc.name} mpbcfw-shard x4"
    problem = build(sc, sc.name)
    solver, rows, collectives = train(problem, check, label,
                                      FOUR_CHIP_ITERS, algo="mpbcfw-shard",
                                      mesh=make_data_mesh(4))
    shards = solver.state.cache.planes.addressable_shards
    n = solver.problem.n
    check(len({s.device for s in shards}) == 4
          and all(s.data.shape[0] == n // 4 for s in shards),
          f"[{label}] plane cache spread over 4 devices, n/4 blocks each: "
          f"{[(str(s.device), s.data.shape) for s in shards]}")
    caps = solver.caps
    want = [caps.collectives_setup + r.approx_passes
            * caps.collectives_per_pass for r in rows]
    check(collectives == want,
          f"[{label}] ledger psums per iteration {collectives} == "
          f"declared budget {want}")
    _, rows1, _ = train(problem, check, f"{sc.name} mpbcfw x1",
                        FOUR_CHIP_ITERS)
    print_memory(label)
    for r4, r1 in zip(rows, rows1):
        print(f"[{sc.name} x4 vs x1] iter {r4.iteration} "
              f"dual {r4.dual!r} vs {r1.dual!r} gap {r4.gap!r} vs "
              f"{r1.gap!r}", flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run mpbcfw-shard on 4 chips against mpbcfw on "
                         "one, and nothing else")
    args = ap.parse_args()

    from repro.launch.compile_cache import setup_compile_cache

    print(f"compile cache: {setup_compile_cache()}", flush=True)
    import jax

    from repro.configs import paper

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform "
              f"{dev.platform!r} ({dev.device_kind})", file=sys.stderr)
        return 2
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}",
          flush=True)
    check = Checks()
    if args.four_chips:
        four_chips(paper.HORSESEG, check)
    else:
        one_chip(paper.OCR, check)
    if check.failed:
        print(f"chip_smoke: {len(check.failed)} check(s) failed",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
