"""Quickstart: train structural SVMs through the public ``repro.api``.

    PYTHONPATH=src python examples/quickstart.py

Three layers, one seam each:

  * **Tasks** are :class:`repro.api.OracleSpec` subclasses (joint feature
    map + loss + loss-augmented decode); ``repro.api.build_problem``
    assembles the max-oracle.  The bundled specs cover the paper's three
    scenarios (multiclass / chain / graph); a custom task is a ~20-line
    spec — demoed below.
  * **Algorithms** are engines in the ``repro.api`` registry
    (``repro.api.algorithms()`` lists them; third parties add their own
    with ``register_engine`` — no core edits):

    ================== ======================================================
    name               what it runs
    ================== ======================================================
    fw                 batch Frank-Wolfe (paper Alg. 1)
    ssg                stochastic subgradient baseline
    bcfw / bcfw-avg    block-coordinate FW (Alg. 2), optionally averaged
    mpbcfw             multi-plane BCFW (Alg. 3) — one fused program per
                       outer iteration (exact pass + slope-ruled
                       approximate batch), one host sync per iteration
    mpbcfw-avg         + two-track weighted averaging (Sec. 3.6)
    mpbcfw-gram        + the Sec-3.5 Gram-cache inner loop (with
                       ``RunConfig.mesh`` it resolves to the sharded
                       gram engine)
    mpbcfw-shard       mpbcfw on a 1-D data mesh (``RunConfig.mesh``):
                       tau-nice exact epoch + sharded approximate batch;
                       bit-for-bit ``mpbcfw`` on a 1-device mesh
    mpbcfw-shard-avg   + averaging
    mpbcfw-shard-tau   explicit tau-nice chunk size via ``RunConfig.tau``
    mpbcfw-shard-gram  the Sec-3.5 scheme on the mesh-sharded plane
                       cache; bit-for-bit ``mpbcfw-gram`` on 1 device
    mpbcfw-gap         gap-proportional exact-pass sampling + gap-aware
                       eviction (the ``repro.policy`` layer); with
                       ``RunConfig.mesh`` it runs sharded
    mpbcfw-async       pipelined MP-BCFW: exact oracle and cache passes
                       run as two concurrently-dispatched programs per
                       iteration, hiding the costly oracle behind the
                       cache work (``TraceRow.oracle_overlap``)
    mpbcfw-shard-async the same two-program pipeline on the data mesh
    ================== ======================================================

  * **The control loop** is :class:`repro.api.Solver`: streaming
    ``iterate()``, gap-tolerance / time-budget stopping, callbacks,
    checkpoint/resume.

Underneath every MP engine sits **the plane cache**
(:mod:`repro.cache`): one :class:`~repro.cache.PlaneCache` pytree owns
the cached planes, validity, activity clock, and (for the gram engines)
the per-block Gram matrices, all declared by a
:class:`~repro.cache.CacheLayout` — see the demo below.
"""
import sys

sys.path.insert(0, "src")

import jax.numpy as jnp  # noqa: E402
import numpy as np       # noqa: E402

from repro.api import (OracleSpec, RunConfig, Solver,  # noqa: E402
                       build_problem)
from repro.core.oracles import multiclass         # noqa: E402
from repro.core.selection import CostModel        # noqa: E402
from repro.data import synthetic                  # noqa: E402
from repro.launch.mesh import make_data_mesh      # noqa: E402


def cm():
    return CostModel(oracle_cost=0.02, plane_cost=1e-4)


def main():
    x, y = synthetic.usps_like(n=300, f=64, num_classes=10, seed=0)
    problem = multiclass.make_problem(jnp.asarray(x), jnp.asarray(y), 10)
    lam = 1.0 / problem.n

    print("== BCFW (baseline) vs MP-BCFW (paper) — same oracle budget ==")
    for algo in ("bcfw", "mpbcfw"):
        res = Solver(problem, RunConfig(lam=lam, algo=algo, max_iters=10,
                                        cap=32, cost_model=cm())).run()
        last = res.trace[-1]
        print(f"{algo:8s}: exact oracle calls {last.n_exact:5d}  "
              f"approx steps {last.n_approx:6d}  "
              f"duality gap {last.gap:.5f}  dual {last.dual:.5f}")

    # -- streaming iteration + gap-tolerance stopping ----------------------
    solver = Solver(problem, RunConfig(lam=lam, algo="mpbcfw", max_iters=50,
                                       cap=32, gap_tol=1e-3,
                                       cost_model=cm()))
    for row in solver.iterate():            # rows stream as iterations run
        # cache_hit_rate / planes_evicted are measured on-device and
        # drained through the same single host sync as the rest of the
        # row (see the README's Observability section).
        print(f"  iter {row.iteration:2d}  gap {row.gap:.6f}  "
              f"hit {row.cache_hit_rate:.2f}  evicted {row.planes_evicted}  "
              f"[{row.dispatches} dispatch / {row.host_syncs} sync]")
    print(f"stopped after {solver.iteration} of 50 iterations "
          f"(gap_tol=1e-3, final gap {solver.trace[-1].gap:.2e})")

    # -- the same run on the mesh-sharded engine ---------------------------
    # (all local devices; on a 1-device host this is bit-for-bit mpbcfw)
    mesh = make_data_mesh()
    res = Solver(problem, RunConfig(lam=lam, algo="mpbcfw-shard", mesh=mesh,
                                    max_iters=10, cap=32,
                                    cost_model=cm())).run()
    last = res.trace[-1]
    syncs = sum(r.host_syncs for r in res.trace)
    disp = sum(r.dispatches for r in res.trace)
    print(f"mpbcfw-shard ({mesh.shape['data']} shard(s)): "
          f"gap {last.gap:.5f}  dual {last.dual:.5f}  "
          f"[{disp} dispatches / {syncs} host syncs over "
          f"{len(res.trace)} iterations]")

    # -- the plane cache is a first-class subsystem ------------------------
    # Every MP engine's working set is a repro.cache.PlaneCache declared
    # by a CacheLayout; gram=True materializes the Sec-3.5 Gram blocks
    # inside the cache (insertions refresh them), which is what lets the
    # sharded gram engine exist — the gram leaf shards with the blocks.
    from repro import cache as plane_cache
    from repro.cache import CacheLayout

    res = Solver(problem, RunConfig(lam=lam, algo="mpbcfw-shard-gram",
                                    mesh=mesh, max_iters=5, cap=32,
                                    cost_model=cm())).run()
    print(f"mpbcfw-shard-gram: gap {res.trace[-1].gap:.5f}  "
          f"ws_mean {res.trace[-1].ws_mean:.1f}  "
          f"[{res.trace[-1].dispatches} dispatch / "
          f"{res.trace[-1].host_syncs} sync per iteration]")
    layout = CacheLayout(cap=8, gram=True, axis="data")
    demo = plane_cache.init(layout, n=4, d=problem.d)
    demo = plane_cache.insert(demo, jnp.asarray(0),
                              jnp.ones((problem.d + 1,)), jnp.asarray(0))
    print(f"PlaneCache: planes {demo.planes.shape}  gram "
          f"{demo.gram.shape}  sizes {np.asarray(plane_cache.sizes(demo))}  "
          f"specs {plane_cache.partition_specs(layout).planes}")

    # -- gap-proportional sampling: the repro.policy layer -----------------
    # mpbcfw-gap swaps the exact pass's uniform epoch for gumbel-top-k
    # sampling proportional to on-device per-block duality-gap estimates
    # (Osokin et al.), spending the costly oracle where the gap still is.
    # gap_frac sets the per-iteration oracle budget; the gap_total /
    # gap_sampled TraceRow columns ride the same single host sync.
    res = Solver(problem, RunConfig(lam=lam, algo="mpbcfw-gap",
                                    max_iters=8, cap=32, gap_frac=0.25,
                                    cost_model=cm())).run()
    for row in res.trace:
        print(f"  mpbcfw-gap iter {row.iteration:2d}  "
              f"sampled {row.gap_sampled:3d}/{problem.n} blocks  "
              f"gap_total {row.gap_total:.5f}  gap {row.gap:.5f}  "
              f"exact calls {row.n_exact:4d}")

    # -- async oracle pipelining: hide the costly oracle -------------------
    # mpbcfw-async dispatches the next blocks' exact oracles (at stale w)
    # and the cache program (eviction + fold-in of the previous pending
    # results + approximate passes) concurrently; the tau-nice fold keeps
    # the dual monotone, and oracle_overlap reports the fraction of the
    # oracle's time hidden behind the cache work.  Under a CostModel the
    # solver credits the hidden span back, so a slow oracle (here 1.0 vs
    # 0.25 per plane-step) makes the pipelined clock visibly faster.
    def slow_cfg(algo):
        # approx_batch >= max_approx_passes keeps the whole approximate
        # batch in one program (no overflow continuations), so the trace
        # shows the bare <= 2 dispatch + 1 sync pipeline contract.
        return RunConfig(lam=lam, algo=algo, max_iters=8, cap=16,
                         max_approx_passes=32, approx_batch=32,
                         cost_model=CostModel(oracle_cost=1.0,
                                              plane_cost=0.25))

    t_fused = Solver(problem, slow_cfg("mpbcfw")).run().trace[-1].time
    res = Solver(problem, slow_cfg("mpbcfw-async")).run()
    ovl = [r.oracle_overlap for r in res.trace]
    print(f"mpbcfw-async: mean oracle_overlap {sum(ovl) / len(ovl):.2f}  "
          f"modeled speedup {t_fused / res.trace[-1].time:.2f}x  "
          f"[{max(r.dispatches for r in res.trace)} dispatches / "
          f"{max(r.host_syncs for r in res.trace)} sync per iteration]")

    # -- record a run: repro.obs (spans + metrics, zero extra syncs) -------
    # The recorder is a Solver callback: it streams JSONL (meta, rows,
    # spans, events, summary), summarized by `python -m repro.obs
    # run.jsonl`.
    import tempfile

    from repro.obs import RunRecorder, summarize_run

    with tempfile.NamedTemporaryFile(suffix=".jsonl") as tmp:
        with RunRecorder(tmp.name) as rec:
            Solver(problem, RunConfig(lam=lam, algo="mpbcfw", max_iters=5,
                                      cap=32, cost_model=cm()),
                   recorder=rec).run()
        s = summarize_run(tmp.name)
        print(f"recorded run: {s['iterations']} iterations  "
              f"approx passes/iter {s['approx_passes_mean']:.1f}  "
              f"host_syncs/iter <= "
              f"{s['contract']['host_syncs_per_iter_max']}")

    # -- train -> serve: the repro.serve path ------------------------------
    # The decoder that defines training defines serving.  Train a chain
    # SSVM, export it as a ServableModel (spec + w, persisted through the
    # checkpoint manifest), and serve mixed-length requests through the
    # bucketed continuous-batching StructuredServer: one jitted program
    # per padding bucket, one dispatch per round (ServeLedger-asserted),
    # bit-for-bit equal to per-example spec.decode.
    from repro.checkpoint.manager import CheckpointManager
    from repro.core.oracles import chain
    from repro.serve import ServableModel, StructuredServer

    Xc, Yc, Mc = synthetic.ocr_like(n=80, f=16, num_labels=8,
                                    mean_len=9, max_len=14, seed=3)
    chain_problem = chain.make_problem(jnp.asarray(Xc), jnp.asarray(Yc),
                                       jnp.asarray(Mc), num_labels=8)
    csolver = Solver(chain_problem,
                     RunConfig(lam=1.0 / chain_problem.n, algo="mpbcfw",
                               max_iters=6, cap=32, cost_model=cm()))
    csolver.run()
    with tempfile.TemporaryDirectory() as ckdir:
        csolver.servable().save(CheckpointManager(ckdir), step=6)
        model = ServableModel.load(CheckpointManager(ckdir))
    requests = [{"x": Xc[i, :int(Mc[i].sum())],
                 "y": Yc[i, :int(Mc[i].sum())],
                 "mask": Mc[i, :int(Mc[i].sum())]} for i in range(16)]
    server = StructuredServer(model, batch_size=8)
    served = server.serve(requests)
    ok = all(np.array_equal(lab, np.asarray(
        model.spec.decode(model.w, {k: jnp.asarray(v)
                                    for k, v in r.items()})))
             for lab, r in zip(served, requests))
    rounds, dispatches, _ = server.ledger.counts()
    print(f"served {len(served)} mixed-length chain requests in {rounds} "
          f"rounds ({dispatches} dispatches)  "
          f"bitwise == per-example decode: {ok}")

    # -- accuracy of the learned (averaged) predictor ----------------------
    res = Solver(problem, RunConfig(lam=lam, algo="mpbcfw-avg",
                                    max_iters=10, cap=32,
                                    cost_model=CostModel())).run()
    w = res.w_avg.reshape(10, -1)
    pred = np.argmax(x @ w.T, axis=1)
    print(f"train accuracy (mpbcfw-avg): {np.mean(pred == y):.3f}")

    # -- a custom task: define an OracleSpec, get every engine for free ----
    class OrdinalSpec(OracleSpec):
        """Ordinal regression, absolute-error loss: labels 0..C-1,
        Delta(y, y') = |y - y'| / (C-1).  Everything the optimizer needs
        is these five methods; build_problem assembles the max-oracle."""

        C = 5

        def dim(self, data):
            return self.C * int(data["x"].shape[-1])

        def truth(self, ex):
            return ex["y"]

        def decode(self, w, ex):
            x, y = ex["x"], ex["y"]
            wc = w.reshape(self.C, x.shape[0])
            delta = jnp.abs(jnp.arange(self.C) - y) / (self.C - 1.0)
            return jnp.argmax(wc @ x + delta)   # loss-augmented argmax

        def features(self, ex, y):
            x = ex["x"]
            return (jnp.zeros((self.C, x.shape[0]), x.dtype)
                    .at[y].add(x)).reshape(-1)

        def loss(self, ex, y):
            return jnp.abs(y - ex["y"]).astype(jnp.float32) / (self.C - 1.0)

    r = np.random.RandomState(1)
    xo = r.randn(200, 16).astype(np.float32)
    yo = np.clip((xo @ r.randn(16) * 0.7 + 2.5), 0, 4.99).astype(np.int32)
    ordinal = build_problem(OrdinalSpec(), {"x": jnp.asarray(xo),
                                            "y": jnp.asarray(yo)})
    res = Solver(ordinal, RunConfig(lam=1.0 / ordinal.n, algo="mpbcfw",
                                    max_iters=10, cap=16,
                                    cost_model=cm())).run()
    wo = res.w.reshape(5, -1)
    mae = np.mean(np.abs(np.argmax(xo @ wo.T, axis=1) - yo))
    print(f"custom OrdinalSpec via mpbcfw: gap {res.trace[-1].gap:.5f}  "
          f"train MAE {mae:.3f}")


if __name__ == "__main__":
    main()
