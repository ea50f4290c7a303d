"""End-to-end training driver (the ``--arch`` entry point).

Runs real steps on the available devices (CPU here, a pod in production):
data pipeline -> sharded train_step -> checkpoint/restart -> metrics.
``--trainer ssvm`` switches the loss/optimizer to the paper's MP-BCFW on a
structured (chain-CRF) head over backbone features — the integration of
the paper's technique as a first-class trainer mode.

Examples
--------
  # ~100M-param LM for a few hundred steps on CPU (examples/lm_train.py
  # wraps this):
  python -m repro.launch.train --arch qwen2-0.5b --reduced --steps 300

  # MP-BCFW structured-head training:
  python -m repro.launch.train --trainer ssvm --scenario ocr --iters 20
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.data.lm import DataConfig, Prefetcher, TokenDataset
from repro.ft import RestartManager
from repro.launch.mesh import make_host_mesh
from repro.models import common, registry
from repro.optim import AdamWConfig, adamw_init, adamw_update, \
    cosine_schedule


def train_lm(arch: str, steps: int, batch_size: int, seq_len: int,
             reduced: bool, ckpt_dir: str | None, save_every: int,
             log_every: int = 10, target_params: int = 0) -> dict:
    cfg = configs.reduced_config(arch) if reduced else configs.get_config(arch)
    if target_params:
        cfg = scale_to_params(cfg, target_params)
    specs = registry.param_specs(cfg)
    ocfg = AdamWConfig(lr=3e-4)
    mesh = make_host_mesh()
    del mesh  # single-device here; the dry-run exercises the pod meshes

    def init_fn():
        params = common.init_params(specs, jax.random.PRNGKey(0))
        return {"params": params, "opt": adamw_init(params, ocfg)}

    rm = RestartManager(ckpt_dir, save_every) if ckpt_dir else None
    if rm is not None:
        state, start_step = rm.resume_or_init(init_fn)
    else:
        state, start_step = init_fn(), 0

    data = TokenDataset(DataConfig(vocab_size=cfg.vocab_size,
                                   batch_size=batch_size, seq_len=seq_len))
    pf = Prefetcher(data, start_step=start_step)

    @jax.jit
    def step_fn(state, batch, step):
        lr = cosine_schedule(step, peak_lr=ocfg.lr, warmup=20, total=steps)
        loss, grads = jax.value_and_grad(
            lambda p: registry.loss_fn(p, cfg, batch))(state["params"])
        params, opt, stats = adamw_update(grads, state["opt"],
                                          state["params"], ocfg, lr)
        return {"params": params, "opt": opt}, loss, stats["grad_norm"]

    losses = []
    t0 = time.time()
    for step in range(start_step, steps):
        batch = pf.next()
        state, loss, gnorm = step_fn(state, batch,
                                     jnp.asarray(step, jnp.int32))
        if step % log_every == 0 or step == steps - 1:
            loss = float(loss)
            losses.append((step, loss))
            print(f"step {step:5d}  loss {loss:.4f}  gnorm {float(gnorm):.3f}"
                  f"  {time.time() - t0:.1f}s", flush=True)
        if rm is not None:
            rm.maybe_save(step + 1, state, {"loss": float(loss)})
    pf.close()
    return {"losses": losses, "final_loss": losses[-1][1]}


def scale_to_params(cfg, target: int):
    """Crude width scaling of a family config to ~target params."""
    from repro.models.registry import param_specs as ps
    import math
    lo, hi = 32, 16384
    best = cfg
    while lo < hi - 16:
        mid = ((lo + hi) // 2) // 16 * 16
        trial = dataclasses.replace(
            cfg, d_model=mid, d_ff=4 * mid if cfg.d_ff else 0,
            num_heads=max(4, mid // 64),
            num_kv_heads=max(2, min(cfg.num_kv_heads, mid // 128)))
        n = sum(math.prod(s.shape) for s in jax.tree_util.tree_leaves(
            ps(trial), is_leaf=lambda x: isinstance(x, common.ParamSpec)))
        if n < target:
            lo = mid
            best = trial
        else:
            hi = mid
    return best


def train_ssvm(scenario: str, iters: int, algo: str = "mpbcfw") -> dict:
    """MP-BCFW trainer mode: structured head via the paper's algorithm."""
    from repro.api import RunConfig, Solver
    from repro.core.selection import CostModel
    from repro.configs.paper import SMALL
    from repro.trainer.ssvm_head import build_problem

    sc = SMALL[scenario]
    prob = build_problem(sc)
    cfg = RunConfig(
        lam=1.0 / prob.n, algo=algo, max_iters=iters,
        cost_model=CostModel(oracle_cost=sc.oracle_cost,
                             plane_cost=sc.plane_cost))
    res = Solver(prob, cfg).run()
    for r in res.trace:
        print(f"iter {r.iteration:3d}  exact {r.n_exact:6d}  "
              f"approx {r.n_approx:7d}  dual {r.dual:.5f}  gap {r.gap:.5f}")
    return {"trace": res.trace}


def main():
    from repro.launch.compile_cache import setup_compile_cache

    setup_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--trainer", choices=["lm", "ssvm"], default="lm")
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--target-params", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--save-every", type=int, default=50)
    ap.add_argument("--scenario", default="ocr")
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--algo", default="mpbcfw")
    args = ap.parse_args()
    if args.trainer == "ssvm":
        train_ssvm(args.scenario, args.iters, args.algo)
    else:
        train_lm(args.arch, args.steps, args.batch_size, args.seq_len,
                 args.reduced, args.ckpt_dir, args.save_every,
                 target_params=args.target_params)


if __name__ == "__main__":
    main()
