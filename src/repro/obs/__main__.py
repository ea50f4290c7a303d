"""CLI of the obs layer.

    python -m repro.obs run.jsonl                    # summarize a run
    python -m repro.obs --diff a.jsonl b.jsonl       # compare two runs
    python -m repro.obs --validate run.jsonl         # schema check
    python -m repro.obs --smoke-run out.jsonl --algo mpbcfw     # tiny run

``--smoke-run`` drives a small deterministic (CostModel-clocked) Solver
run with a :class:`~repro.obs.RunRecorder` installed — it is what
``scripts/ci.sh --obs`` uses to produce fixture runs, and doubles as a
minimal end-to-end example of the recorder wiring.

Exit status: nonzero on validation errors or unreadable runs.
"""
from __future__ import annotations

import argparse
import sys


def _smoke_run(out_path: str, algo: str, seed: int, iters: int) -> int:
    # Local imports: the summarize/diff/validate paths must work without
    # initializing jax.
    import jax.numpy as jnp

    from ..api import RunConfig, Solver
    from ..core.oracles import multiclass
    from ..core.selection import CostModel
    from ..data import synthetic
    from . import RunRecorder

    x, y = synthetic.usps_like(n=24, f=8, num_classes=4, seed=7)
    problem = multiclass.make_problem(jnp.asarray(x), jnp.asarray(y), 4)
    cfg = RunConfig(lam=0.1, algo=algo, cap=8, ttl=5, max_iters=iters,
                    max_approx_passes=12, approx_batch=4, seed=seed,
                    cost_model=CostModel(oracle_cost=1.0, plane_cost=1e-3))
    with RunRecorder(out_path) as rec:
        Solver(problem, cfg, recorder=rec).run()
    print(f"smoke run ({algo}, seed={seed}, {iters} iters) -> {out_path}")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.obs",
        description="Summarize, diff and validate obs run traces.")
    ap.add_argument("runs", nargs="*", help="run JSONL file(s)")
    ap.add_argument("--diff", action="store_true",
                    help="diff two runs (requires exactly two files)")
    ap.add_argument("--validate", action="store_true",
                    help="validate the JSONL against the schema")
    ap.add_argument("--smoke-run", action="store_true",
                    help="produce a tiny recorded run at RUNS[0] (CI)")
    ap.add_argument("--algo", default="mpbcfw",
                    help="engine for --smoke-run (default: mpbcfw)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--iters", type=int, default=6)
    args = ap.parse_args(argv)

    if args.smoke_run:
        if len(args.runs) != 1:
            ap.error("--smoke-run needs exactly one output path")
        return _smoke_run(args.runs[0], args.algo, args.seed, args.iters)

    from .schema import validate_file
    from .summary import (diff_runs, format_diff, format_summary, load_run,
                          summarize)

    if args.validate:
        if not args.runs:
            ap.error("--validate needs at least one run file")
        status = 0
        for path in args.runs:
            count, errs = validate_file(path)
            if errs:
                status = 1
                print(f"{path}: {count} records, {len(errs)} error(s)")
                for e in errs[:20]:
                    print(f"  {e}")
            else:
                print(f"{path}: {count} records, schema OK")
        return status

    if args.diff:
        if len(args.runs) != 2:
            ap.error("--diff needs exactly two run files")
        print(format_diff(diff_runs(load_run(args.runs[0]),
                                    load_run(args.runs[1]))))
        return 0

    if len(args.runs) != 1:
        ap.error("expected one run file (or --diff with two)")
    print(format_summary(summarize(load_run(args.runs[0]))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
