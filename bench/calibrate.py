#!/usr/bin/env python3
"""Readings that set the limits of a cell's correctness check.  Run on
the chip, by hand, at the cell's own size; the benchmark's runs never
run it:

    python3 bench/calibrate.py --workload train-ocr --seeds 11,12,13 \
        --controls 3

For every seed it prints the numbers the check compares for the sound
program (the lower readings).  For the first ``--controls`` seeds it
also prints them for the control and for each planted fault, each put
in the program's place and compared with the same float64 reference
(the upper readings):

- training: ``control`` is the reference computed in bfloat16;
  ``fault_half`` runs each exact pass over half of its blocks;
  ``fault_alter`` corrupts each exact oracle answer where it is made.
  (A step that returns its state unchanged leaves the dual at 0 and
  reads 1 on ``dual``, with no run.)
- serving: ``control`` decodes with the reference in bfloat16;
  ``fault_alter`` changes one label of every served answer;
  ``fault_half`` drops half of every round's requests.

One JSON line per (seed, variant).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))


def train_readings(cell, seed: int, controls: bool):
    import gc

    import jax
    import numpy as np

    from benchkit import device as dev
    from benchkit.drivers import train
    from benchkit.reference import BF16
    from repro.api import build_problem

    rc = train.run_config(cell, seed, int(cell.traffic["max_iters"]))
    data = cell.task.make_data(cell.config, dev.seed_key(seed))
    problem = build_problem(cell.task.spec(cell.config), data)
    got = train.drive_check(problem, rc)
    host = {k: np.asarray(v) for k, v in jax.device_get(data).items()}
    del data, problem
    gc.collect()
    passes = [r.approx_passes for r in got.rows]
    ref = train.reference_run(cell, host, rc, passes)
    yield "program", train.compare(got, ref)
    if not controls:
        return
    ctl = train.reference_run(cell, host, rc, passes, prec=BF16)
    yield "control", train.compare(ctl, ref)
    for fault in ("half", "alter"):
        bad = train.reference_run(cell, host, rc, passes, fault=fault)
        yield f"fault_{fault}", train.compare(bad, ref)


def serve_readings(cell, seed: int, controls: bool, seconds: float):
    import numpy as np

    from benchkit.drivers import serve
    from benchkit.reference import BF16

    server, pool, lengths, host, weights = serve.setup(cell, seed)
    w = serve.plan(cell.traffic, seconds, lengths, seed)
    keep = serve.sample(w, int(cell.traffic["check_sample"]), seed)
    serve.open_loop(server, pool, w, keep)
    lost = w.submitted - int(np.sum(~np.isnan(w.done))) - server.pending
    yield "program", serve.check_values(cell.config, weights, host, w, lost)
    if not controls:
        return
    answered = list(w.labels)
    ctl = serve.reference_labels(cell.config, weights, host, w, answered,
                                 BF16)
    gaps = serve.label_gaps(cell.config, weights, host, w, ctl)
    yield "control", {"lost": 0.0, "label_gap": max(gaps)}
    bad = {k: np.asarray(v).copy() for k, v in w.labels.items()}
    for v in bad.values():
        v[0] = (v[0] + 1) % int(cell.config["num_labels"])
    gaps = serve.label_gaps(cell.config, weights, host, w, bad)
    yield "fault_alter", {"lost": 0.0, "label_gap": max(gaps)}
    # Half of every round dropped: those requests are neither answered
    # nor queued.
    dropped = sum(r[2] // 2 for r in w.rounds)
    yield "fault_half", {"lost": float(dropped), "label_gap": 0.0}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=10.0,
                    help="serving: the short window at the cell's rate")
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", str(BENCH / "out" / "tpu_logs"))

    from benchkit import cells, device as dev

    cell = cells.load_cell(args.workload, ROOT)
    dev.setup_compile_cache(ROOT)
    try:
        dev.require_tpu(cell.chips)
    except dev.NoChip as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2
    seeds = [int(s) for s in args.seeds.split(",")]
    for k, seed in enumerate(seeds):
        controls = k < args.controls
        if cell.traffic["kind"] == "train":
            it = train_readings(cell, seed, controls)
        else:
            it = serve_readings(cell, seed, controls, args.seconds)
        for variant, values in it:
            print(json.dumps({"workload": cell.name, "seed": seed,
                              "variant": variant, **values}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
