#!/usr/bin/env python3
"""Find the serving knee: the highest offered rate the server sustains
without a growing backlog.  Run once on the chip, by hand:

    python3 bench/sweep_serve.py --workload serve-ocr --seed 1 \
        --seconds 8 --rates 4000,8000,12000,16000

One process: set-up as the cell's own (``benchkit.drivers.serve``), then
for each rate one open-loop window on the real clock.  Prints one line
per rate (offered and answered per second, the server's backlog and
the due requests held back by its ``MAX_QUEUED`` bound at the close,
p50 and p95 latency) and, last, a JSON object with the table and the
knee.  A rate is sustained when at least 99% of the offered requests are
answered in the window and the p95 latency stays under
``--p95-limit-ms`` (by default 20 ms, under twice the tail at low load);
the knee is the highest rate sustained.  It never runs as part of the
benchmark.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", default="serve-ocr")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--p95-limit-ms", type=float, default=20.0)
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", str(BENCH / "out" / "tpu_logs"))

    import numpy as np

    from benchkit import cells, device as dev, stats
    from benchkit.drivers import serve

    cell = cells.load_cell(args.workload, ROOT)
    dev.setup_compile_cache(ROOT)
    try:
        dev.require_tpu(cell.chips)
    except dev.NoChip as e:
        print(f"sweep_serve: {e}", file=sys.stderr)
        return 2
    server, pool, lengths, _, _ = serve.setup(cell, args.seed)
    table = []
    for rate in (float(r) for r in args.rates.split(",")):
        w = serve.plan(cell.traffic, args.seconds, lengths, args.seed,
                       rate=rate)
        serve.open_loop(server, pool, w, set())
        lat = stats.open_loop_latencies(w.due, w.done, w.end)
        answered = int(np.sum(~np.isnan(w.done)))
        row = {"rate_per_s": rate,
               "answered_per_s": answered / args.seconds,
               "labels_per_s": stats.labels_per_s(w.length, w.done, w.end,
                                                  args.seconds),
               "backlog": server.pending,
               "held_back": int(np.sum(w.due < w.end)) - w.submitted,
               "p50_ms": 1e3 * float(np.median(lat)),
               "p95_ms": 1e3 * stats.p95(lat),
               "rounds": len(w.rounds)}
        row["sustained"] = (row["answered_per_s"] >= 0.99 * rate
                            and row["p95_ms"] < args.p95_limit_ms)
        table.append(row)
        print(json.dumps(row), flush=True)
        server.drain()
        time.sleep(0.5)
    knee = max((r["rate_per_s"] for r in table if r["sustained"]),
               default=None)
    print(json.dumps({"knee_per_s": knee, "table": table}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
