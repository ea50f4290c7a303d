"""One run of a cell: drive it and print its lines (``bench/run.py``'s
work once the chip is found; the tests call it with the look for a chip
skipped)."""
from __future__ import annotations

import json
import sys

from . import cells
from . import device as dev
from . import trace as tr


def report(cell, args, clock0, devices, out=sys.stdout) -> int:
    """Run the cell's driver; print its notes, then the result line."""
    counter = dev.CompileCounter()
    outcome = cell.driver.run(cell, args.seed, args.seconds,
                              bool(args.trace), clock0, devices, counter,
                              cells.ROOT / "bench" / "out" / "trace")
    for line in outcome.notes:
        print(f"[{cell.name}] {line}", file=out, flush=True)
    if args.trace:
        ctx = dict(outcome.ctx,
                   peaks=dev.peaks(outcome.ctx["device"]["kind"]))
        metrics = cells.read_per_layer(cell, ctx)
    else:
        metrics = {m.name: {"value": outcome.end_to_end[m.name],
                            "unit": m.unit}
                   for m in cell.end_to_end
                   if m.name in outcome.end_to_end}
    device = dict(outcome.ctx["device"])
    result = {"correct": outcome.correct, "attempted": outcome.attempted,
              "failed": outcome.failed, "metrics": metrics,
              "device": device}
    if args.trace:
        busy = tr.busy_seconds(outcome.trace) if outcome.trace else None
        device["busy_s"] = busy
        device["window_s"] = outcome.trace_window_s
        if outcome.trace:
            result["breakdown"] = {
                "device_ops": tr.top_ops(outcome.trace),
                "idle_gaps": tr.idle_gaps(outcome.trace)}
    result["checked"] = {c.name: {"value": c.value, "limit": c.limit}
                         for c in outcome.compared}
    for c in outcome.compared:
        print(f"check {c.name} {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAIL'}", file=sys.stderr, flush=True)
    print(json.dumps(result), file=out, flush=True)
    return 0
