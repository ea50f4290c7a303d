#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell's configuration, traffic mix, correctness limits and per-layer
metric readers are found by name from ``BENCHMARK.json``
(``bench/benchkit/cells.py``); the traffic file's ``kind`` picks the
generic driver.  The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the end-to-end
metrics with ``--trace 0``, the per-layer ones with ``--trace 1``),
``device``, with ``--trace 1`` also ``breakdown``, and last ``checked``:
each number the correctness check compared, beside its limit.  The same
numbers end standard error.

Without a TPU, or with fewer chips than the cell needs, it exits 2 and
prints no result.
"""
from __future__ import annotations

import argparse
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchkit import device as dev

    clock0 = dev.process_start()
    # The TPU runtime logs under /tmp unless told otherwise: keep its logs
    # inside the checkout.
    os.environ.setdefault("TPU_LOG_DIR", str(BENCH / "out" / "tpu_logs"))
    if not (ROOT / "src" / "repro").is_dir():
        print("bench/run.py: the system under test (src/repro) is not in "
              "this checkout", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))

    from benchkit import cells

    cell = cells.load_cell(args.workload, ROOT)
    dev.setup_compile_cache(ROOT)
    try:
        devices = dev.require_tpu(cell.chips)
    except dev.NoChip as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 2
    from benchkit.report import report

    return report(cell, args, clock0, devices)


if __name__ == "__main__":
    sys.exit(main())
