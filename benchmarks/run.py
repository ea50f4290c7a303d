"""Benchmark harness entry point — one section per paper table/figure.

Prints ``name,value,derived`` CSV rows:
  * fig3_*   oracle convergence  (gap at equal exact-oracle budget)
  * fig4_*   runtime convergence (simulated oracle-cost regimes)
  * fig5_*   working-set size trajectory
  * fig6_*   approximate passes per exact pass
  * hostsync_* control-loop host syncs per outer iteration (batched vs old)
  * shard_*  sharded-engine smoke: psums per approximate pass, collectives,
             host syncs and program dispatches per outer iteration vs the
             host-loop equivalent — including ``shard_driver_*`` rows for
             the public ``repro.api.Solver`` path (``algo='mpbcfw-shard'``)
  * kernel_* hot-path microbenchmarks (us per call)
  * analysis_* static-analyzer wall time + per-engine statically counted
             collectives (the budgets ``repro.analysis`` proves)
  * obs_overhead_* host wall time per iteration with and without a
             ``repro.obs.RunRecorder`` installed (recorder cost)
  * serve_*  batched structured-prediction serving: closed/open-loop
             p50/p99 latency (us), labels/sec throughput, and the
             batched-vs-one-at-a-time speedup per bundled spec
  * async_*  oracle pipelining (``mpbcfw-async``): mean oracle overlap
             hidden behind the cache program (CostModel + wall modes),
             modeled speedup over the fused serial engine, and the
             fold-in scatter-strategy microbenchmark
             (``fold_scatter_{chunked,per_elem}_us_*``)
  * dryrun_/roofline_ summary of the (arch x shape) grid

``--smoke``: a fast CI-friendly subset — 4-iteration convergence runs and
small-shape kernel benches, skipping the dry-run/roofline grid (which
needs the multi-minute XLA compile cells).  ``--quick`` only shortens the
convergence runs of the full suite.
"""
from __future__ import annotations

import sys


def main() -> None:
    from repro.launch.compile_cache import setup_compile_cache

    setup_compile_cache()
    quick = "--quick" in sys.argv
    smoke = "--smoke" in sys.argv
    from . import (analysis_bench, async_bench, kernel_bench, obs_bench,
                   paper_convergence, serving_bench, sharded_bench,
                   workset_stats)
    rows = []
    rows += paper_convergence.main(quick=quick or smoke)
    rows += workset_stats.main()
    rows += sharded_bench.main(smoke=smoke)
    rows += async_bench.main(smoke=smoke)
    rows += kernel_bench.main(smoke=smoke)
    rows += analysis_bench.main(smoke=smoke)
    rows += obs_bench.main(smoke=smoke)
    rows += serving_bench.main(smoke=smoke)
    if not smoke:
        from . import roofline_report
        rows += roofline_report.main()
    print("name,value,derived")
    for r in rows:
        print(",".join(str(x) for x in r))


if __name__ == "__main__":
    main()
