#!/usr/bin/env bash
# Tier-1 CI gate: the fast offline test suite + the benchmark smoke run.
#
#   scripts/ci.sh            # what CI runs
#   scripts/ci.sh --runslow  # + the multi-minute XLA compile cells
#   scripts/ci.sh --mesh     # + the mesh-marked tests under 8 forced
#                            #   host devices (XLA_FLAGS)
#   scripts/ci.sh --analyze  # + the static program-contract checker
#                            #   (python -m repro.analysis --strict)
#   scripts/ci.sh --obs      # only the obs stage: two recorded smoke
#                            #   runs, JSONL schema validation, a
#                            #   summary and a run diff
#   scripts/ci.sh --policy   # only the policy stage: the repro.policy
#                            #   property tests + the gap-vs-uniform
#                            #   oracle-call convergence smoke row
#   scripts/ci.sh --serve    # only the serve stage: the repro.serve +
#                            #   viterbi tests, then the serving bench
#                            #   which must emit serve_p50_us_* /
#                            #   serve_p99_us_* / serve_throughput_*
#                            #   rows with the batched path beating the
#                            #   one-at-a-time baseline
#   scripts/ci.sh --async    # only the async stage: the async-pipeline
#                            #   test suite, the async_bench smoke
#                            #   (oracle overlap >= 0.5 under the slow-
#                            #   oracle CostModel, <= 2 dispatches +
#                            #   1 host sync, fold-scatter bitwise), and
#                            #   the strict analyzer (rule J009 proves
#                            #   the two-program split statically)
#
# The obs, policy, serve, and async stages also run as part of the
# default flow (after the test suite, before/with the benchmark smoke)
# so a broken recorder/CLI, a gap-sampling regression, a serving
# regression, or a pipelining regression fails CI.
#
# The smoke benchmarks exercise the public Solver path end to end,
# including the fused score+select kernel vs the two-step path, the
# sharded gram engine's dispatch contract, and the policy layer's
# gap-proportional sampler.
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

MESH=0
ANALYZE=0
OBS_ONLY=0
POLICY_ONLY=0
SERVE_ONLY=0
ASYNC_ONLY=0
ARGS=()
for a in "$@"; do
  if [[ "$a" == "--mesh" ]]; then MESH=1
  elif [[ "$a" == "--analyze" ]]; then ANALYZE=1
  elif [[ "$a" == "--obs" ]]; then OBS_ONLY=1
  elif [[ "$a" == "--policy" ]]; then POLICY_ONLY=1
  elif [[ "$a" == "--serve" ]]; then SERVE_ONLY=1
  elif [[ "$a" == "--async" ]]; then ASYNC_ONLY=1
  else ARGS+=("$a"); fi
done

obs_stage() {
  # End-to-end obs check: record two tiny runs, validate them against
  # the JSONL schema, and summarize + diff them through the CLI.
  local dir
  dir="$(mktemp -d)"
  trap 'rm -rf "$dir"' RETURN
  python -m repro.obs --smoke-run "$dir/a.jsonl" --algo mpbcfw --iters 5
  python -m repro.obs --smoke-run "$dir/b.jsonl" --algo mpbcfw-gram --iters 5
  python -m repro.obs --validate "$dir/a.jsonl" "$dir/b.jsonl"
  python -m repro.obs "$dir/a.jsonl"
  python -m repro.obs --diff "$dir/a.jsonl" "$dir/b.jsonl"
}

policy_stage() {
  # Policy-layer gate: the repro.policy property/parity tests, then the
  # paper-scenario convergence smoke which must emit a
  # gap_vs_uniform_oracle_calls_* row showing the gap-proportional
  # sampler reaching the fixed gap target in fewer exact-oracle calls
  # than uniform sampling on at least one scenario.
  python -m pytest -x -q tests/test_policy.py
  python -m benchmarks.paper_convergence --smoke
}

serve_stage() {
  # Serving gate: the serve/viterbi test suites (export round-trip,
  # batcher contracts, kernel-vs-NumPy properties), then the serving
  # bench, which must emit latency/throughput rows for every bundled
  # spec and show the batched bucketed path beating one-at-a-time
  # decode on throughput.
  python -m pytest -x -q tests/test_serve.py tests/test_viterbi.py
  local out
  out="$(mktemp)"
  python -m benchmarks.serving_bench --smoke | tee "$out"
  python - "$out" <<'EOF'
import sys
rows = {}
for line in open(sys.argv[1]):
    line = line.strip()
    if line:
        name, value = line.split(",")[:2]
        rows[name] = float(value)
for kind in ("chain", "multiclass", "graph"):
    for prefix in ("serve_p50_us_", "serve_p99_us_", "serve_throughput_"):
        assert prefix + kind in rows, f"missing {prefix + kind} row"
    speedup = rows[f"serve_batched_speedup_{kind}"]
    assert speedup > 1.0, \
        f"batched serving lost to one-at-a-time on {kind}: {speedup}x"
print("serve stage OK: batched path beats single-request decode")
EOF
  rm -f "$out"
}

async_stage() {
  # Async-pipelining gate: the mpbcfw-async / mpbcfw-shard-async test
  # suite (dual monotonicity under stragglers, bitwise resume, the
  # CollectiveTrace split regression), then the async bench smoke —
  # which asserts the pipeline hides >= 0.5 of the modeled oracle under
  # the slow-oracle CostModel at <= 2 dispatches + 1 host sync per
  # outer iteration and that the chunked fold-scatter is bit-identical
  # — and the strict analyzer whose rule J009 proves the
  # async_oracle/async_cache two-program split statically.
  python -m pytest -x -q -m "not mesh" tests/test_async.py
  python -m benchmarks.async_bench --smoke
  python -m repro.analysis --strict
}

if [[ "$OBS_ONLY" == 1 ]]; then
  obs_stage
  exit 0
fi

if [[ "$SERVE_ONLY" == 1 ]]; then
  serve_stage
  exit 0
fi

if [[ "$POLICY_ONLY" == 1 ]]; then
  policy_stage
  exit 0
fi

if [[ "$ASYNC_ONLY" == 1 ]]; then
  async_stage
  exit 0
fi

if [[ "$ANALYZE" == 1 ]]; then
  # Static gate first: traces every registered engine's fused programs,
  # cross-checks jaxpr/HLO collective budgets, lints src/.  Fails fast
  # (nonzero exit on any finding) before the test suite spends minutes.
  python -m repro.analysis --strict
fi

if [[ "$MESH" == 1 ]]; then
  # Split stages: the fast suite without the mesh-marked tests first,
  # then only the mesh-marked tests under 8 forced host devices (the
  # subprocess smokes force the count themselves; the stage-level flag
  # covers any in-process multi-device collection).
  python -m pytest -x -q -m "not mesh" ${ARGS[@]+"${ARGS[@]}"}
  obs_stage
  policy_stage
  serve_stage
  async_stage
  python -m benchmarks.run --smoke
  # The mesh-marked tests include the mpbcfw-shard-async subprocess
  # smoke (8 forced host devices), so the two-program split's dispatch
  # contract is exercised on a real multi-shard mesh here.
  XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python -m pytest -x -q -m mesh ${ARGS[@]+"${ARGS[@]}"}
else
  python -m pytest -x -q ${ARGS[@]+"${ARGS[@]}"}
  obs_stage
  policy_stage
  serve_stage
  async_stage
  python -m benchmarks.run --smoke
fi
