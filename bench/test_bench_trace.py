"""Trace-to-metric reduction, on small excerpts of traces recorded on a
TPU v5e (``benchkit/testdata``) and on a hand-made trace."""
import json
from pathlib import Path

import numpy as np
import pytest

from benchkit import cells, trace

DATA = Path(__file__).resolve().parent / "benchkit" / "testdata"
RECORDED = sorted(DATA.glob("trace_*.json"))


def covered_ns(events):
    """Busy nanoseconds by brute force: mark every covered nanosecond."""
    lo = int(min(e[1] for e in events))
    hi = int(max(e[1] + e[2] for e in events)) + 1
    line = np.zeros(hi - lo, bool)
    for _, s, d in events:
        line[int(s) - lo: int(s + d) - lo] = True
    return int(line.sum())


@pytest.mark.parametrize("path", RECORDED, ids=lambda p: p.stem)
def test_busy_time_of_a_recorded_trace(path):
    t = json.loads(path.read_text())
    (dev, events), = t["device"].items()
    assert dev.startswith("/device:TPU:")
    assert len(events) > 100
    busy = trace.busy_seconds(t)
    assert busy == pytest.approx(covered_ns(events) / 1e9, rel=1e-3)
    lo, hi = trace.window_bounds(t)
    idle = trace.idle_share(t, (hi - lo) / 1e9)
    assert 0.0 <= idle < 100.0
    assert idle == pytest.approx(100 * (1 - busy / ((hi - lo) / 1e9)))


@pytest.mark.parametrize("path", RECORDED, ids=lambda p: p.stem)
def test_ops_and_gaps_of_a_recorded_trace(path):
    t = json.loads(path.read_text())
    top = trace.top_ops(t)
    assert 0 < len(top) <= 10
    assert all(a[1] >= b[1] for a, b in zip(top, top[1:]))
    name, seconds = top[0]
    assert seconds == pytest.approx(sum(
        e[2] for e in trace.kernel_events(t, name)) / 1e9)
    gaps = trace.idle_gaps(t)
    assert 0 < len(gaps) <= 10
    assert all(g[1] > 0 for g in gaps)
    spans = {h[0] for h in t["host"]} | {"no span"}
    assert all(g[0] in spans for g in gaps)


def test_kernel_roofline_readers_on_recorded_traces():
    by_name = {p.stem: json.loads(p.read_text()) for p in RECORDED}
    peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    serve = by_name["trace_serve"]
    events = trace.kernel_events(serve, "viterbi_step")
    assert events
    read = cells.reader("viterbi_step_roofline")
    got = read({"trace": serve, "batch_size": 32, "peaks": peaks,
                "config": {"num_labels": 26}})
    # per step: 2*32*26*26 ops, (3*32*26 + 26*26)*4 bytes: memory-bound
    least = (3 * 32 * 26 + 26 * 26) * 4 / 819e9
    want = 100 * len(events) * least / (sum(e[2] for e in events) / 1e9)
    assert got == pytest.approx(want)
    assert 0 < got < 100
    train = by_name["trace_train"]
    events = trace.kernel_events(train, "plane_scores")
    assert events
    got = cells.reader("plane_scores_roofline")(
        {"trace": train, "peaks": peaks, "config": {"cap": 64},
         "task_dim": 4004})
    least = (64 * 4005 + 4004) * 4 / 819e9
    want = 100 * len(events) * least / (sum(e[2] for e in events) / 1e9)
    assert got == pytest.approx(want)
    assert 0 < got < 100


def test_idle_gaps_are_named_by_the_innermost_host_span():
    ms = 1e6
    t = {"device": {"/device:TPU:0": [["a", 0.0, 1 * ms],
                                      ["b", 3 * ms, 1 * ms],
                                      ["a", 4 * ms, 1 * ms],
                                      ["c", 9 * ms, 1 * ms]]},
         "host": [["iteration", 0.0, 10 * ms],
                  ["evaluate", 5 * ms, 3.5 * ms]]}
    assert trace.busy_seconds(t) == pytest.approx(4e-3)
    assert trace.idle_share(t, 10e-3) == pytest.approx(60.0)
    gaps = trace.idle_gaps(t)
    assert gaps == [["evaluate", pytest.approx(4e-3)],
                    ["iteration", pytest.approx(2e-3)]]
    assert trace.top_ops(t)[0] == ["a", pytest.approx(2e-3)]
    assert trace.idle_share(None, 1.0) is None
    assert trace.idle_share({"device": {}, "host": []}, 1.0) is None


def test_op_names_drop_the_instruction_suffix():
    assert trace.op_name("%viterbi_step.6 = (f32[32,128]) custom-call(x)") \
        == "viterbi_step"
    assert trace.op_name("%fusion.12 = f32[4] fusion(y)") == "fusion"
    assert trace.op_name("%copy-start = (f32[4]) copy-start(z)") == \
        "copy-start"
