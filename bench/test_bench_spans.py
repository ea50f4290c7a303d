"""The readers of the program's own spans, scopes and counts: on small
profiles recorded on a TPU v5e (``benchkit/testdata/scoped_*.xplane.pb``),
on whole tiny runs of the cells on the CPU, and on the older recorded
traces, which a program without spans left: there each reader finds
nothing and says so with None."""
import json
import shutil
import time
import types
from pathlib import Path

import jax
import pytest

from benchkit import cells, program, trace
from benchkit import device as dev
from repro.obs import spans

DATA = Path(__file__).resolve().parent / "benchkit" / "testdata"
OLD = sorted(DATA.glob("trace_*.json"))
NEW = ["oracle_us.train", "exact_pass_ms.train", "approx_pass_ms.train",
       "eval_ms.train", "eval_idle.train", "compiles_per_iter.train",
       "device_wait_ms.serve"]


@pytest.fixture
def recorded(tmp_path, monkeypatch):
    """``ctx`` of a traced run whose profile is a recorded one."""
    def load(name):
        src = DATA / f"scoped_{name}.xplane.pb"
        dst = tmp_path / "plugins" / "profile" / "run" / src.name
        dst.parent.mkdir(parents=True)
        shutil.copy(src, dst)
        monkeypatch.setattr(program, "TRACE_DIR", tmp_path)
        return {"trace": trace.load_xplane(str(src))}
    return load


def merged_ns(intervals):
    """Length of a union of ``(start, end)`` intervals, by a sweep over
    their end points."""
    points = sorted([(s, -1) for s, _ in intervals]
                    + [(e, 1) for _, e in intervals])
    depth, total, since = 0, 0.0, None
    for t, end in points:                  # starts sort before ends
        if depth == 0 and end == -1:
            since = t
        depth -= end
        if depth == 0:
            total += t - since
    return total


def under(prog, *names):
    """Intervals of the operations whose scopes hold ``names`` in order."""
    out = []
    for evs in prog["scoped"].values():
        for path, s, d in evs:
            parts = path.split("/")
            at = [parts.index(n) for n in names if n in parts]
            if len(at) == len(names) and at == sorted(at):
                out.append((s, s + d))
    return out


def iteration_counts(prog, key):
    return sum(int(s[3][key]) for s in prog["program"]
               if s[0] == spans.ITERATION)


# -- expected values, computed apart from the readers -----------------------


def want_oracle_us(ctx, prog):
    return 1e-3 * merged_ns(under(prog, spans.EXACT_PASS, spans.ORACLE)) \
        / iteration_counts(prog, "exact_calls")


def want_exact_pass_ms(ctx, prog):
    iters = [s for s in prog["program"] if s[0] == spans.ITERATION]
    return 1e-6 * merged_ns(under(prog, spans.EXACT_PASS)) / len(iters)


def want_approx_pass_ms(ctx, prog):
    return 1e-6 * merged_ns(under(prog, spans.APPROX_PASS)) \
        / iteration_counts(prog, "approx_passes")


def want_eval_idle(ctx, prog):
    dev_ops, = ctx["trace"]["device"].values()
    busy = [(s, s + d) for _, s, d in dev_ops]
    total = idle = 0.0
    for name, s, d, _ in prog["program"]:
        if name != spans.EVALUATE:
            continue
        inside = [(max(a, s), min(b, s + d)) for a, b in busy
                  if b > s and a < s + d]
        total += d
        idle += d - merged_ns(inside)
    return 100.0 * idle / total


def want_device_wait_ms(ctx, prog):
    waits = []
    for name, s, d, _ in prog["program"]:
        if name == spans.ROUND:
            waits.append(sum(p[2] for p in prog["program"]
                             if s <= p[1] and p[1] + p[2] <= s + d
                             and p[0] in (spans.DECODE, spans.SYNC)))
    return 1e-6 * sum(waits) / len(waits)


RECORDED = {
    "oracle_us.train": ("train", want_oracle_us),
    "exact_pass_ms.train": ("train", want_exact_pass_ms),
    "approx_pass_ms.train": ("train", want_approx_pass_ms),
    "eval_idle.train": ("train", want_eval_idle),
    "device_wait_ms.serve": ("serve", want_device_wait_ms),
}


@pytest.mark.parametrize("metric", sorted(RECORDED))
def test_reader_on_a_recorded_profile(recorded, metric):
    name, want = RECORDED[metric]
    ctx = recorded(name)
    prog = program.of(ctx)
    got = cells.reader(metric)(ctx)
    assert got is not None and got > 0
    assert got == pytest.approx(want(ctx, prog), rel=1e-9)


def test_recorded_training_profile_holds_the_scopes_and_spans(recorded):
    ctx = recorded("train")
    prog = program.of(ctx)
    paths = {p for evs in prog["scoped"].values() for p, _, _ in evs}
    # eviction's operations are fused into others, which name the fusion
    assert {p.split("/")[0] for p in paths} == {spans.EXACT_PASS,
                                               spans.APPROX_PASS}
    assert spans.EXACT_PASS + "/" + spans.ORACLE in paths
    # every scoped operation is one of the chip's operations
    ops = {(s, d) for evs in ctx["trace"]["device"].values()
           for _, s, d in evs}
    assert all((s, d) in ops for evs in prog["scoped"].values()
               for _, s, d in evs)
    # each iteration holds its dispatch, sync and evaluation, and says
    # what it did
    iters = [s for s in prog["program"] if s[0] == spans.ITERATION]
    assert iters
    for _, s, d, meta in iters:
        inside = [p[0] for p in prog["program"]
                  if s < p[1] and p[1] + p[2] <= s + d]
        assert inside[0] == spans.DISPATCH and spans.EVALUATE in inside
        assert int(meta["exact_calls"]) > 0
        assert int(meta["approx_passes"]) >= 1


def test_recorded_serving_profile_nests_the_round(recorded):
    prog = program.of(recorded("serve"))
    rounds = [s for s in prog["program"] if s[0] == spans.ROUND]
    assert rounds
    for _, s, d, meta in rounds:
        inside = [p[0] for p in prog["program"]
                  if s < p[1] and p[1] + p[2] <= s + d]
        assert inside == [spans.PICK, spans.PAD, spans.STACK, spans.DECODE,
                          spans.SYNC, spans.ANSWER]
        assert int(meta["batch"]) >= 1


@pytest.mark.parametrize("metric,ctx,want", [
    ("eval_ms.train",
     {"trainings": [
         types.SimpleNamespace(rows=[types.SimpleNamespace(eval_s=0.150),
                                     types.SimpleNamespace(eval_s=0.170)]),
         types.SimpleNamespace(rows=[types.SimpleNamespace(eval_s=0.160)])]},
     160.0),
    ("compiles_per_iter.train",
     {"trainings": [types.SimpleNamespace(rows=[
         types.SimpleNamespace(compiles=c) for c in (3, 1, 2)])]},
     2.0),
], ids=["eval_ms.train", "compiles_per_iter.train"])
def test_row_column_readers(metric, ctx, want):
    assert cells.reader(metric)(ctx) == pytest.approx(want)


@pytest.mark.parametrize("metric", NEW)
def test_reader_finds_nothing_where_the_program_has_no_spans(
        tmp_path, monkeypatch, metric):
    """The older recorded traces with no profile of the program's own,
    and rows without the host columns: what a program without spans
    leaves."""
    monkeypatch.setattr(program, "TRACE_DIR", tmp_path)
    old_rows = [types.SimpleNamespace(n_exact=6877, approx_passes=1,
                                      time=0.6)]
    for path in OLD:
        ctx = {"trace": json.loads(path.read_text()),
               "trainings": [types.SimpleNamespace(rows=old_rows)]}
        assert cells.reader(metric)(ctx) is None, path.name


def test_scope_path_keeps_the_program_scopes_only():
    scopes = (spans.EXACT_PASS, spans.ORACLE)
    assert program.scope_path(
        "jit(_outer_program)/exact_pass/while/body/closed_call/oracle/"
        "dot_general", scopes) == "exact_pass/oracle"
    assert program.scope_path("jit(f)/while/body/add", scopes) is None
    assert program.scope_path("", scopes) is None


# -- whole tiny runs on the CPU: the host spans and columns are read -------


def tiny_cell(name):
    cell = cells.load_cell(name, cells.ROOT)
    if cell.traffic["kind"] == "train":
        cell.config.update(n=40, f=8, num_labels=4, max_len=6)
        cell.traffic["gap_target"] = 1e-9
    else:
        cell.config.update(f=8, num_labels=4)
        cell.traffic.update(pool=64, rate_per_s=200.0, check_sample=40)
    return cell


@pytest.mark.parametrize("name,want", [
    ("train-ocr", {"eval_ms.train", "compiles_per_iter.train"}),
    ("serve-ocr-sat", {"device_wait_ms.serve"}),
])
def test_traced_tiny_run_reads_the_program_metrics(tmp_path, monkeypatch,
                                                   name, want):
    cell = tiny_cell(name)
    monkeypatch.setattr(program, "TRACE_DIR", tmp_path)
    out = cell.driver.run(cell, 2 ** 32 + 91, 0.5, True, time.perf_counter(),
                          jax.devices()[:1], dev.CompileCounter(), tmp_path)
    ctx = dict(out.ctx, peaks={"flops_per_s": 1e12,
                               "hbm_bytes_per_s": 1e11})
    got = cells.read_per_layer(cell, ctx)
    # the CPU has no TPU plane: what needs the chip's operations is absent
    assert want <= set(got)
    assert all(got[m]["value"] >= 0 for m in want)
    assert got.get("eval_ms.train", {"value": 1})["value"] > 0
    assert got.get("device_wait_ms.serve", {"value": 1})["value"] > 0
