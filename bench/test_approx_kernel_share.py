"""The reader of ``approx_kernel_share.train``: on the older recorded
traces, which a program without spans left, on a profile recorded on a
TPU v5e whose approximate passes ran as a scan of block steps, and on a
small made-up profile of the kernel."""
import json
import shutil
import types
from pathlib import Path

import pytest

from benchkit import cells, program, trace

METRIC = "approx_kernel_share.train"
DATA = Path(__file__).resolve().parent / "benchkit" / "testdata"
OLD = sorted(DATA.glob("trace_*.json"))


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


@pytest.mark.parametrize("path", OLD, ids=[p.stem for p in OLD])
def test_reads_nothing_where_the_program_has_no_spans(tmp_path, monkeypatch,
                                                      path):
    """A program without spans leaves no ``approx_pass`` scope: the
    reader says so with None and raises nothing."""
    monkeypatch.setattr(program, "TRACE_DIR", tmp_path)
    rows = [types.SimpleNamespace(n_exact=6877, approx_passes=1, time=0.6)]
    ctx = {"trace": json.loads(path.read_text()),
           "trainings": [types.SimpleNamespace(rows=rows)]}
    assert cells.reader(METRIC)(ctx) is None


def test_reads_0_on_a_profile_of_the_scan(recorded):
    """The recorded training profile ran its approximate passes as a scan
    of block steps: the kernel's share of them is 0."""
    assert cells.reader(METRIC)(recorded("train")) == 0.0


def test_counts_the_kernel_within_the_scope(monkeypatch):
    """Two passes under the scope, each one kernel launch (8 + 6 ns)
    beside a 2 ns scatter; an overlapping op counts once; an operation
    named like the kernel on a chip with no scope counts for nothing."""
    chip = "/device:TPU:0"
    prog = {"program": [], "scoped": {chip: [
        ["approx_pass", 0.0, 8.0], ["approx_pass", 8.0, 2.0],
        ["approx_pass", 20.0, 6.0], ["approx_pass", 24.0, 1.0],
        ["approx_pass", 26.0, 2.0], ["exact_pass", 30.0, 50.0]]}}
    ctx = {"trace": {"device": {
        chip: [["approx_block_steps", 0.0, 8.0], ["fusion", 8.0, 2.0],
               ["approx_block_steps", 20.0, 6.0], ["fusion", 24.0, 1.0],
               ["scatter", 26.0, 2.0], ["fusion", 30.0, 50.0]],
        "/device:TPU:1": [["approx_block_steps", 0.0, 99.0]]}}}
    monkeypatch.setattr(program, "of", lambda c: prog)
    assert cells.reader(METRIC)(ctx) == pytest.approx(100.0 * 14.0 / 18.0)
