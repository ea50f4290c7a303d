"""A whole run of each cell kind at a tiny size, with the look for a chip
skipped: sound, it is correct; with the timed path broken underneath it
is not, once for each fault the cell can have.  The control (the
reference in bfloat16, put in the program's place) fails too."""
import io
import json
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchkit import cells
from benchkit.report import report
from benchkit.drivers import serve, train
from benchkit.reference import BF16

TINY = dict(n=40, f=8, num_labels=4, max_len=6)
# Examples in the control's check: the configuration's widths, fewer of
# them.
CONTROL_N = 300
TRAIN_CELLS = [w["name"] for w in cells.load_benchmark()["workloads"]
               if cells.load_cell(w["name"]).traffic["kind"] == "train"]


def tiny_cell(name):
    cell = cells.load_cell(name, cells.ROOT)
    if cell.traffic["kind"] == "train":
        cell.config.update(TINY)
        cell.traffic["gap_target"] = 1e-9
    else:
        cell.config.update(f=8, num_labels=4)
        cell.traffic.update(pool=64, rate_per_s=200.0, check_sample=40)
    return cell


def run_cell(cell, seconds=0.5, trace=0):
    out = io.StringIO()
    args = types.SimpleNamespace(seed=2 ** 32 + 77, seconds=seconds,
                                 trace=trace)
    rc = report(cell, args, time.perf_counter(),
                          jax.devices()[:1], out=out)
    assert rc == 0
    lines = out.getvalue().strip().splitlines()
    result = json.loads(lines[-1])
    assert list(result)[-1] == "checked"
    return result


@pytest.fixture(scope="module", params=TRAIN_CELLS)
def train_cell(request):
    return tiny_cell(request.param)


def test_train_sound_run_is_correct(train_cell):
    r = run_cell(train_cell)
    assert r["correct"], r["checked"]
    assert set(r["metrics"]) <= {"train_s", "setup_s"}
    assert r["device"]["count"] == 1


def test_train_setup_runs_the_overflow_program_once(train_cell,
                                                    monkeypatch):
    from repro.api import build_problem, engines

    calls = []
    real = engines.FusedEngine.continue_passes

    def counted(self, mp, perms, clock):
        calls.append(perms.shape)
        return real(self, mp, perms, clock)
    monkeypatch.setattr(engines.FusedEngine, "continue_passes", counted)
    cfg = train_cell.config
    rc = train.run_config(train_cell, 5, 10)
    data = train_cell.task.make_data(cfg, jax.random.PRNGKey(5))
    problem = build_problem(train_cell.task.spec(cfg), data)
    check = train.drive_check(problem, rc)
    assert len(check.rows) == train.CHECK_ITERS
    # the window's overflow batches have this shape
    assert calls[-1] == (min(rc.approx_batch, rc.max_approx_passes),
                         problem.n)


def _state_unchanged(monkeypatch, cell):
    from repro.api import engines

    real = engines.FusedEngine.outer_iteration

    def frozen(self, mp, perm, perms, clock, **kw):
        keep = jax.tree_util.tree_map(jnp.copy, mp)
        _, clock, stats = real(self, mp, perm, perms, clock, **kw)
        return keep, clock, stats
    monkeypatch.setattr(engines.FusedEngine, "outer_iteration", frozen)


def _half_batch(monkeypatch, cell):
    from repro.api import engines

    real = engines.FusedEngine.outer_iteration

    def half(self, mp, perm, perms, clock, **kw):
        return real(self, mp, perm[: perm.shape[0] // 2], perms, clock,
                    **kw)
    monkeypatch.setattr(engines.FusedEngine, "outer_iteration", half)


def _answer_altered(monkeypatch, cell):
    spec = type(cell.task.spec(cell.config))
    labels = int(cell.config["num_labels"])
    real = spec.decode

    def altered(self, w, ex):
        y = real(self, w, ex)
        return y.at[0].set((y[0] + 1) % labels)
    monkeypatch.setattr(spec, "decode", altered)


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _answer_altered])
def test_train_faults_are_not_correct(train_cell, fault, monkeypatch):
    fault(monkeypatch, train_cell)
    r = run_cell(train_cell)
    assert not r["correct"], r["checked"]


def test_train_control_fails_the_limits(train_cell):
    full = cells.load_cell(train_cell.name, cells.ROOT).config
    cfg = dict(full, n=CONTROL_N)
    cell = types.SimpleNamespace(config=cfg, task=train_cell.task,
                                 traffic=train_cell.traffic)
    data = train_cell.task.make_data(cfg, jax.random.PRNGKey(3))
    host = {k: np.asarray(v) for k, v in data.items()}
    rc = train.run_config(cell, 3, 10)
    passes = [1] * train.CHECK_ITERS
    ref = train.reference_run(cell, host, rc, passes)
    ctl = train.reference_run(cell, host, rc, passes, prec=BF16)
    values = train.compare(ctl, ref)
    limits = train_cell.limits["limits"]
    assert any(values[k] > v for k, v in limits.items()), values


@pytest.fixture(scope="module")
def serve_cell():
    return tiny_cell("serve-ocr")


def test_serve_sound_run_is_correct(serve_cell):
    r = run_cell(serve_cell, seconds=1.0)
    assert r["correct"], r["checked"]
    assert set(r["metrics"]) == {"serve_p95_ms", "setup_s"}
    assert r["attempted"] == 200 and r["failed"] == 0


def test_serve_traced_run_reads_its_per_layer_metrics(serve_cell,
                                                      monkeypatch):
    from benchkit import device

    monkeypatch.setattr(device, "peaks", lambda kind: {
        "flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})
    r = run_cell(serve_cell, seconds=1.0, trace=1)
    assert r["correct"]
    # the CPU has no TPU plane: only host-side metrics are read
    assert {"queue_ms.serve", "service_ms.serve"} <= set(r["metrics"])
    assert "busy_s" in r["device"] and "window_s" in r["device"]


def test_serve_answer_altered_is_not_correct(serve_cell, monkeypatch):
    from repro.serve.engine import ChainDecodeEngine

    real = ChainDecodeEngine.unpad

    def altered(self, labels, key):
        y = np.array(real(self, labels, key))
        y[0] = (y[0] + 1) % self.spec.num_labels
        return y
    monkeypatch.setattr(ChainDecodeEngine, "unpad", altered)
    r = run_cell(serve_cell, seconds=1.0)
    assert not r["correct"]
    assert r["checked"]["label_gap"]["value"] > \
        r["checked"]["label_gap"]["limit"]


def test_serve_half_the_batch_left_out_is_not_correct(serve_cell,
                                                      monkeypatch):
    from repro.serve.batcher import StructuredServer

    real = StructuredServer.step

    def half(self):
        reqs = real(self)
        return reqs[: (len(reqs) + 1) // 2]
    monkeypatch.setattr(StructuredServer, "step", half)
    r = run_cell(serve_cell, seconds=1.0)
    assert not r["correct"]
    assert r["checked"]["lost"]["value"] > 0


def test_serve_control_fails_the_limits(serve_cell):
    cfg = dict(cells.load_cell("serve-ocr", cells.ROOT).config)
    cell = types.SimpleNamespace(config=cfg, task=serve_cell.task,
                                 traffic=dict(serve_cell.traffic, pool=256))
    key_w, key_p = jax.random.split(jax.random.PRNGKey(5))
    weights = np.asarray(serve.make_weights(cfg, cell.traffic, key_w))
    data = cell.task.make_data(cfg, key_p, n=256)
    host = {k: np.asarray(v) for k, v in data.items()}
    lengths = host["mask"].sum(axis=1)
    w = serve.plan(cell.traffic, 1.0, lengths, 5, rate=256)
    keys = list(range(len(w.due)))
    ctl = serve.reference_labels(cfg, weights, host, w, keys, BF16)
    gaps = serve.label_gaps(cfg, weights, host, w, ctl)
    assert max(gaps) > serve_cell.limits["limits"]["label_gap"]
