"""Cells, configurations, traffic mixes and metrics are found by name,
and the command refuses to measure without a TPU."""
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from benchkit import cells

ROOT = Path(__file__).resolve().parents[1]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def bench_json():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_benchmark_json_keeps_the_contract_shape():
    b = bench_json()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["paths"] == ["bench"] and b["command"][1] == "bench/run.py"
    names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    names += [w["name"] for w in b["workloads"]]
    names += [c["name"] for c in b["configs"]]
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    assert any(m["name"] == "setup_s" for m in b["end_to_end"])
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for c in b["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert isinstance(c["reduced"], list) and cfg["name"] == c["name"]


@pytest.mark.parametrize("name", [w["name"] for w in
                                  bench_json()["workloads"]])
def test_every_cell_is_found_by_name(name):
    cell = cells.load_cell(name, ROOT)
    assert cell.chips in (1, 4)
    assert cell.driver.run and cell.task.make_data
    e2e = {m.name for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert m.entry["moves"] in e2e
        # every reader finds nothing to read in an empty context
        assert cells.reader(m.name, ROOT)({}) is None


def test_a_cell_metric_and_traffic_are_added_by_files_alone(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "out"))
    b = bench_json()
    b["workloads"].append({"name": "serve-ocr-burst", "config": "ocr",
                           "traffic": "serve-ocr-burst", "chips": 1,
                           "why": "on/off bursts"})
    b["per_layer"].append({"name": "burst_ms.serve", "unit": "ms",
                           "better": "lower", "source": "host_clock",
                           "layer": "serve/batcher.py",
                           "moves": "serve_p95_ms",
                           "workloads": ["serve-ocr-burst"]})
    b["end_to_end"][1]["workloads"].append("serve-ocr-burst")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    traffic = json.loads((ROOT / "bench/traffic/serve-ocr.json").read_text())
    traffic["rate_per_s"] = 123.0
    (tmp_path / "bench/traffic/serve-ocr-burst.json").write_text(
        json.dumps(traffic))
    (tmp_path / "bench/metrics/burst_ms.serve.py").write_text(
        "def read(ctx):\n    return ctx.get('burst')\n")
    cell = cells.load_cell("serve-ocr-burst", tmp_path)
    assert cell.traffic["rate_per_s"] == 123.0
    assert cell.config["name"] == "ocr"
    assert [m.name for m in cell.per_layer] == ["burst_ms.serve"]
    assert {m.name for m in cell.end_to_end} == {"serve_p95_ms", "setup_s"}
    got = cells.read_per_layer(cell, {"burst": 4.5}, tmp_path)
    assert got == {"burst_ms.serve": {"value": 4.5, "unit": "ms"}}
    assert cells.read_per_layer(cell, {}, tmp_path) == {}


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "serve-ocr",
         "--seed", "4294967301", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_means_a_nonzero_exit_and_no_result():
    r = _run(ROOT)
    assert r.returncode != 0
    assert "TPU" in r.stderr
    assert not any(line.startswith("{") for line in r.stdout.splitlines())


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "out"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    r = _run(tmp_path)
    assert r.returncode != 0
    assert r.stdout.strip() == ""


def test_compile_counter_counts_only_while_armed():
    import jax
    import jax.numpy as jnp

    from benchkit.device import CompileCounter

    counter = CompileCounter()
    jax.jit(lambda x: x * 3.0 + 1.0)(jnp.ones(5))
    assert (counter.made, counter.loaded) == (0, 0)
    counter.armed = True
    jax.jit(lambda x: jnp.sin(x) * 7.0 - 2.0)(jnp.ones(6))
    counter.armed = False
    assert counter.made >= 1
    assert counter.compiled == counter.made - counter.loaded
    assert len(counter.compiled_names) == counter.compiled
    assert any("lambda" in name for name in counter.compiled_names)
    assert counter.note().startswith(f"compiles_in_window {counter.compiled} ")
