"""repro.analysis: the static program-contract checker.

Covers all three layers — jaxpr budget proofs on real engines, HLO
cross-checks, AST lint fixtures (one failing + one passing case per
rule, plus waivers), the repo-clean CI gate, the CLI exit codes, the
registration guard — and the runtime counterparts the static layers
certify (SyncLedger, CollectiveTrace).
"""
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import (RULES, count_program, lint_source, run_all,
                            run_jaxpr_layer)
from repro.analysis.contracts import trace_engine
from repro.analysis.hlo import check_hlo_trace, check_tiles
from repro.analysis.lint import parse_waivers, run_lint_layer


# ---------------------------------------------------------------------------
# count_program: the jaxpr walk itself


def test_count_program_psum_depths():
    """A psum outside a loop counts as setup; inside the while loop of a
    fori_loop as per-pass — under shard_map, like the real engines."""
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import make_data_mesh

    mesh = make_data_mesh(1, axis="i")

    def f(x):
        setup = jax.lax.psum(x, "i")

        def body(_, c):
            return c + jax.lax.psum(x * c, "i")

        return jax.lax.fori_loop(0, 3, body, setup)

    sharded = jax.shard_map(f, mesh=mesh, in_specs=P("i"), out_specs=P())
    jaxpr = jax.make_jaxpr(sharded)(jnp.ones(4))
    facts = count_program(jaxpr)
    assert facts.setup_collectives == 1, facts.detail
    assert facts.pass_collectives == 1, facts.detail
    assert facts.callbacks == 0


def test_count_program_clean_scan():
    jaxpr = jax.make_jaxpr(
        lambda x: jax.lax.scan(lambda c, _: (c * 2, c), x,
                               None, length=4))(jnp.ones(3))
    facts = count_program(jaxpr)
    assert facts.total_collectives == 0
    assert facts.f64_avals == 0


def test_count_program_detects_callback():
    def f(x):
        jax.debug.callback(lambda v: None, x)
        return x + 1

    facts = count_program(jax.make_jaxpr(f)(jnp.ones(2)))
    assert facts.callbacks == 1


# ---------------------------------------------------------------------------
# Layer 1: jaxpr budgets on the real engines


def test_jaxpr_budget_single_device():
    """The fused single-device program: 0 collectives, 0 callbacks."""
    et = trace_engine("mpbcfw")
    assert not et.on_mesh
    assert {p.name for p in et.programs} == {"outer", "continue"}
    for prog in et.programs:
        assert prog.facts.total_collectives == 0
        assert prog.facts.callbacks == 0
        assert prog.facts.f64_avals == 0


@pytest.mark.parametrize("name", ["mpbcfw-shard", "mpbcfw-shard-tau"])
def test_jaxpr_budget_shard(name):
    """The paper contract, proven statically: exactly 1 psum per
    approximate pass (inside the pass loop) + 1 setup reduction."""
    et = trace_engine(name)
    assert et.on_mesh
    for prog in et.programs:
        assert prog.facts.pass_collectives == 1, prog.facts.detail
        assert prog.facts.setup_collectives == 1, prog.facts.detail
        assert prog.facts.callbacks == 0


def test_jaxpr_layer_mesh_optional_traces_both():
    findings, facts, traces = run_jaxpr_layer(["mpbcfw-gram"])
    assert findings == []
    assert {t.label for t in traces} == {"mpbcfw-gram[single]",
                                         "mpbcfw-gram[mesh]"}
    assert facts["mpbcfw-gram[single]"]["outer_pass"] == 0
    assert facts["mpbcfw-gram[mesh]"]["outer_pass"] == 1


def test_jaxpr_layer_all_engines_clean():
    """Every registered engine's declared budgets are proven."""
    findings, facts, traces = run_jaxpr_layer()
    assert findings == [], [str(f) for f in findings]
    assert len(traces) >= 12  # 11 engines + the extra gram[mesh] config


# ---------------------------------------------------------------------------
# Rule J008: serving decode engines


def test_j008_builtin_serve_engines_clean():
    """The three shipped DecodeEngines' per-round programs are proven
    callback-, collective-, and f64-free."""
    from repro.analysis import check_serve_engines

    findings, facts = check_serve_engines()
    assert findings == [], [str(f) for f in findings]
    for label in ("serve:chain", "serve:multiclass", "serve:graph"):
        assert facts[label] == {"collectives": 0, "callbacks": 0,
                                "f64_avals": 0}


def test_j008_flags_callback_in_decode_program():
    """A decode engine that smuggles a host callback into its round
    program is caught statically."""
    import jax.numpy as jnp
    from repro import serve
    from repro.analysis import check_serve_engines
    from repro.core.oracles.multiclass import MulticlassSpec

    class LeakySpec(MulticlassSpec):
        pass

    class LeakyEngine(serve.MulticlassDecodeEngine):
        def _decode_batch(self, w, batch):
            jax.debug.callback(lambda: None)
            return super()._decode_batch(w, batch)

    def leaky_case():
        spec = LeakySpec(num_classes=2)
        model = serve.ServableModel(spec, jnp.zeros((10,), jnp.float32))
        engine = LeakyEngine(model)
        batch = engine.stack([
            engine.pad({"x": jnp.zeros(5), "y": jnp.int32(0)}, ())])
        return model, batch

    serve.register_decode_engine(LeakySpec, LeakyEngine,
                                 trace_case=leaky_case,
                                 trace_label="leaky")
    try:
        findings, facts = check_serve_engines()
        j8 = [f for f in findings if f.rule == "J008"
              and f.where == "serve:leaky"]
        assert len(j8) == 1 and "host-callback" in j8[0].message
        assert facts["serve:leaky"]["callbacks"] == 1
    finally:
        serve.unregister_decode_engine(LeakySpec, trace_label="leaky")
    findings, _ = check_serve_engines()
    assert findings == []


# ---------------------------------------------------------------------------
# Layer 2: HLO cross-check + tiles


def test_hlo_cross_check_shard():
    et = trace_engine("mpbcfw-shard")
    findings, facts = check_hlo_trace(et)
    assert findings == [], [str(f) for f in findings]
    # XLA kept both psums (1-device mesh still materializes all-reduce)
    assert facts["outer_hlo_total"] <= 2
    assert "outer_hlo_bytes" in facts


def test_hlo_zero_budget_single_device():
    et = trace_engine("mpbcfw")
    findings, facts = check_hlo_trace(et)
    assert findings == []
    assert facts["outer_hlo_total"] == 0


def test_tile_policies_aligned():
    assert check_tiles() == []


# ---------------------------------------------------------------------------
# Layer 3: lint fixtures — each rule has a failing and a passing case

_HOT = "repro/shard/hot.py"       # in R004 scope (+ R003, R005 scopes)
_COLD = "repro/api/cold.py"       # outside the hot-path scopes


def _rules(findings):
    return [f.rule for f in findings]


def test_r001_flags_raw_sentinel():
    src = "LO = -1e30\nHI = 1e30\n"
    assert _rules(lint_source(_COLD, src)) == ["R001", "R001"]


def test_r001_allows_ops_and_invalid_score():
    assert lint_source("repro/kernels/ops.py", "INVALID_SCORE = -1e30\n") \
        == []
    src = "from .ops import INVALID_SCORE\nneg = INVALID_SCORE\n"
    assert lint_source("repro/kernels/viterbi.py", src) == []


def test_r002_flags_removed_names():
    src = ("from repro.core.types import WorkSet\n"
           "from repro.core.driver import run\n"
           "ws = WorkSet\n"
           "gc = GramCache()\n"
           "res = driver.run(problem)\n")
    rules = _rules(lint_source(_COLD, src))
    assert rules.count("R002") == 5


def test_r002_has_no_shim_waivers_anymore():
    """The one-release shims are deleted, so the former waiver files are
    held to R002 like everything else — and the retired shim module's
    mere existence in a tree is a finding."""
    src = "from ..cache.state import PlaneCache as WorkSet\n"
    assert _rules(lint_source("repro/core/types.py", src)) == ["R002"]


def test_r002_flags_resurrected_workset_module(tmp_path):
    shim = tmp_path / "repro" / "core"
    shim.mkdir(parents=True)
    (shim / "workset.py").write_text("# back from the dead\n")
    findings = run_lint_layer(tmp_path)
    assert [f.rule for f in findings] == ["R002"]
    assert "repro/core/workset.py" in findings[0].where


def test_r003_flags_direct_psum_in_shard():
    src = ("import jax.lax as lax\n"
           "def f(x):\n    return lax.psum(x, 'data')\n")
    assert _rules(lint_source(_HOT, src)) == ["R003"]
    # same code outside repro/shard/ is not R003's business
    assert lint_source(_COLD, src) == []


def test_r003_allows_collective_trace():
    src = ("import jax\n"
           "class CollectiveTrace:\n"
           "    def psum(self, x, axis, *, tag):\n"
           "        return jax.lax.psum(x, axis)\n")
    assert lint_source("repro/shard/telemetry.py", src) == []


def test_r004_flags_host_syncs_in_hot_path():
    src = ("import numpy as np\n"
           "def step(x):\n"
           "    a = float(x)\n"
           "    b = np.asarray(x)\n"
           "    c = x.item()\n"
           "    x.block_until_ready()\n"
           "    return a, b, c\n")
    assert _rules(lint_source(_HOT, src)) == ["R004"] * 4


def test_r004_exempts_init_and_module_level():
    src = ("lam0 = float('1.0')\n"
           "class E:\n"
           "    def __init__(self, lam):\n"
           "        self.lam = float(lam)\n")
    assert lint_source(_HOT, src) == []
    # and hot-path rules don't apply outside the hot scope at all
    src2 = "def f(x):\n    return float(x)\n"
    assert lint_source(_COLD, src2) == []


def test_r005_flags_float64_in_device_code():
    src = ("import jax.numpy as jnp\n"
           "def f(x):\n"
           "    return jnp.zeros(3, jnp.float64), "
           "jnp.zeros(3, dtype='float64')\n")
    assert _rules(lint_source(_HOT, src)) == ["R005", "R005"]


def test_r005_allows_host_np_float64():
    src = ("import numpy as np\n"
           "def fit(xs):\n    return np.asarray(xs, np.float64)\n")
    assert lint_source(_COLD, src) == []


def test_waiver_suppresses_only_named_rule():
    src = ("def step(x):\n"
           "    a = float(x)  # repro: allow[R004] measured host read\n"
           "    b = float(x)  # repro: allow[R001] wrong rule id\n"
           "    return a, b\n")
    assert _rules(lint_source(_HOT, src)) == ["R004"]


def test_waiver_parser_multi_rule():
    w = parse_waivers("x = 1  # repro: allow[R001, R004] both\n")
    assert w == {1: {"R001", "R004"}}


def test_syntax_error_is_reported_not_raised():
    assert _rules(lint_source(_COLD, "def f(:\n")) == ["R000"]


def test_rule_table_covers_all_rules():
    for rid in ("J001", "J002", "J003", "J004", "J005", "J006", "J007",
                "J008",
                "H001", "H002", "H003", "H004",
                "R001", "R002", "R003", "R004", "R005"):
        assert rid in RULES


# ---------------------------------------------------------------------------
# The CI gate: the repo itself is clean


def test_repo_is_lint_clean():
    findings = run_lint_layer()
    assert findings == [], [str(f) for f in findings]


def test_run_all_lint_on_fixture_tree(tmp_path):
    bad = tmp_path / "repro" / "api"
    bad.mkdir(parents=True)
    (bad / "mod.py").write_text("SENTINEL = -1e30\n")
    report = run_all(layers=["lint"], root=tmp_path)
    assert not report.ok
    assert [f.rule for f in report.findings] == ["R001"]
    assert "R001" in report.to_json()


def test_run_all_rejects_unknown_layer():
    with pytest.raises(ValueError):
        run_all(layers=["jaxpr", "nope"])


# ---------------------------------------------------------------------------
# CLI


def test_cli_strict_exit_codes(tmp_path):
    from repro.analysis.__main__ import main

    bad = tmp_path / "repro" / "api"
    bad.mkdir(parents=True)
    (bad / "mod.py").write_text("SENTINEL = 1e30\n")
    assert main(["--layer", "lint", "--strict",
                 "--root", str(tmp_path)]) == 1
    # without --strict findings are reported but the exit stays 0
    assert main(["--layer", "lint", "--root", str(tmp_path)]) == 0
    (bad / "mod.py").write_text("SENTINEL = None\n")
    assert main(["--layer", "lint", "--strict",
                 "--root", str(tmp_path)]) == 0
    assert main(["--rules"]) == 0


@pytest.mark.slow
def test_cli_strict_subprocess():
    """The exact CI command exits 0 on the repo (jaxpr layer only to
    keep tier-1 time bounded; --analyze in ci.sh runs all layers)."""
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--strict",
         "--layer", "jaxpr", "--json"],
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert '"ok": true' in proc.stdout


# ---------------------------------------------------------------------------
# Registration guard


def test_registration_guard_rejects_undeclared_mesh_engine():
    from repro.analysis import install_registration_guard
    from repro.api.engine import (EngineCapabilities, register_engine,
                                  remove_registration_hook,
                                  unregister_engine)

    hook = install_registration_guard()
    try:
        with pytest.raises(ValueError, match="collectives_per_pass"):
            register_engine(
                "bad-mesh-engine", lambda p, cfg: None,
                EngineCapabilities(supports_mesh=True))
        # declared budgets register fine
        register_engine(
            "ok-mesh-engine", lambda p, cfg: None,
            EngineCapabilities(supports_mesh=True, collectives_per_pass=1,
                               collectives_setup=1))
    finally:
        remove_registration_hook(hook)
        unregister_engine("ok-mesh-engine")
    from repro.api import algorithms

    assert "bad-mesh-engine" not in algorithms()
    assert "ok-mesh-engine" not in algorithms()


def test_capability_validation_rejects_negative_budget():
    from repro.api.engine import (EngineCapabilities, register_engine)

    with pytest.raises(ValueError):
        register_engine("neg-budget", lambda p, cfg: None,
                        EngineCapabilities(collectives_per_pass=-1))


# ---------------------------------------------------------------------------
# Runtime counterparts: SyncLedger / CollectiveTrace direct units


def test_sync_ledger_counts_and_sync():
    from repro.core.selection import SyncLedger

    led = SyncLedger()
    assert led.counts() == (0, 0, 0)
    led.dispatched()
    led.dispatched(2)
    led.collected(5)
    tree = {"a": jnp.arange(3), "b": (jnp.ones(2), 7)}
    host = led.sync(tree)
    assert led.counts() == (1, 5, 3)
    assert host["b"][1] == 7
    assert [int(v) for v in host["a"]] == [0, 1, 2]
    # snapshots difference cleanly across an interval
    before = led.counts()
    led.dispatched()
    led.sync(jnp.zeros(1))
    after = led.counts()
    assert (after[0] - before[0], after[2] - before[2]) == (1, 1)


def test_collective_trace_counts_sites_per_program():
    from repro.shard.telemetry import CollectiveTrace

    tr = CollectiveTrace()

    def prog(x):
        tr.begin("multi_approx")
        s = tr.psum(x, "i", tag="setup")
        out = tr.psum(s, "i", tag="pass") + tr.psum(s, "i", tag="pass")
        tr.commit()
        return out

    res = jax.vmap(prog, axis_name="i")(jnp.arange(4.0))
    assert tr.count("multi_approx", "setup") == 1
    assert tr.count("multi_approx", "pass") == 2
    assert tr.count("multi_approx", "missing") == 0
    assert tr.count("other", "setup") == 0
    assert float(res[0]) == pytest.approx(4 * 6.0 * 2)

    # a retrace overwrites instead of accumulating
    jax.vmap(prog, axis_name="i")(jnp.arange(8.0))
    assert tr.count("multi_approx", "pass") == 2
