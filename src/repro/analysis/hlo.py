"""Layer 2: compiled-HLO cross-checks of the Layer-1 jaxpr facts.

The jaxpr layer counts the collectives *the program asked for*; XLA:SPMD
can introduce more (resharding all-reduces, all-gathers materializing a
replicated operand) or — on a 1-device mesh — elide some.  This layer
lowers the same traced programs (:class:`~.contracts.EngineTrace` from
Layer 1) to optimized HLO via ``jax.jit(fn).lower(*args).compile()`` and
parses the module text with
:func:`repro.launch.hlo_analysis.collective_bytes`, which buckets ops by
while-loop placement.  Invariants:

  * **H001** — the compiled module must not contain *more* collective
    ops than the jaxpr (per bucket: in-loop vs total).  More means XLA
    introduced communication the budgets never accounted for.
  * **H002** — a zero-collective-budget configuration (every
    single-device program) must compile to zero collective ops, full
    stop.
  * **H003** — the Pallas kernel tiling policies (plane_scores /
    plane_select block shapes, the viterbi label padding, the
    approx_block_steps DMA tiles) must produce (8, 128)-aligned
    (sublane, lane) tiles for every shape.
  * **H004** — every traced program must actually lower and compile.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax

from ..launch.hlo_analysis import CollectiveStats, collective_bytes
from .contracts import EngineTrace
from .findings import Finding


def lower_program(fn, args) -> str:
    """Optimized HLO text of one traced program (compiled for the
    current backend — CPU in CI; the collective *structure* is
    backend-independent)."""
    return jax.jit(fn).lower(*args).compile().as_text()


def check_hlo_trace(et: EngineTrace) -> Tuple[List[Finding],
                                              Dict[str, object]]:
    """Lower every program of one traced engine configuration and
    cross-check HLO collective counts against the jaxpr facts."""
    findings: List[Finding] = []
    facts: Dict[str, object] = {}
    exp_pass, exp_setup = et.expected_budgets()
    zero_budget = (exp_pass == 0 and exp_setup == 0)
    for prog in et.programs:
        where = f"{et.label}:{prog.name}"
        try:
            text = lower_program(prog.fn, prog.args)
        except Exception as e:  # noqa: BLE001 - any failure is a finding
            findings.append(Finding(
                "H004", where,
                f"failed to lower/compile for HLO analysis: "
                f"{type(e).__name__}: {e}"))
            continue
        stats: CollectiveStats = collective_bytes(text)
        hlo_total = stats.total_count
        hlo_in_loop = sum(stats.in_loop_count_by_kind.values())
        jax_total = prog.facts.total_collectives
        jax_pass = prog.facts.pass_collectives
        facts[f"{prog.name}_hlo_total"] = hlo_total
        facts[f"{prog.name}_hlo_in_loop"] = hlo_in_loop
        facts[f"{prog.name}_hlo_bytes"] = (stats.total_bytes
                                           + stats.total_in_loop_bytes)
        if zero_budget and hlo_total > 0:
            findings.append(Finding(
                "H002", where,
                f"zero-collective budget but optimized HLO contains "
                f"{hlo_total} collective op(s): "
                f"{dict(stats.count_by_kind)} + in-loop "
                f"{dict(stats.in_loop_count_by_kind)}"))
            continue
        if hlo_total > jax_total:
            findings.append(Finding(
                "H001", where,
                f"optimized HLO contains {hlo_total} collective op(s) "
                f"but the jaxpr only issues {jax_total} — XLA "
                f"introduced communication (HLO kinds: "
                f"{dict(stats.count_by_kind)} + in-loop "
                f"{dict(stats.in_loop_count_by_kind)})"))
        if hlo_in_loop > jax_pass:
            findings.append(Finding(
                "H001", where,
                f"{hlo_in_loop} collective op(s) inside HLO while "
                f"loop(s) but the jaxpr pass loop issues {jax_pass} — "
                f"a setup collective was sunk into the loop or XLA "
                f"added one (in-loop kinds: "
                f"{dict(stats.in_loop_count_by_kind)})"))
    return findings, facts


# ---------------------------------------------------------------------------
# Pallas tile-alignment checks (H003)

#: TPU fp32 native tile: 8 sublanes x 128 lanes.
SUBLANE, LANE = 8, 128

#: shape sweep: tiny/awkward/aligned (n-or-batch, d-or-labels) cases.
_TILE_SHAPES = ((1, 1), (3, 7), (8, 128), (17, 129), (63, 500),
                (128, 512), (1000, 1024), (257, 4097))


def check_tiles() -> List[Finding]:
    """Statically verify the kernel tiling policies produce
    (8, 128)-aligned blocks that evenly divide the padded operands.

    These are the exact block/padding rules the kernels pass to
    ``pl.BlockSpec`` — checking the policy functions over a shape sweep
    proves alignment for every launch without compiling Pallas.
    """
    from ..kernels.plane_scores import effective_blocks

    findings: List[Finding] = []

    def bad(kernel: str, msg: str) -> None:
        findings.append(Finding("H003", f"kernels/{kernel}", msg))

    for n, d in _TILE_SHAPES:
        for bn, bd in ((128, 512), (8, 128), (16, 256), (1000, 4096)):
            en, ed = effective_blocks(n, d, bn, bd)
            if en % SUBLANE or ed % LANE:
                bad("plane_scores.py",
                    f"effective_blocks({n}, {d}, {bn}, {bd}) -> "
                    f"({en}, {ed}) not ({SUBLANE}, {LANE})-aligned")
            # the kernels pad n,d up to the block and require the grid
            # to divide exactly
            if (n + (-n % en)) % en or (d + (-d % ed)) % ed:
                bad("plane_scores.py",
                    f"padded operand for ({n}, {d}) does not divide "
                    f"block ({en}, {ed})")

    # viterbi_step pads the label alphabet C to the lane width and tiles
    # the batch by block_b (default 8); both must stay aligned.
    for c in (1, 3, 26, 127, 128, 129, 500):
        cp = c + (-c % LANE)
        if cp % LANE:
            bad("viterbi.py",
                f"padded alphabet {c} -> {cp} not {LANE}-aligned")
    for block_b in (8, 16, 64):
        if block_b % SUBLANE:
            bad("viterbi.py",
                f"batch tile {block_b} not a multiple of {SUBLANE}")

    # approx_block_steps DMAs whole tiles: TILE slots of a block (or TILE
    # phi_i rows) by d+1 lanes padded as in HBM; a cap the tiles do not
    # divide must leave the pass to the scan.
    from ..kernels import approx_steps

    for _, d in _TILE_SHAPES:
        rows, lanes = approx_steps.tile_shape(d)
        if rows % SUBLANE or lanes % LANE or lanes < d + 1:
            bad("approx_steps.py",
                f"tile_shape({d}) -> ({rows}, {lanes}) not "
                f"({SUBLANE}, {LANE})-aligned over d+1 = {d + 1}")
    for cap in (1, 7, 10, 12, 100):
        if approx_steps.fits(64, cap, 128, 64):
            bad("approx_steps.py",
                f"fits() admits cap={cap}, which {approx_steps.TILE}-slot "
                f"tiles do not divide")
    return findings


def run_hlo_layer(traces: List[EngineTrace],
                  engines: Optional[List[str]] = None
                  ) -> Tuple[List[Finding], Dict[str, Dict[str, object]]]:
    """Cross-check every traced engine configuration + the tile rules."""
    findings: List[Finding] = []
    facts: Dict[str, Dict[str, object]] = {}
    for et in traces:
        if engines is not None and et.engine not in engines:
            continue
        fs, fx = check_hlo_trace(et)
        findings.extend(fs)
        if fx:
            facts[et.label] = fx
    findings.extend(check_tiles())
    return findings, facts
