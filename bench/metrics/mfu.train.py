"""Whole-training share of the chip's peak FLOP/s: the operations MP-BCFW
requires (exact oracles, block updates, plane scoring of the valid
planes in the passes run, the evaluation's oracles;
``benchkit.flops.training_iteration``) over every iteration of the
window's trainings, over their wall time times the peak.  In percent."""
from benchkit import flops


def read(ctx):
    ts = [t for t in ctx.get("trainings", []) if t.rows]
    wall = sum(t.stamps[-1] for t in ts)
    if not ts or wall <= 0:
        return None
    cfg = ctx["config"]
    ops = sum(flops.training_iteration(int(cfg["n"]), ctx["task_dim"],
                                       ctx["oracle_ops"], r.approx_passes,
                                       r.ws_mean)
              for t in ts for r in t.rows)
    return 100.0 * ops / (wall * ctx["peaks"]["flops_per_s"])
