"""Serving share of the chip's peak FLOP/s: the unary and Viterbi
operations of every request answered in the window
(``benchkit.flops.chain_decode``) over the window times the peak.  In
percent."""
import numpy as np

from benchkit import flops


def read(ctx):
    w = ctx.get("window")
    if w is None:
        return None
    cfg = ctx["config"]
    answered = ~np.isnan(w.done)
    if not answered.any():
        return None
    ops = sum(flops.chain_decode(float(L), int(cfg["f"]),
                                 int(cfg["num_labels"]))
              for L in w.length[answered])
    return 100.0 * ops / (w.end * ctx["peaks"]["flops_per_s"])
