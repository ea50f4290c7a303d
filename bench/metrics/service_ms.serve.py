"""Median milliseconds from the start of a request's round to its
answer on the host (pad, stack, transfer, dispatch, decode, sync,
unpad), over the requests answered in the window."""
import numpy as np


def read(ctx):
    w = ctx.get("window")
    if w is None:
        return None
    sel = ~np.isnan(w.start)
    return 1e3 * float(np.median(w.done[sel] - w.start[sel])) \
        if sel.any() else None
