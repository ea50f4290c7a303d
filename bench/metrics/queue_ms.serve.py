"""Median milliseconds a request waits from its due time to the start of
the round that serves it (the batcher's queue), over the requests
answered in the window."""
import numpy as np


def read(ctx):
    w = ctx.get("window")
    if w is None:
        return None
    sel = ~np.isnan(w.start)
    return 1e3 * float(np.median(w.start[sel] - w.due[sel])) \
        if sel.any() else None
