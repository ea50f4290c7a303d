"""Share of the traced window in which no operation ran on the chip
(1 - union of device operation intervals / window), in percent."""
from benchkit import trace


def read(ctx):
    return trace.idle_share(ctx.get("trace"), ctx.get("trace_window_s"))
