"""Approximate passes the slope rule ran per outer iteration
(``TraceRow.approx_passes``), mean over the window's iterations."""


def read(ctx):
    n = [r.approx_passes for t in ctx.get("trainings", []) for r in t.rows]
    return sum(n) / len(n) if n else None
