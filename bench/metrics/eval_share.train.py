"""Share of training wall time outside ``TraceRow.time``: the
evaluation (primal, dual, gap) that ``Solver`` excludes from its clock.

``1 - sum(last TraceRow.time) / sum(wall from the first iterate to the
last row)`` over the window's trainings, in percent."""


def read(ctx):
    ts = [t for t in ctx.get("trainings", []) if t.rows]
    wall = sum(t.start + t.stamps[-1] - t.iter_start for t in ts)
    if not ts or wall <= 0:
        return None
    return 100.0 * (1.0 - sum(t.rows[-1].time for t in ts) / wall)
