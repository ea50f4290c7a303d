"""Milliseconds of host wall time per evaluation (primal, dual, gap),
timed by the Solver around the evaluate call itself
(``TraceRow.eval_s``), mean over every iteration of the window's
trainings."""


def read(ctx):
    s = [getattr(r, "eval_s", None) for t in ctx.get("trainings", [])
         for r in t.rows]
    if not s or None in s:
        return None
    return 1e3 * sum(s) / len(s)
