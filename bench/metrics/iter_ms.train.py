"""Milliseconds per outer iteration of the fused MP-BCFW program and its
host loop (``TraceRow.time`` differences, evaluation excluded), mean
over every iteration of the window's trainings."""


def read(ctx):
    steps = []
    for t in ctx.get("trainings", []):
        prev = 0.0
        for r in t.rows:
            steps.append(r.time - prev)
            prev = r.time
    return 1e3 * sum(steps) / len(steps) if steps else None
