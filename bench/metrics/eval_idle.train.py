"""Share of the time inside the program's ``repro:evaluate`` host spans
(the Solver's evaluation: primal, dual, gap) in which no operation ran
on the chip, over the traced iterations.  In percent.  The traced run
has the profiler's Python tracer on, which lengthens the op-by-op
evaluation's host side (a 265 ms span against 150 ms untraced, on a TPU
v5e), so this reads a few points above the untraced idle share."""
from benchkit import program


def read(ctx):
    prog = program.of(ctx)
    if prog is None or not ctx["trace"]["device"]:
        return None
    spans = program.spans_named(prog, program.names().EVALUATE)
    total = sum(s[2] for s in spans) / 1e9
    if total <= 0:
        return None
    return 100.0 * (1.0 - program.covered_seconds(ctx["trace"], spans)
                    / total)
