"""Host milliseconds per serving round spent on the device's work: the
round's ``repro:decode`` (the dispatch) and ``repro:sync`` (the wait for
the labels) spans in the traced tail, mean over the rounds that
decoded.  The traced tail runs under the profiler's Python tracer, which
these two spans barely feel (1.07 ms traced, 1.08 with the tracer off,
on a TPU v5e); the rest of the round, pure Python, it slows about
twofold, so the round's host part is no metric yet."""
from benchkit import program


def read(ctx):
    prog = program.of(ctx)
    waits = program.round_waits(prog) if prog is not None else []
    if not waits:
        return None
    return 1e3 * sum(waits) / len(waits)
