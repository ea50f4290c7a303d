"""Milliseconds of device time under the program's ``exact_pass`` scope
per traced outer iteration: the union of the intervals of the
operations that carry it (the exact-pass scan with its oracle calls and
plane insertions)."""
from benchkit import program


def read(ctx):
    prog = program.of(ctx)
    if prog is None:
        return None
    seconds = program.scope_seconds(prog, program.names().EXACT_PASS)
    iters = len(program.iterations(prog))
    if seconds is None or not iters:
        return None
    return 1e3 * seconds / iters
