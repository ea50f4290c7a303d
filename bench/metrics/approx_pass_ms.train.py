"""Milliseconds of device time per approximate pass: the union of the
intervals of the operations under the program's ``approx_pass`` scope in
the traced iterations, over the approximate passes they ran (their
``repro:iteration`` spans' ``approx_passes``)."""
from benchkit import program


def read(ctx):
    prog = program.of(ctx)
    if prog is None:
        return None
    seconds = program.scope_seconds(prog, program.names().APPROX_PASS)
    passes = sum(int(m.get("approx_passes", 0))
                 for m in program.iterations(prog))
    if seconds is None or not passes:
        return None
    return 1e3 * seconds / passes
