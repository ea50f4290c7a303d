"""Microseconds of device time per exact-oracle call: the operations
under the program's ``oracle`` scope inside its ``exact_pass`` scope in
the traced iterations (the union of their intervals), over the
exact-oracle calls those iterations made (their ``repro:iteration``
spans' ``exact_calls``)."""
from benchkit import program


def read(ctx):
    prog = program.of(ctx)
    if prog is None:
        return None
    sp = program.names()
    seconds = program.scope_seconds(prog, sp.EXACT_PASS, sp.ORACLE)
    calls = sum(int(m.get("exact_calls", 0))
                for m in program.iterations(prog))
    if seconds is None or not calls:
        return None
    return 1e6 * seconds / calls
