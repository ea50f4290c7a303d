"""Share of the approximate passes' device time that the
``approx_block_steps`` kernel takes: the union of the intervals of the
operations named ``approx_block_steps`` over the union of those under
the program's ``approx_pass`` scope, each averaged over the chips, in
the traced iterations.  In percent; 0 where the passes run as a scan of
block steps, which makes no such operation."""
from benchkit import program, trace

KERNEL = "approx_block_steps"


def read(ctx):
    prog = program.of(ctx)
    if prog is None:
        return None
    scoped = program.scope_seconds(prog, program.names().APPROX_PASS)
    if not scoped:
        return None
    per = [sum(e - s for s, e in trace.union(
        [[s, s + d] for name, s, d in evs if name == KERNEL])) / 1e9
        for chip, evs in ctx["trace"]["device"].items()
        if chip in prog["scoped"]]
    return 100.0 * sum(per) / max(len(per), 1) / scoped
