"""Executables JAX made per outer iteration, compiled or loaded from
the persistent cache (``TraceRow.compiles``, the program's own
counter), mean over every iteration of the window's trainings."""


def read(ctx):
    n = [getattr(r, "compiles", None) for t in ctx.get("trainings", [])
         for r in t.rows]
    if not n or None in n:
        return None
    return sum(n) / len(n)
