"""Mean wall milliseconds of one ``StructuredServer.step`` round, over
the rounds of the window."""


def read(ctx):
    w = ctx.get("window")
    if w is None:
        return None
    if not w.rounds:
        return None
    return 1e3 * sum(r[1] - r[0] for r in w.rounds) / len(w.rounds)
