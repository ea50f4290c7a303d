"""Share of the decoded positions that are padding: positions past a
request's length inside its bucket, and the filler rows that complete a
batch, over the bucket length times the batch size of every round of
the window.  In percent."""


def read(ctx):
    w = ctx.get("window")
    if w is None:
        return None
    total = sum(r[4] * ctx["batch_size"] for r in w.rounds)
    if not total:
        return None
    return 100.0 * (1.0 - sum(r[3] for r in w.rounds) / total)
