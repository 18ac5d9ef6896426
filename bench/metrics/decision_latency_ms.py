"""Milliseconds of window per selection decision: the whole window, up to
the moment the last ticket settled, over the decisions made in it (the
time a cluster waits for each probe decision, paper Table 3)."""


def read(ctx):
    n = sum(r.decisions for r in ctx["records"])
    return 1000.0 * ctx["window_s"] / n if n else None
