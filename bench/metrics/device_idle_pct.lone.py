"""Share of the window in which no operation ran on the chip: one minus
the union of the trace's operation intervals over the window's length."""


def read(ctx):
    tr = ctx["trace"]
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
