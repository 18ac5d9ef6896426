"""Per-request latency of an open-loop window: from the time a request was
due to the time its result settled, over every request due in the window.
A request that failed, was refused or never settled counts as missing, at
infinity.  Percentiles are nearest-rank."""

import math


def latencies(ctx):
    out = []
    for r in ctx["records"]:
        if r.due is None:
            continue
        ok = r.status == "done" and r.settled is not None
        out.append(r.settled - (ctx["t0"] + r.due) if ok else math.inf)
    return sorted(out)


def percentile(ctx, q):
    lat = latencies(ctx)
    if not lat:
        return None
    return lat[max(math.ceil(q / 100.0 * len(lat)) - 1, 0)]
