"""Milliseconds the service spends rebuilding one finished run's Outcome
from its banked row on the host (``_reconstruct_outcome``: recommendation,
trajectory, spend replay): the mean of the flight recorder's ``outcome``
spans in the window, one per rebuilt outcome.  A program whose phase
vocabulary has no ``outcome`` times no rebuild and reads 0; one that has
it but recorded no such span reads nothing."""


def read(ctx):
    spans = [e.data["dur_s"] for e in ctx["events"]
             if e.kind == "span" and e.data.get("phase") == "outcome"]
    if spans:
        return 1000.0 * sum(spans) / len(spans)
    from repro.obs import PHASES
    return None if "outcome" in PHASES else 0.0
