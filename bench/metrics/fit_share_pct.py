"""The forest fits' share of the device's busy time in the window: device
self time of the segment program's operations in scope ``fit`` and every
scope below it (``bench/metrics/_scopes.py``), root and speculative fits
together.  Nothing to read when no operation of the scope ran."""

import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
import _scopes  # noqa: E402


def read(ctx):
    return _scopes.share(ctx, "fit")
