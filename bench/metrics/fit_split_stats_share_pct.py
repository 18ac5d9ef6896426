"""The fits' split statistics as a share of the device's busy time in the
window: device self time in scope ``fit/split_stats`` (the node one-hot and
each level's node-statistics product at ``Precision.HIGHEST``;
``bench/metrics/_scopes.py``)."""

import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
import _scopes  # noqa: E402


def read(ctx):
    return _scopes.share(ctx, "fit/split_stats")
