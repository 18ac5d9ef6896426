"""The fits' bootstrap draws as a share of the device's busy time in the
window: device self time in scope ``fit/bootstrap`` (per-point keys,
uniforms, the Knuth sampler's running product and compare, the dead-tree
guard; ``bench/metrics/_scopes.py``)."""

import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
import _scopes  # noqa: E402


def read(ctx):
    return _scopes.share(ctx, "fit/bootstrap")
