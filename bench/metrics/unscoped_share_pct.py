"""How much of the device's busy time the scope attribution misses: device
self time of the window's operations that carry no scope of the segment
program (XLA's own copies and loop control, the host path's programs, a
name whose result shape differs from the segment program's), over busy
time (``bench/metrics/_scopes.py``).  100 for a program that declares no
scopes."""

import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
import _scopes  # noqa: E402


def read(ctx):
    tr = ctx.get("trace")
    if not tr or not tr["busy_s"]:
        return None
    sec = _scopes.seconds(ctx)
    if sec is None:
        return 100.0
    if not sec:
        return None
    return 100.0 * sec[_scopes.UNSCOPED] / tr["busy_s"]
