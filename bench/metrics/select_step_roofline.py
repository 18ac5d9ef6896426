"""The fused selector kernel's share of its roofline: the least time the
chip could take for the sweeps the window's busy seats needed (bench/
work.py: elementwise float32 operations against the vector unit's rate,
bytes against HBM's, from the peak table) over the kernel's device time
in the trace.  Nothing to read without the kernel in the trace."""

import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
import work  # noqa: E402


def read(ctx):
    tr = ctx["trace"]
    kernel_s = (tr or {}).get("kernels", {}).get("select_step")
    seat_steps = ctx["m1"].busy_slot_steps - ctx["m0"].busy_slot_steps
    if not kernel_s or seat_steps <= 0:
        return None
    st = ctx["cell"].config["settings"]
    job = ctx["svc"].jobs[0].space
    w = work.select_step(job.m, job.raw.shape[1], int(st["n_trees"]),
                         int(st["depth"]), int(st["la"]), int(st["k_gh"]))
    least, _ = work.roofline_s(w, ctx["peaks"])
    return 100.0 * least * seat_steps / kernel_s
