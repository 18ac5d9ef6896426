#!/usr/bin/env python3
"""Readings behind each limit of the check: the served runs' numbers and
the controls' numbers, seed by seed, in one process on the chip.

    python3 bench/control.py --workload <name> --seeds 11,12,13 \
        --seconds <s> [--controls bf16,high] [--control-seeds 3] \
        [--faults kappa] [--fault-seeds 3]

For every seed it runs the cell's window as ``run.py`` does (same traffic,
same load), judges the served outcomes against the reference, and, on the
first ``--control-seeds`` seeds, judges each control at the same states:
the reference in bfloat16 (``bf16``), and the reference with the split
statistics' matrix product at ``Precision.HIGH`` (``high``, three bf16
passes — the step below the configuration's ``HIGHEST``).  Then, on the
first ``--fault-seeds`` seeds, it serves each planted fault — the program
run with a setting other than the configuration states (``kappa``: the
timeout cap's posterior slack 1.25 for 1.0) — and judges it against the
configuration as stated.  Each window's numbers, and in an open loop its
latency percentiles, are printed as one JSON line.  The benchmark's runs
never run it.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

import run

CONTROLS = {"bf16": {"dtype": "bfloat16"}, "high": {"precision": "high"}}
FAULTS = {"kappa": {"timeout_kappa": 1.25}}
LATENCY = run.data.load_module(run.BENCH / "metrics" / "_latency.py")


def window(cell, judged, seed: int, seconds: float, deciders: dict) -> dict:
    """Serve ``cell``'s window and judge it as ``judged`` states it."""
    r = run.serve(cell, seed, seconds, False)
    out = {"requests": len(r["records"]),
           "decisions": sum(x.decisions for x in r["records"])}
    if cell.mix["loop"] == "open":
        ctx = {"records": r["records"], "t0": r["t0"]}
        out.update({f"p{q}_s": LATENCY.percentile(ctx, q) for q in (50, 95)})
    out.update(run.judge(judged, r["svc"], r["records"], seed, deciders))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--controls", default="bf16,high")
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--faults", default="")
    ap.add_argument("--fault-seeds", type=int, default=3)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(run.ROOT / ".jax_cache")
    import jax
    if jax.devices()[0].platform != "tpu":
        run.say("needs a TPU")
        return 2
    sys.path.insert(0, str(run.ROOT / "src"))
    from repro import compile_cache
    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from reference import Reference
    cell = run.load_cell(run.ROOT, args.workload)
    names = [c for c in args.controls.split(",") if c]
    deciders = {c: Reference(cell.config["settings"], **CONTROLS[c])
                for c in names}
    seeds = [int(x) for x in args.seeds.split(",")]
    for i, seed in enumerate(seeds):
        use = deciders if i < args.control_seeds else {}
        print(json.dumps({"workload": args.workload, "seed": seed,
                          **window(cell, cell, seed, args.seconds, use)}),
              flush=True)
    for f in (f for f in args.faults.split(",") if f):
        st = dict(cell.config["settings"], **FAULTS[f])
        planted = dataclasses.replace(
            cell, config=dict(cell.config, settings=st))
        for seed in seeds[:args.fault_seeds]:
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "fault": f, **window(planted, cell, seed,
                                                   args.seconds, {})}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
