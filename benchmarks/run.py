"""Benchmark harness: one section per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--runs N] [--quick] [--only fig4,...]

Prints ``bench,key,value`` CSV lines; full artifacts land in
results/benchmarks/*.json.  Figures share one cached outcome store
(benchmarks.common), mirroring the paper's one-experiment-many-views layout.
"""

from __future__ import annotations

import argparse
import sys
import time

from benchmarks import (batched_vs_sequential, common, fig1a_landscape,
                        fig1b_disjoint, fig4_cno_tf, fig5_cno_scout_cp,
                        fig6_la_ablation, fig7_cno_vs_nex, fig8_budget,
                        fig9_nex, fig_timeout, streaming_throughput,
                        table3_latency, roofline, kernels_bench)

SECTIONS = {
    "fig1a": fig1a_landscape.main,
    "fig1b": fig1b_disjoint.main,
    "fig4": fig4_cno_tf.main,
    "fig5": fig5_cno_scout_cp.main,
    "fig6": fig6_la_ablation.main,
    "fig7": fig7_cno_vs_nex.main,
    "fig8": fig8_budget.main,
    "fig9": fig9_nex.main,
    "fig_timeout": fig_timeout.main,
    "table3": table3_latency.main,
    "batched": batched_vs_sequential.main,
    "streaming": streaming_throughput.main,
    "roofline": roofline.main,
    "kernels": kernels_bench.main,
}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--runs", type=int, default=20)
    ap.add_argument("--quick", action="store_true",
                    help="5 runs / reduced sweeps (CI smoke)")
    ap.add_argument("--only", default=None)
    ap.add_argument("--sequential", action="store_true",
                    help="drive figure sweeps through the sequential oracle "
                         "instead of the batched harness")
    ap.add_argument("--stream", action="store_true",
                    help="drive figure sweeps through the streaming tuning "
                         "service (audit mode for repro.service; outcomes "
                         "must match the batched backend)")
    ap.add_argument("--scheduler", choices=("compact", "lockstep"),
                    default="compact",
                    help="batched-backend scheduler: lane-compacting work "
                         "queue (default) or the fixed-lane lockstep "
                         "baseline")
    ap.add_argument("--bucket", default=None, metavar="M,F,T",
                    help="pad every job into this geometry bucket (padded "
                         "audit mode: outcomes must match the native "
                         "backend; cached under a distinct key)")
    args = ap.parse_args(argv)
    if args.sequential and args.stream:
        ap.error("--sequential and --stream are mutually exclusive")
    if args.bucket is not None:
        if args.sequential:
            ap.error("--bucket pads the batched/stream backends; the "
                     "sequential oracle always runs native")
        if args.scheduler == "lockstep":
            ap.error("--bucket requires the compact scheduler")
        try:
            widths = tuple(int(w) for w in args.bucket.split(","))
        except ValueError:
            widths = ()
        if len(widths) != 3 or any(w < 1 for w in widths):
            ap.error("--bucket expects three positive integers: M,F,T")
        common.DEFAULT_BUCKET = widths
    if args.sequential:
        common.DEFAULT_BACKEND = "sequential"
    elif args.stream:
        common.DEFAULT_BACKEND = "stream"
    common.DEFAULT_SCHEDULER = args.scheduler
    from repro import compile_cache
    compile_cache.enable()
    n_runs = 5 if args.quick else args.runs
    only = args.only.split(",") if args.only else list(SECTIONS)
    failed = []
    for name in only:
        t0 = time.time()
        print(f"== {name} ==", flush=True)
        try:
            SECTIONS[name](n_runs=n_runs, quick=args.quick)
        except Exception as e:  # keep the harness going; report at the end
            import traceback
            traceback.print_exc()
            print(f"bench,{name},ERROR,{type(e).__name__}", flush=True)
            failed.append(name)
        print(f"bench,{name},seconds,{time.time() - t0:.1f}", flush=True)
    if failed:
        print(f"bench: sections failed: {','.join(failed)}", file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
