#!/usr/bin/env python3
"""Chip smoke: the served tuning path on one TPU, checked against the oracle.

    python3 chip_smoke.py              # one chip (the default)
    python3 chip_smoke.py --chips 4    # only the four-shard identity check

One process; it starts no child.  It fails (non-zero exit, no result line)
unless JAX's first device is a TPU — there is no CPU branch.  At the paper's
selector settings (``la=2, k_gh=3``, 10 trees of depth 4, exact refit,
timeouts on) over the three 384-point TensorFlow jobs it:

1. compiles the service's segment program ahead of time (the served phases
   then find it in the persistent compile cache), prints its compile
   seconds and ``memory_analysis`` bytes, and requires that it fits the
   device and holds the compiled Pallas selector (``tpu_custom_call``);
2. serves requests through ``StreamingTuner.submit`` → ``TuningTicket.
   result`` (two per job, mixed budgets) on ``ServiceConfig``'s default
   lane count;
3. replays every request through the sequential oracle ``optimize`` on the
   chip and requires zero drifting runs, ``spend_trajectory`` included;
4. serves one request with ``fused_selector="ref"`` (the unfused program,
   on a one-lane service) and requires it to equal the fused one.

``--chips 4`` instead serves four requests, one per shard, on
``num_shards=4`` and the same four on ``num_shards=1``, and requires
byte-identical outcomes, with the four shards on four distinct devices and
one request on each.  Requests are submitted to the running service, as a
user's would be.  The last line of output is the JSON result.
The benchmark outcome cache (``results/benchmarks/cache/``) is never read.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent

# (job index, seed, budget multiplier b): two requests per TF job, mixed b.
REQUESTS = ((0, 0, 1.5), (0, 1, 2.5), (1, 2, 1.5), (1, 3, 2.5),
            (2, 4, 1.5), (2, 5, 2.5))
# The four-shard check: one request per shard, shorter budgets.
SHARD_REQUESTS = ((0, 6, 1.25), (1, 7, 1.1), (2, 8, 1.25), (0, 9, 1.1))
RESULT_TIMEOUT_S = 900.0


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(*parts) -> None:
    print("chip_smoke:", *parts, flush=True)


def paper_settings(**kw):
    from repro.core import Settings
    return Settings(policy="lynceus", la=2, k_gh=3, n_trees=10, depth=4,
                    refit="exact", timeout=True, **kw)


def serve(jobs, settings, config, requests):
    """Submit ``requests`` to a started tuner; returns (outcomes, each
    ticket's shard, the tuner's engines, wall seconds).  The tuner is
    stopped before returning."""
    from repro.service import StreamingTuner
    with StreamingTuner(jobs, settings, config) as tuner:
        t0 = time.perf_counter()
        tuner.start()
        tickets = [tuner.submit(job=jobs[j], seed=seed, budget_b=b)
                   for j, seed, b in requests]
        outcomes = [t.result(timeout=RESULT_TIMEOUT_S) for t in tickets]
        wall = time.perf_counter() - t0
        shards = [t.shard for t in tickets]
        engines = list(tuner._engines.shards)
        m = tuner.metrics()
    # serve_seconds sums every shard's time inside segments, including a
    # compile that no persistent cache entry spared.
    say(f"  {config.num_shards} shard(s) x {config.lane_slots} lanes: "
        f"{m.segments} segments, {m.steps} steps, serve_seconds "
        f"{m.serve_seconds:.3f} ({m.serve_seconds / max(m.steps, 1):.4f} "
        f"s/step), lane_occupancy {m.lane_occupancy:.3f}")
    return outcomes, shards, engines, wall


def compile_segment(jobs, settings, config):
    """The deployment's segment program, compiled ahead of time
    (``repro.obs.segment_program``); returns (compiled, seconds)."""
    from repro.obs import segment_program
    t0 = time.perf_counter()
    compiled = segment_program(jobs, settings, config)
    return compiled, time.perf_counter() - t0


def drift(expected, actual) -> list[int]:
    """Indices of the runs whose outcomes differ (``outcomes_equal``, the
    benchmarks' comparator: every pinned field, spend_trajectory
    included); each difference is printed."""
    from benchmarks.common import outcomes_equal
    from repro.obs import diff_outcomes
    bad = [i for i, (e, a) in enumerate(zip(expected, actual, strict=True))
           if not outcomes_equal(e, a)]
    for i in bad:
        for line in diff_outcomes([expected[i]], [actual[i]]):
            say(f"DRIFT request {i}:", line)
    return bad


def one_chip_phases(device) -> None:
    from repro.core import lookahead, optimize
    from repro.jobs import tensorflow_jobs
    from repro.service import ServiceConfig

    jobs = tensorflow_jobs(0)
    check(all(j.space.n_points == 384 for j in jobs),
          "expected the three 384-point TensorFlow spaces")
    s = paper_settings()
    mode = lookahead._fused_mode(s)
    say("fused_selector resolved to", repr(mode))
    check(mode == "pallas", f"fused selector resolved to {mode!r}, not "
          "the compiled Pallas kernel")
    config = ServiceConfig()
    say("lane_slots", config.lane_slots)

    # 1. The segment program, compiled ahead of time.
    compiled, compile_s = compile_segment(jobs, s, config)
    mem = compiled.memory_analysis()
    program_bytes = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
                     + mem.output_size_in_bytes)
    limit = device.memory_stats()["bytes_limit"]
    say(f"segment compile_seconds {compile_s:.3f}")
    say(f"segment memory_analysis temp={mem.temp_size_in_bytes} "
        f"args={mem.argument_size_in_bytes} "
        f"out={mem.output_size_in_bytes} total={program_bytes} "
        f"device_bytes_limit={limit}")
    check(program_bytes < limit, "segment program does not fit the device")
    check("tpu_custom_call" in compiled.as_text(),
          "no tpu_custom_call (Pallas kernel) in the compiled segment")

    # 2. The served path.
    served, _, _, wall = serve(jobs, s, config, REQUESTS)
    say(f"served {len(served)} requests, warm_wall_seconds {wall:.3f}")
    for (j, seed, b), o in zip(REQUESTS, served):
        say(f"request job={jobs[j].name} seed={seed} b={b}: nex={o.nex} "
            f"recommended={o.recommended} cno={o.cno:.4f} "
            f"censored={len(o.censored)}")

    # 3. The sequential oracle, on the chip, same requests and settings.
    t0 = time.perf_counter()
    oracle = [optimize(jobs[j], s, budget_b=b, seed=seed)
              for j, seed, b in REQUESTS]
    say(f"oracle wall_seconds {time.perf_counter() - t0:.3f}")
    bad = drift(oracle, served)
    say(f"drifting_runs {len(bad)} of {len(served)}")
    check(not bad, f"{len(bad)} served runs drift from the oracle")

    # 4. First parity check of the compiled kernel on real hardware: the
    # unfused program serves one request identically.
    ref_s = dataclasses.replace(s, fused_selector="ref")
    check(lookahead._fused_mode(ref_s) is None, "ref mode is not unfused")
    one_lane = ServiceConfig(lane_slots=1)
    _, compile_s = compile_segment(jobs, ref_s, one_lane)
    say(f"unfused one-lane segment compile_seconds {compile_s:.3f}")
    (ref_out,), _, _, wall = serve(jobs, ref_s, one_lane, REQUESTS[:1])
    say(f"unfused one-lane service wall_seconds {wall:.3f}")
    bad = drift(served[:1], [ref_out])
    say(f"fused_vs_ref_equal {not bad}")
    check(not bad, "fused and unfused selectors disagree on the chip")


def four_chip_phase(devices) -> None:
    from repro.jobs import tensorflow_jobs
    from repro.service import ServiceConfig

    jobs = tensorflow_jobs(0)
    s = paper_settings()
    runs = {}
    for n in (4, 1):
        t0 = time.perf_counter()
        runs[n], shards, engines, _ = serve(
            jobs, s, ServiceConfig(num_shards=n), SHARD_REQUESTS)
        say(f"num_shards={n}: served {len(runs[n])} requests in "
            f"{time.perf_counter() - t0:.3f} s (compile included), "
            f"ticket shards {shards}")
        if n == 4:
            homes = [next(iter(e._carry["y"].devices())) for e in engines]
            say("shard devices", [str(d) for d in homes])
            check(len(set(homes)) == 4 and set(homes) == set(devices[:4]),
                  "the four shards do not sit on four distinct devices")
            check(sorted(shards) == [0, 1, 2, 3],
                  "the requests did not spread over all four shards")
    bad = drift(runs[1], runs[4])
    say(f"four_shard_vs_one_identical {not bad}")
    check(not bad, "num_shards=4 outcomes differ from num_shards=1")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-shard identity check")
    args = ap.parse_args(argv)
    t0 = time.perf_counter()

    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX's first device is "
              f"{platform!r}", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX sees {len(devices)}", file=sys.stderr)
        return 2
    say("device_kind", devices[0].device_kind, "device_count",
        len(devices))

    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from repro import compile_cache
    say("compile_cache_dir", compile_cache.enable())

    if args.chips == 4:
        four_chip_phase(devices)
    else:
        one_chip_phases(devices[0])
    say("peak_bytes_in_use", devices[0].memory_stats()["peak_bytes_in_use"])
    say(f"phases_done_seconds {time.perf_counter() - t0:.3f}")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
