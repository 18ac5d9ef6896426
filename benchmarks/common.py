"""Shared benchmark machinery: cached policy runs + CSV helpers.

Every figure pulls from one memoized outcome store, so e.g. Fig 4/6/7 reuse
the same simulated optimizations (the paper does the same: one experiment,
several views).  Cache key = (dataset, job, policy, la, refit, b, n_runs,
backend) — where backend carries the scheduler (batched) or the segment/
service knobs (stream), so no backend is ever served another's files.
"""

from __future__ import annotations

import json
import pathlib

import numpy as np

from repro.core import (RunRequest, Settings, metrics, run_many,
                        run_many_batched)
from repro.jobs import cherrypick_jobs, scout_jobs, tensorflow_jobs

CACHE = pathlib.Path("results/benchmarks/cache")
OUT = pathlib.Path("results/benchmarks")

POLICY_SET = [("rnd", 0), ("bo", 0), ("la0", 0), ("lynceus", 1),
              ("lynceus", 2)]

# Figure sweeps run on the batched device-resident harness by default; flip
# to "sequential" (benchmarks.run --sequential) to audit any figure against
# the one-run-at-a-time oracle, or to "stream" (--stream) to audit the
# streaming tuning service end to end.
DEFAULT_BACKEND = "batched"
# Which batched scheduler drains the sweep: "compact" (lane-compacting work
# queue, default) or "lockstep" (fixed lanes; benchmarks.run --scheduler).
DEFAULT_SCHEDULER = "compact"
# Geometry bucket (m, f, t) the batched/stream sweeps pad every job into,
# or None for native geometry (benchmarks.run --bucket m,f,t — the padded
# audit mode).  Part of the cache key: padded runs are bit-identical to
# native ones on audited configs, but an audit that silently read native
# (PR 3/4-era) cache files would be vacuous.
DEFAULT_BUCKET = None
# Segment/service knobs the "stream" backend sweeps run under.  Part of the
# stream cache key: pacing must never alias across knob settings (the whole
# point of a --stream audit is that it doesn't matter — serving a compact
# cache file, or a differently paced stream file, would make it vacuous).
DEFAULT_STREAM = None  # lazily a repro.service.ServiceConfig (jax import)


def _stream_config():
    global DEFAULT_STREAM
    if DEFAULT_STREAM is None:
        from repro.service import ServiceConfig
        DEFAULT_STREAM = ServiceConfig(lane_slots=8, queue_capacity=16,
                                       step_quota=16)
    if DEFAULT_STREAM.bucket != DEFAULT_BUCKET:
        import dataclasses
        DEFAULT_STREAM = dataclasses.replace(DEFAULT_STREAM,
                                             bucket=DEFAULT_BUCKET)
    return DEFAULT_STREAM


def datasets():
    return {"tensorflow": tensorflow_jobs(0), "scout": scout_jobs(0),
            "cherrypick": cherrypick_jobs(0)}


# Every Outcome field that determinism pins (everything except the
# wall-clock select_seconds).  THE comparator for backend/scheduler/
# streaming parity — re-exported from repro.obs (the forensics layer owns
# the single copy) so the benchmark gates, the scripts/ci.sh smokes and
# the divergence artifacts can never drift apart.
from repro.obs import PINNED_OUTCOME_FIELDS as OUTCOME_FIELDS


def outcomes_equal(a, b) -> bool:
    return all(getattr(a, f) == getattr(b, f) for f in OUTCOME_FIELDS)


def _bucket_key(bucket) -> str:
    """Cache-key component of the active geometry bucket ('' when native):
    a padded sweep must never alias the native files (nor one bucket's
    files another's)."""
    if bucket is None:
        return ""
    return "__pad" + "x".join(str(int(w)) for w in bucket)


def _backend_key(backend: str, bucket) -> str:
    """The backend component of the cache key, carrying every knob of that
    backend that an audit must not alias across."""
    if backend == "sequential":
        # The oracle always runs native: a bucket audit compares padded
        # batched/stream runs against these same sequential files.
        return "sequential"
    if backend == "stream":
        # The streaming/segment knobs ride along: lane seats, device queue
        # capacity, low-water mark, step quota.  Pacing cannot change
        # outcomes (the service determinism contract), but a stream audit
        # at one pacing must never silently read files cached at another —
        # or, worse, the compact-batch files cached by PR 3.
        c = _stream_config()
        return (f"stream-l{c.lane_slots}-c{c.queue_capacity}"
                f"-w{c.resolved_low_water()}-q{c.step_quota}"
                + _bucket_key(bucket))
    return f"{backend}-{DEFAULT_SCHEDULER}{_bucket_key(bucket)}"


def _key(ds, job, policy, la, b, n_runs, refit, backend, timeout, bucket):
    # backend is part of the key: a --sequential audit must never be served
    # results the batched harness cached (they agree on audited configs, but
    # serving one for the other would make the audit vacuous).  For the
    # batched backend the scheduler rides along for the same reason (a
    # --scheduler lockstep audit must re-run, not read compact's cache), and
    # the stream backend carries its segment/service knobs (_backend_key).
    # Ditto the timeout flag: fig_timeout's on/off comparison must never
    # alias.  The version token shields readers from cache files whose
    # contents the current code could not reproduce: v2 fenced off
    # pre-timeout-era files (no spend_trajectory/n_censored); v3 fences
    # off pre-geometry-bucket files — PR 5 changed the bootstrap-weight
    # derivation (trees.bootstrap_weights: padding-invariant per-point
    # fold_in draws), which shifts every simulated outcome; v4 fences off
    # files from before the tree fit's node sums moved to the pinned fold
    # (a backend-ordered matvec had split the lane-batched harness from
    # the sequential oracle on some runs), which moves those runs.
    to = "__to" if timeout else ""
    return (f"{ds}__{job}__{policy}{la}__b{b}__r{n_runs}__{refit}"
            f"__{_backend_key(backend, bucket)}{to}__v4")


def run_policy(ds_name, job, policy, la, *, b=3.0, n_runs=20,
               refit="frozen", seed0=0, quiet=False, backend=None,
               timeout=False):
    """Cached multi-run optimization; identical i-th bootstraps per policy.

    The per-run seeds (7777 + r) and the bootstraps derived from them are
    shared across every policy on a job — the paper's fairness protocol.
    ``backend`` picks the harness: "batched" (default, device-resident
    lanes under ``DEFAULT_SCHEDULER``), "sequential" (the Python-loop
    oracle), or "stream" (submit every run to a ``StreamingTuner`` under
    the ``DEFAULT_STREAM`` pacing and drain — the service audit mode).
    ``timeout`` enables timeout-censored exploration (paper §3,
    mechanism i).
    """
    backend = backend or DEFAULT_BACKEND
    bucket = DEFAULT_BUCKET
    if policy == "rnd":
        # rnd is host-driven (no device program to stream OR pad): it
        # runs — and must be cache-keyed — as the native batched
        # fallthrough, never as a vacuous "stream"/"padded" audit of
        # results no service or bucket ever touched.
        backend = "batched" if backend == "stream" else backend
        bucket = None
    CACHE.mkdir(parents=True, exist_ok=True)
    f = CACHE / (_key(ds_name, job.name, policy, la, b, n_runs, refit,
                      backend, timeout, bucket) + ".json")
    if f.exists():
        return json.loads(f.read_text())
    s = Settings(policy=policy, la=la, k_gh=3, refit=refit, timeout=timeout)
    seeds = [7777 + r for r in range(n_runs)]        # shared across policies
    if backend == "sequential":
        outcomes = run_many(job, s, budget_b=b, seeds=seeds)
    elif backend == "stream":
        from repro.service import StreamingTuner
        svc = StreamingTuner(job, s, _stream_config())
        tickets = [svc.submit(RunRequest(job, seed, b)) for seed in seeds]
        svc.drain()
        outcomes = [t.result() for t in tickets]
    else:
        outcomes = run_many_batched(job, s, budget_b=b, seeds=seeds,
                                    scheduler=DEFAULT_SCHEDULER,
                                    bucket=bucket)
    outs = []
    for r, o in enumerate(outcomes):
        outs.append({"cno": o.cno, "nex": o.nex, "spent": o.spent,
                     "found": o.found_optimum,
                     "select_s": o.select_seconds,
                     "n_censored": len(o.censored),
                     "trajectory": list(o.trajectory),
                     "spend_trajectory": list(o.spend_trajectory)})
        if not quiet:
            print(f"    {ds_name}/{job.name} {policy}{la} b={b} "
                  f"run {r + 1}/{n_runs} cno={o.cno:.3f}", flush=True)
    f.write_text(json.dumps(outs))
    return outs


def cno_stats_d(outs):
    c = np.array([o["cno"] for o in outs])
    return {"mean": float(c.mean()), "p50": float(np.percentile(c, 50)),
            "p90": float(np.percentile(c, 90)),
            "p95": float(np.percentile(c, 95)), "std": float(c.std()),
            "hit": float(np.mean([o["found"] for o in outs]))}


def write_json(name, payload):
    OUT.mkdir(parents=True, exist_ok=True)
    (OUT / f"{name}.json").write_text(json.dumps(payload, indent=1))


def write_bench_json(name, payload):
    """Persist one benchmark's measured numbers as results/BENCH_<name>.json
    (the committed-artifact convention: gates print booleans, the measured
    values land here for the record)."""
    path = pathlib.Path("results") / f"BENCH_{name}.json"
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(payload, indent=1, default=str))
    return path


def csv_line(*fields):
    print(",".join(str(f) for f in fields), flush=True)
