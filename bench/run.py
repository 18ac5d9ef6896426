#!/usr/bin/env python3
"""One run of one benchmark cell of the Lynceus tuning service.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

It needs a TPU: it exits non-zero, printing no result, when JAX's first
device is not one or there are fewer chips than the cell asks for, and
when the program (``src/repro``) is not beside it.  It finds the cell in
``BENCHMARK.json``, its configuration in ``bench/configs/<config>.json``,
its traffic mix in ``bench/traffic/<traffic>.json`` and each metric's
reader in ``bench/metrics/<metric>.py``, builds the deployment, warms up
the segment program and every seat count the traffic can use, then
drives ``StreamingTuner.submit`` → ``TuningTicket.result`` for
``--seconds``.  At the close, outstanding tickets are cancelled (closed
loop) or drained (open loop), and the window ends when the last ticket
settles.  Then every outcome is judged against the plain reference
(``check.py``), and the last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` ``breakdown``, and last ``check``, each number compared
beside its limit.  ``--trace 0`` reports the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics, read from a profiler trace and the
service's flight recorder; a per-layer metric listed for the cell that
reads nothing is an error (exit 3, no result).
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import pathlib
import shutil
import sys
import time

T_START = time.perf_counter()
BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import check  # noqa: E402
import data  # noqa: E402
import traffic  # noqa: E402

POLL_S = 0.002
SETTLE_LIMIT_S = 120.0
WARM_PUMPS = 100
TRACE_EVENTS = 1 << 20
KERNELS = {"select_step": r'custom_call_target="tpu_custom_call"'}


def say(*parts) -> None:
    print("bench:", *parts, file=sys.stderr, flush=True)


class MissingMetric(RuntimeError):
    """A per-layer metric listed for the cell read nothing in its run."""


@dataclasses.dataclass
class Cell:
    name: str
    root: pathlib.Path
    chips: int
    config: dict
    mix: dict
    end_to_end: list
    per_layer: list


def load_cell(root: pathlib.Path, workload: str) -> Cell:
    spec = data.load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    cfg = next(c for c in spec["configs"] if c["name"] == w["config"])
    config = data.load_json(root / cfg["file"])
    mix = data.load_json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    mine = lambda m: workload in m.get("workloads", [workload])
    return Cell(workload, root, int(w["chips"]), config, mix,
                [m for m in spec["end_to_end"] if mine(m)],
                [m for m in spec["per_layer"] if mine(m)])


def peaks_for(root: pathlib.Path, kind: str) -> dict:
    """The peak row of a device kind; a kind not in the table is an
    error, not a default."""
    peaks = data.load_json(root / "bench" / "peaks.json")["devices"]
    if kind not in peaks:
        raise KeyError(f"no peaks for device kind {kind!r} in "
                       "bench/peaks.json")
    return peaks[kind]


def reader(root: pathlib.Path, metric: str):
    return data.load_module(root / "bench" / "metrics" / f"{metric}.py").read


@dataclasses.dataclass
class Record:
    """One request of the window: when it was due and submitted, and how
    it settled."""

    req: traffic.Request
    due: float | None = None       # seconds after the window opened
    ticket: object = None
    submitted: float = 0.0         # perf_counter
    settled: float | None = None   # perf_counter
    status: str = "pending"        # done | cancelled | failed | refused
    outcome: object = None

    @property
    def decisions(self) -> int:
        if self.outcome is None:
            return 0
        n = self.outcome.nex - len(self.req.bootstrap)
        return max(n, 0)


class Service:
    """The deployment under test: the program's tuner over the
    configuration's job tables."""

    def __init__(self, cell: Cell, trace: bool):
        from repro.core import Settings
        from repro.service import ServiceConfig
        self.cell = cell
        self.jobs = data.make_jobs(cell.root, cell.config)
        self.program_jobs = data.program_jobs(self.jobs)
        self.settings = Settings(**cell.config["settings"])
        svc = dict(cell.config["service"])
        if trace:
            svc.update(trace=True, trace_profiler=True,
                       trace_capacity=TRACE_EVENTS)
        self.service_config = ServiceConfig(**svc)

    def tuner(self, **overrides):
        from repro.service import StreamingTuner
        cfg = dataclasses.replace(self.service_config, **overrides)
        return StreamingTuner(self.program_jobs, self.settings, cfg)

    def submit(self, tuner, req: traffic.Request):
        return tuner.submit(job=self.program_jobs[req.job], seed=req.seed,
                            budget_b=req.b,
                            bootstrap=list(req.bootstrap))

    def warm_up(self, seed: int) -> None:
        """Compile what the window will run before it opens: the segment
        program, and the host path for every number of requests one pump
        can stage together (the seat scatter and the bootstrap replay
        compile once per count) — up to the clients of a closed loop, up
        to the device queue plus the lanes for an open one.  One step per
        segment; every warm-up ticket is cancelled."""
        cfg = self.service_config
        most = cfg.queue_capacity + cfg.lane_slots
        if self.cell.mix["loop"] == "closed":
            most = min(most, int(self.cell.mix["clients"]))
        warm = self.tuner(step_quota=1, trace=False, trace_profiler=False)
        k = 0
        for n in range(1, most + 1):
            tickets = []
            for _ in range(n):
                req = traffic.request(self.cell.mix, self.jobs, seed, 1 << 30,
                                      k)
                tickets.append(self.submit(warm, req))
                k += 1
            warm.pump()
            for t in tickets:
                t.cancel()
            for _ in range(WARM_PUMPS):
                if all(t.done() for t in tickets):
                    break
                warm.pump()
            else:
                raise RuntimeError("warm-up tickets did not settle")
        warm.stop()


def _settle(rec: Record) -> None:
    from repro.service import TicketCancelled
    t = rec.ticket
    rec.settled = t.resolved_at if t.resolved_at is not None \
        else time.perf_counter()
    try:
        rec.outcome = t.result(timeout=0)
        rec.status = "done"
    except TicketCancelled as e:
        rec.outcome = e.partial
        rec.status = "cancelled"
    except (RuntimeError, TimeoutError) as e:
        say("ticket failed:", repr(e))
        rec.status = "failed"


def closed_loop(svc: Service, tuner, seed: int, seconds: float):
    """``clients`` callers, each submitting its next request as soon as its
    last settles; at the close every outstanding ticket is cancelled."""
    mix = svc.cell.mix
    records, live, count, late = [], {}, {}, 0.0

    def submit(c, now):
        rec = Record(traffic.request(mix, svc.jobs, seed, c,
                                     count.get(c, 0)))
        count[c] = count.get(c, 0) + 1
        rec.submitted = now
        rec.ticket = svc.submit(tuner, rec.req)
        records.append(rec)
        live[c] = rec

    t0 = time.perf_counter()
    for c in range(int(mix["clients"])):
        submit(c, t0)
    end = t0 + seconds
    while True:
        now = time.perf_counter()
        if now >= end:
            break
        for c, rec in list(live.items()):
            if rec.ticket.done():
                _settle(rec)
                del live[c]
                now = time.perf_counter()
                late = max(late, now - rec.settled)
                if now < end:
                    submit(c, now)
        time.sleep(POLL_S)
    for rec in live.values():
        rec.ticket.cancel()
    _wait(list(live.values()))
    return t0, records, {"generator_late_max_s": late}


def open_loop(svc: Service, tuner, seed: int, seconds: float):
    """Requests due on the mix's schedule, submitted when due whatever the
    service is doing; every request due in the window is drained."""
    sched = traffic.open_schedule(svc.cell.mix, svc.jobs, seed, seconds)
    records, lates = [], []
    t0 = time.perf_counter()
    for due, req in sched:
        wait = t0 + due - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        rec = Record(req, due=due)
        rec.submitted = time.perf_counter()
        lates.append(rec.submitted - (t0 + due))
        rec.ticket = svc.submit(tuner, req)
        records.append(rec)
    _wait(records)
    lates.sort()
    return t0, records, {
        "generator_late_max_s": lates[-1] if lates else 0.0,
        "generator_late_p95_s": (lates[int(0.95 * (len(lates) - 1))]
                                 if lates else 0.0)}


def _wait(records) -> None:
    limit = time.perf_counter() + SETTLE_LIMIT_S
    for rec in records:
        while not rec.ticket.done() and time.perf_counter() < limit:
            time.sleep(POLL_S)
        if rec.ticket.done():
            _settle(rec)
        else:
            rec.status = "failed"
            rec.settled = float("inf")
            say(f"ticket {rec.ticket.id} did not settle within "
                f"{SETTLE_LIMIT_S} s of the close")


class CompileCounter:
    """Counts backend compilations while ``active`` (one per process)."""

    _one = None

    def __init__(self):
        import jax
        self.active, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if self.active and event.endswith("backend_compile_duration"):
            self.count += 1

    @classmethod
    def get(cls) -> "CompileCounter":
        if cls._one is None:
            cls._one = cls()
        cls._one.count = 0
        return cls._one


def serve(cell: Cell, seed: int, seconds: float, trace: bool) -> dict:
    """Set up, warm up, run the window; returns everything measured."""
    import jax
    svc = Service(cell, trace)
    svc.warm_up(seed)
    tuner = svc.tuner()
    counter = CompileCounter.get()
    setup_s = time.perf_counter() - T_START
    logdir = cell.root / "bench_out" / "trace"
    if trace:
        shutil.rmtree(logdir, ignore_errors=True)
        jax.profiler.start_trace(str(logdir))
    tuner.start()
    m0 = tuner.metrics()
    counter.active = True
    loop = closed_loop if cell.mix["loop"] == "closed" else open_loop
    with jax.profiler.TraceAnnotation("bench/window"):
        t0, records, gen = loop(svc, tuner, seed, seconds)
        t1 = max([r.settled for r in records] + [t0])
    counter.active = False
    m1 = tuner.metrics()
    events = [e for e in tuner.flight_record() if t0 <= e.t <= t1]
    tuner.stop()
    if trace:
        jax.profiler.stop_trace()
    stats = jax.devices()[0].memory_stats() or {}
    del tuner
    gc.collect()
    return {"svc": svc, "records": records, "t0": t0, "t1": t1,
            "setup_s": setup_s, "m0": m0, "m1": m1, "events": events,
            "gen": gen, "compiles": counter.count, "logdir": logdir,
            "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}


def judge(cell: Cell, svc: Service, records, seed: int,
          deciders: dict | None = None) -> dict:
    """The check's numbers for the served outcomes; with ``deciders`` (name
    → a reference in a lower precision) also each control's numbers at
    the same states."""
    from reference import Reference
    st = cell.config["settings"]
    ref = Reference(st)
    replays = []
    for rec in records:
        if rec.outcome is not None:
            led = check.Ledger(svc.jobs[rec.req.job], st)
            replays.append(check.Replay(led, rec.req, rec.outcome,
                                        rec.status == "done"))
    steps = check.sample_steps(replays, int(cell.config["check_steps"]), seed)
    for rp in replays:
        rp.resolve(ref)
    rows = {"served": []}
    rows.update({k: [] for k in deciders or {}})
    for ri, j in steps:
        rp = replays[ri]
        y, obs, cens, beta = rp.state(j)
        key = check.key_for_step(rp.req.seed, j)
        args = (key, y, obs, cens, beta, rp.ledger.job.space.left,
                rp.ledger.u, rp.ledger.t_max)
        out = ref.decide(*args)
        rows["served"].append(check.judge_step(out, rp, j,
                                               check.served_step(rp, j)))
        for k, dec in (deciders or {}).items():
            pick = check.control_step(dec.decide(*args), rp, j)
            rows[k].append(check.judge_step(out, rp, j, pick))
    return {k: check.combine(v, replays, served=(k == "served"))
            for k, v in rows.items()}


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool) -> dict:
    """One run; returns the result object (the last line's content)."""
    import jax
    run = serve(cell, seed, seconds, trace)
    records = run["records"]
    window_s = run["t1"] - run["t0"]
    ctx = dict(run, window_s=window_s, lanes=run["svc"].service_config
               .lane_slots, cell=cell, trace=None)
    say(f"window {window_s!r} s, {len(records)} requests, "
        f"{sum(r.decisions for r in records)} decisions, "
        f"compiles in window {run['compiles']}, "
        + ", ".join(f"{k} {v!r}" for k, v in run["gen"].items()))
    dev = jax.devices()
    device = {"platform": dev[0].platform, "kind": dev[0].device_kind,
              "count": len(dev), "memory_peak_bytes": run["memory_peak_bytes"]}
    result = {}
    if trace:
        import xplane as tr
        raw = tr.load(tr.find_xplane(run["logdir"]))
        red = tr.reduce(raw, KERNELS)
        shutil.rmtree(run["logdir"], ignore_errors=True)
        ctx["trace"] = red
        ctx["peaks"] = peaks_for(cell.root, dev[0].device_kind)
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
        wanted = cell.per_layer
    else:
        wanted = cell.end_to_end
    metrics = {}
    for m in wanted:
        if m["name"] == "setup_s":
            value = run["setup_s"]
        else:
            value = reader(cell.root, m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    # A per-layer metric is listed for the cells in which its reader has
    # something to read: one that reads nothing there is a fault of the
    # run (a kernel renamed, a span gone), not a metric to leave out.
    missing = [m["name"] for m in wanted
               if trace and m["name"] not in metrics]
    numbers = judge(cell, run["svc"], records, seed)["served"]
    failed = sum(r.status in ("failed", "refused") for r in records)
    min_steps = int(cell.config.get("check_min_steps", check.MIN_STEPS))
    result = {"correct": check.verdict(numbers, min_steps) and failed == 0,
              "attempted": len(records), "failed": failed,
              "metrics": metrics, "device": device, **result,
              "check": {k: {"value": numbers[k], "limit": lim}
                        for k, lim in check.LIMITS.items()}}
    result["check"]["steps_checked"] = {"value": numbers["steps_checked"],
                                        "limit": min_steps}
    for line in check.lines(numbers, min_steps):
        say(line)
    if missing:
        raise MissingMetric(f"{cell.name}: nothing to read for "
                            f"{', '.join(missing)} in the traced run")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        say(f"the program (src/repro) is not in {ROOT}")
        return 2
    try:
        cell = load_cell(ROOT, args.workload)
    except (KeyError, OSError, StopIteration) as e:
        say(f"cannot load the cell: {e!r}")
        return 2
    # The persistent compile cache lives at one fixed place in the checkout.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        say(f"needs a TPU; JAX's first device is {devices[0].platform!r}")
        return 2
    if len(devices) < cell.chips:
        say(f"{args.workload} needs {cell.chips} chips, JAX sees "
            f"{len(devices)}")
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro import compile_cache
    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    seed = args.seed & ((1 << 64) - 1)
    try:
        result = run_cell(cell, seed, args.seconds, bool(args.trace))
    except MissingMetric as e:
        say(str(e))
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
