"""The one traffic generator: every mix is a data file of its parameters.

A mix file (``bench/traffic/<name>.json``) holds:

* ``loop``: ``"closed"`` — ``clients`` callers, each submitting its next
  request when its last one settles — or ``"open"`` — requests due on a
  schedule whatever the service does: bursts arrive as a Poisson process
  of ``rate_per_s / burst_mean`` bursts per second, each of a geometric
  size with mean ``burst_mean``, all of a burst due at once;
* ``budgets``: the budget multipliers ``b``, drawn uniformly;
* ``popularity``: ``"uniform"`` over the configuration's jobs, or
  ``{"zipf": s}`` — job ``k`` (in the configuration's order) drawn with
  weight ``(k + 1) ** -s``.

Every seed gets the same set of sizes and arrivals, in another order: the
jobs, budgets, burst times and burst sizes are drawn once from the mix
itself (``base_seed``, 0 by default).  ``--seed`` draws each request's
own seed (which keys its PRNG chain and its bootstrap sample) and, in an
open loop, which request fills which arrival slot.  The arrival times are
the same for every seed: where they changed with the seed, the spread of
a tail latency between seeds was the spread of the bursts' clustering.
A closed loop's clients keep their own order of jobs and budgets: where
the seed rotated them, which eight requests took the lanes first decided
whether four lanes sat empty for most of a window, and the seed chose
between two throughputs.
A request is a function of ``(seed, stream, index)`` alone, so the same
seed gives the same requests however the service paces them.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from data import Job, latin_hypercube

SEED_MAX = 2 ** 31 - 1


@dataclass(frozen=True)
class Request:
    job: int
    seed: int
    b: float
    bootstrap: tuple


def _job_weights(mix: dict, n_jobs: int) -> np.ndarray:
    pop = mix.get("popularity", "uniform")
    if pop == "uniform":
        w = np.ones(n_jobs)
    else:
        w = (np.arange(n_jobs) + 1.0) ** -float(pop["zipf"])
    return w / w.sum()


def _size(mix: dict, n_jobs: int, stream: int, index: int):
    """(job, b) of request ``index`` of ``stream``, from the mix alone."""
    rng = np.random.default_rng([int(mix.get("base_seed", 0)), stream, index])
    j = int(rng.choice(n_jobs, p=_job_weights(mix, n_jobs)))
    return j, float(rng.choice(np.asarray(mix["budgets"], np.float64)))


def _make(jobs: list[Job], j: int, b: float, seed: int, stream: int,
          index: int) -> Request:
    rseed = int(np.random.default_rng([seed, stream, index]).integers(
        0, SEED_MAX))
    space = jobs[j].space
    boot = latin_hypercube(space, space.bootstrap_size(),
                           np.random.default_rng(rseed))
    return Request(j, rseed, b, tuple(int(i) for i in boot))


def request(mix: dict, jobs: list[Job], seed: int, client: int,
            index: int) -> Request:
    """The ``index``-th request of closed-loop ``client`` under run seed
    ``seed``: its job and budget are the mix's own for that client and
    index, its seed the run's."""
    j, b = _size(mix, len(jobs), client, index)
    return _make(jobs, j, b, seed, client + 1, index)


def open_schedule(mix: dict, jobs: list[Job], seed: int,
                  seconds: float) -> list[tuple[float, Request]]:
    """``(due seconds from the window's start, request)``, due times in
    ``[0, seconds)``, sorted."""
    base = np.random.default_rng([int(mix.get("base_seed", 0)), 1 << 20])
    mean = float(mix["burst_mean"])
    burst_rate = float(mix["rate_per_s"]) / mean
    gaps, sizes, t = [], [], 0.0
    while True:
        gap = float(base.exponential(1.0 / burst_rate))
        if t + gap >= seconds:
            break
        t += gap
        gaps.append(gap)
        sizes.append(int(base.geometric(1.0 / mean)))
    n = sum(sizes)
    kinds = [_size(mix, len(jobs), 0, i) for i in range(n)]
    rng = np.random.default_rng([seed, 1 << 20])
    kinds = [kinds[i] for i in rng.permutation(n)]
    out, due, i = [], 0.0, 0
    for gap, size in zip(gaps, sizes):
        due += gap
        for _ in range(size):
            out.append((due, _make(jobs, *kinds[i], seed, 0, i)))
            i += 1
    return out
