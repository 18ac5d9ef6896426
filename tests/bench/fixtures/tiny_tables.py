"""A tiny table family for the benchmark's CPU tests: two jobs on one
2-dimension, 24-point grid, runtimes and prices drawn from the seed, T_max
at the median runtime."""

import itertools

import numpy as np


def make(seed):
    raw = np.array(list(itertools.product(range(6), range(4))), np.float64)
    jobs = []
    for j in range(2):
        rng = np.random.default_rng(seed * 100 + j)
        runtime = rng.uniform(0.1, 2.0, len(raw))
        jobs.append(dict(name=f"tiny-{j}", dims=["a", "b"], raw=raw,
                         runtime=runtime,
                         unit_price=rng.uniform(0.5, 2.0, len(raw)),
                         t_max=float(np.median(runtime))))
    return jobs
