"""The paper's three TensorFlow parameter-server jobs (Lynceus §5.1.1).

The paper's EC2 traces were never released, so the tables are regenerated
from a seed with the same statistics the repo's generator targets: the
5-dimension, 384-point space of Tables 1–2, a 10-minute timeout, cost
spread of about three orders of magnitude, T_max met by about half the
space.  This is the benchmark's own copy of that generator, so the data
the reference reads is made by the benchmark, not by the program.
"""

from __future__ import annotations

import itertools

import numpy as np

TIMEOUT_H = 10.0 / 60.0
VM_TYPES = ((1, 0.023), (2, 0.0464), (4, 0.1856), (8, 0.3712))  # vcpus, $/h
DIMS = {
    "learning_rate": [1e-5, 1e-4, 1e-3],
    "batch_size": [16, 256],
    "sync": [0, 1],
    "vm_type": [0, 1, 2, 3],
    "cluster_vcpus": [8, 16, 32, 48, 64, 80, 96, 112],
}
PHYSICS = {
    "tf-cnn": dict(work=0.5, model_mb=45.0, lr_best=1, diverge=0.8,
                   stale=0.012, straggle=0.05, samples=3.2e5),
    "tf-rnn": dict(work=0.9, model_mb=25.0, lr_best=1, diverge=0.35,
                   stale=0.02, straggle=0.04, samples=2.6e5),
    "tf-multilayer": dict(work=0.3, model_mb=12.0, lr_best=2, diverge=0.1,
                          stale=0.008, straggle=0.06, samples=4.0e5),
}


def make(seed: int) -> list[dict]:
    """One dict per job: name, dims, raw points [M, F], runtime and unit
    price [M] (hours, $/h), and t_max (hours)."""
    raw = np.array(list(itertools.product(*DIMS.values())), np.float64)
    lr_i = np.searchsorted(DIMS["learning_rate"], raw[:, 0])
    bs, sync, vm, vcpus = raw[:, 1], raw[:, 2], raw[:, 3].astype(int), raw[:, 4]
    per_vm = np.array([v[0] for v in VM_TYPES])[vm]
    price = np.array([v[1] for v in VM_TYPES])[vm]
    n_vms = vcpus / per_vm
    jobs = []
    for j, (name, ph) in enumerate(PHYSICS.items()):
        rng = np.random.default_rng(seed * 1000 + j)
        eff_batch = bs * np.where(sync == 1, n_vms, 1.0)
        lr_best = np.minimum(ph["lr_best"] + ((sync == 1) & (eff_batch >= 2048)),
                             2)
        pen = np.where(lr_i < lr_best, 14.0 ** (lr_best - lr_i), 1.0)
        diverge = (lr_i > lr_best) & (rng.random(raw.shape[0]) < ph["diverge"])
        pen = np.where((lr_i > lr_best) & ~diverge, 0.8, pen)
        sync_pen = np.where(sync == 1, (eff_batch / 256.0) ** 0.25, 1.0)
        sync_pen = np.where((sync == 1) & (lr_i < lr_best), sync_pen * 1.6,
                            sync_pen)
        async_pen = np.where(sync == 0, 1.0 + ph["stale"] * n_vms, 1.0)
        samples = (ph["samples"] * pen * np.where(bs == 256, 1.35, 1.0)
                   * sync_pen * async_pen)
        compute_h = samples * ph["work"] / 1000.0 / 3600.0 / vcpus
        steps = samples / (bs * n_vms)
        comm_h = steps * (ph["model_mb"] * n_vms / 2400.0) / 3600.0
        comm_h *= np.where(sync == 1, 1.0 + ph["straggle"] * np.log2(n_vms),
                           0.85)
        runtime = (compute_h + comm_h) * np.where((vm == 0) & (bs == 256),
                                                  1.5, 1.0)
        runtime *= np.exp(rng.normal(0.0, 0.08, raw.shape[0]))
        runtime = np.where(diverge, TIMEOUT_H, np.minimum(runtime, TIMEOUT_H))
        t_max = float(np.quantile(runtime, 0.5))
        t_max = min(t_max, TIMEOUT_H * 0.999)
        jobs.append(dict(name=name, dims=list(DIMS), raw=raw, runtime=runtime,
                         unit_price=(n_vms + 1) * price, t_max=t_max))
    return jobs
