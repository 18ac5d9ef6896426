"""Deployments as data: configuration files, job tables, spaces, bootstraps.

Everything here is the benchmark's own: job tables come from the table
families under ``bench/tables/`` (found by name), spaces are normalised
and given their split thresholds here, and bootstrap samples are drawn
here.  The program receives only the finished tables and requests; the
reference (``bench/reference.py``) reads the arrays made here.
"""

from __future__ import annotations

import importlib.util
import json
import math
import pathlib
from dataclasses import dataclass

import numpy as np


def load_module(path: pathlib.Path):
    """Import a benchmark file by path (names may hold dots)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: pathlib.Path) -> dict:
    return json.loads(pathlib.Path(path).read_text())


@dataclass(frozen=True)
class Space:
    """A discrete space: raw points, per-dimension [0, 1] points and the
    split thresholds (midpoints of consecutive distinct values, +inf padded
    to one width), with the table ``left[m, f*T + t] = x[m, f] <= thr[f, t]``."""

    dims: tuple
    raw: np.ndarray          # [M, F] float64
    points: np.ndarray       # [M, F] float32
    thresholds: np.ndarray   # [F, T] float32
    left: np.ndarray         # [M, F*T] float32 (0/1)

    @classmethod
    def from_raw(cls, dims, raw) -> "Space":
        raw = np.asarray(raw, np.float64)
        lo, hi = raw.min(0), raw.max(0)
        pts = np.where(hi > lo, (raw - lo) / np.where(hi > lo, hi - lo, 1.0),
                       0.5)
        mids = []
        for f in range(raw.shape[1]):
            u = np.unique(pts[:, f])
            mids.append((u[1:] + u[:-1]) / 2.0)
        width = max(1, max(m.size for m in mids))
        thr = np.full((raw.shape[1], width), np.inf)
        for f, m in enumerate(mids):
            thr[f, :m.size] = m
        pts32, thr32 = pts.astype(np.float32), thr.astype(np.float32)
        left = (pts32[:, :, None] <= thr32[None]).reshape(raw.shape[0], -1)
        return cls(tuple(dims), raw, pts32, thr32, left.astype(np.float32))

    @property
    def m(self) -> int:
        return self.raw.shape[0]

    def bootstrap_size(self) -> int:
        """N = max(3% of |space|, number of dimensions) (paper §5.2)."""
        return max(int(math.ceil(0.03 * self.m)), self.raw.shape[1])


@dataclass(frozen=True)
class Job:
    """One job table, float32 columns as the tuner bills them."""

    name: str
    space: Space
    runtime: np.ndarray      # [M] float64 hours
    unit_price: np.ndarray   # [M] float64 $/h
    t_max: float

    @property
    def cost(self) -> np.ndarray:
        return self.runtime * self.unit_price

    def budget(self, b: float) -> float:
        """B = N * mean cost * b (paper §5.2)."""
        return self.space.bootstrap_size() * float(self.cost.mean()) * b


def make_jobs(root: pathlib.Path, cfg: dict) -> list[Job]:
    """The configuration's jobs, from the table families it names."""
    jobs = []
    for fam in cfg["tables"]:
        mod = load_module(root / "bench" / "tables" / f"{fam['family']}.py")
        for d in mod.make(int(fam["seed"])):
            if fam.get("names") and d["name"] not in fam["names"]:
                continue
            jobs.append(Job(d["name"], Space.from_raw(d["dims"], d["raw"]),
                            np.asarray(d["runtime"], np.float64),
                            np.asarray(d["unit_price"], np.float64),
                            float(d["t_max"])))
    return jobs


def latin_hypercube(space: Space, n: int, rng: np.random.Generator
                    ) -> np.ndarray:
    """``n`` distinct indices: a Latin hypercube over the unit cube, each
    sample snapped to the nearest point, collisions replaced by uniform
    draws from the unused points (paper §4.3, footnote 3)."""
    m, f = space.points.shape
    n = min(n, m)
    u = (rng.permuted(np.tile(np.arange(n), (f, 1)), axis=1).T
         + rng.random((n, f))) / n
    idx = ((u[:, None, :] - space.points[None]) ** 2).sum(-1).argmin(1)
    chosen, used = [], np.zeros(m, bool)
    for i in idx:
        if not used[i]:
            chosen.append(int(i))
            used[i] = True
    while len(chosen) < n:
        pick = int(rng.choice(np.nonzero(~used)[0]))
        chosen.append(pick)
        used[pick] = True
    return np.array(chosen, np.int32)


def program_jobs(jobs: list[Job]):
    """The same tables as the program's ``JobTable`` objects."""
    from repro.core.space import DiscreteSpace
    from repro.jobs.tables import JobTable
    return [JobTable(j.name, DiscreteSpace.from_points(j.space.dims,
                                                       j.space.raw),
                     j.runtime, j.unit_price, j.t_max) for j in jobs]
