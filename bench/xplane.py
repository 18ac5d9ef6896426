"""Reduction of a profiler trace (``.xplane.pb``) to the benchmark's device
numbers: busy and idle time of the window, device time per operation and
per kernel, and the longest idle gaps named by what the host was doing.

The window is the host span ``bench/window`` that ``run.py`` opens around
the measured window.  Busy time is the union of the operation intervals
of each chip's op line inside the window, averaged over the chips used.
Operations nest on that line (a ``while`` holds its body's operations), so
the time per operation is its self time: its duration less that of the
operations directly inside it.  An operation is named by its HLO
instruction (``fusion.12``, ``custom-call.3``); a kernel is found by a
pattern over the whole instruction text.  An idle gap is named by the host
annotation (``lynceus/<phase>`` from the service, ``bench/<step>`` from the
harness) that overlaps it most, or ``"none"``.
"""

from __future__ import annotations

import pathlib
import re

WINDOW = "bench/window"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OP_LINES = ("XLA Ops",)
HOST_NAME = re.compile(r"^(lynceus|bench)/")


def find_xplane(logdir: pathlib.Path) -> pathlib.Path:
    files = sorted(pathlib.Path(logdir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return files[-1]


def _union(iv):
    iv = sorted(iv)
    out = []
    for a, b in iv:
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def load(path) -> dict:
    """The raw pieces of a trace: ``device`` — per chip, a list of
    ``(name, start_ns, end_ns)`` op events; ``host`` — the host
    annotations ``(name, start_ns, end_ns)``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    device, host = {}, []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            evs = []
            for line in plane.lines:
                if line.name in OP_LINES:
                    evs += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events]
            device[plane.name] = evs
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                host += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                         for e in line.events if HOST_NAME.match(e.name)]
    return {"device": device, "host": host}


def _short(name: str) -> str:
    return name.split(" = ", 1)[0].lstrip("%")


def _self_times(evs):
    """(name, self seconds) of each event; ``evs`` are (name, a, b) with
    children lying inside their parent's interval."""
    order = sorted(range(len(evs)), key=lambda i: (evs[i][1], -evs[i][2]))
    own = [b - a for _, a, b in evs]
    stack = []
    for i in order:
        _, a, b = evs[i]
        while stack and evs[stack[-1]][2] <= a:
            stack.pop()
        if stack:
            own[stack[-1]] -= b - a
        stack.append(i)
    return [(evs[i][0], own[i] * 1e-9) for i in range(len(evs))]


def reduce(raw: dict, kernels: dict | None = None, top: int = 10) -> dict:
    """Numbers of the window: ``window_s``, ``busy_s`` (mean over chips
    with ops), ``ops`` — device self seconds per full op name, ``kernels``
    — device seconds of the ops whose names match each kernel's pattern,
    ``device_ops`` and ``idle_gaps`` — the ``top`` longest, as
    ``[name, seconds]``."""
    wins = [(a, b) for n, a, b in raw["host"] if n == WINDOW]
    if not wins:
        raise ValueError(f"no {WINDOW!r} span in the trace")
    w0, w1 = wins[-1]
    ops, busy, gaps = {}, [], []
    host = [(n, a, b) for n, a, b in raw["host"]
            if n != WINDOW and b > w0 and a < w1]
    for evs in raw["device"].values():
        evs = [(n, max(a, w0), min(b, w1)) for n, a, b in evs
               if min(b, w1) > max(a, w0)]
        if not evs:
            continue
        for name, sec in _self_times(evs):
            ops[name] = ops.get(name, 0.0) + sec
        u = _union([(a, b) for _, a, b in evs])
        busy.append(sum(b - a for a, b in u) * 1e-9)
        edges = [w0] + [x for ab in u for x in ab] + [w1]
        gaps += [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    kern = {}
    for k, pat in (kernels or {}).items():
        rx = re.compile(pat)
        hit = [v for n, v in ops.items() if rx.search(n)]
        if hit:
            kern[k] = sum(hit)
    named = []
    for a, b in gaps:
        best, over = "none", 0
        for n, ha, hb in host:
            o = min(b, hb) - max(a, ha)
            if o > over:
                best, over = n, o
        named.append([best, (b - a) * 1e-9])
    named.sort(key=lambda x: -x[1])
    short = {}
    for n, v in ops.items():
        short[_short(n)] = short.get(_short(n), 0.0) + v
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": sum(busy) / len(busy) if busy else 0.0,
        "ops": ops,
        "kernels": kern,
        "device_ops": [[n, s] for n, s in sorted(short.items(),
                                                 key=lambda x: -x[1])[:top]],
        "idle_gaps": named[:top],
    }
