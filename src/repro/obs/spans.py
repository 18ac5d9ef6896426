"""Per-phase timing spans around the segment loop, with compile attribution.

:func:`phase_span` wraps one phase of the stage -> seat -> inject ->
dispatch -> device_block -> harvest cycle (``service/broker.py``,
``service/engine.py``; ``outcome`` inside harvest, once per rebuilt
Outcome) and emits a ``span`` event carrying the phase name and wall
duration.  With ``compiles=True`` the span also records how many
episode/selector programs were compiled inside it — read off the existing
``episode_cache_size()`` / ``selector_cache_size()`` observables — so a
slow dispatch is attributable to *compilation* vs *execution* without a
profiler.

With ``profiler=True`` the phase additionally runs under a
``jax.profiler.TraceAnnotation`` named scope (``ServiceConfig.
trace_profiler``), so the phases show up by name in a captured device
trace.  The annotation is host-side naming only; the device's work inside
a segment is named by the scopes of ``scopes.py``.
"""

from __future__ import annotations

import contextlib
import time

__all__ = ["PHASES", "phase_span"]

# The segment-cycle phase vocabulary, in execution order (the span diagram
# in docs/ARCHITECTURE.md).  "stage" is the broker's per-shard staging
# (backlog purge, admission, eviction picks) before the engine's cycle;
# "dispatch" covers tracing + compilation + the async enqueue of the jitted
# segment; "device_block" is the wait for the device to finish it — their
# split is what separates host overhead from device work.  "outcome" times
# one finished run's Outcome rebuild, inside "harvest".
PHASES = ("stage", "seat", "inject", "dispatch", "device_block", "harvest",
          "outcome")


def _cache_sizes() -> tuple[int, int]:
    # Lazy import: obs must stay importable without pulling the whole core
    # (core imports only obs.scopes, whose module imports nothing of the
    # repo, so there is no cycle either way).
    from repro.core import episode_cache_size, selector_cache_size
    return episode_cache_size(), selector_cache_size()


def _profiler_scope(name: str):
    import jax
    ann = getattr(getattr(jax, "profiler", None), "TraceAnnotation", None)
    return ann(name) if ann is not None else contextlib.nullcontext()


@contextlib.contextmanager
def phase_span(recorder, phase: str, *, segment: int | None = None,
               profiler: bool = False, compiles: bool = False,
               shard: int | None = None):
    """Time one phase into ``recorder`` (no-op when it is absent/disabled).

    Emits ``span`` with ``phase`` and ``dur_s``; with ``compiles=True``
    also ``episode_compiles``/``selector_compiles`` deltas across the
    phase; with ``shard`` set, the emitting engine's shard id (the sharded
    service runs one segment cycle per shard, so spans must say whose
    phase they time).  The span is emitted even when the body raises (a
    crashed dispatch still shows up in the record — that is the point).
    """
    if phase not in PHASES:
        raise ValueError(f"unknown phase {phase!r} (known: {PHASES})")
    enabled = recorder is not None and getattr(recorder, "enabled", False)
    scope = _profiler_scope(f"lynceus/{phase}") if profiler \
        else contextlib.nullcontext()
    if not enabled:
        with scope:
            yield
        return
    e0, s0 = _cache_sizes() if compiles else (0, 0)
    t0 = time.perf_counter()
    try:
        with scope:
            yield
    finally:
        data = {"phase": phase, "dur_s": time.perf_counter() - t0}
        if shard is not None:
            data["shard"] = shard
        if compiles:
            e1, s1 = _cache_sizes()
            data["episode_compiles"] = e1 - e0
            data["selector_compiles"] = s1 - s0
        recorder.emit("span", segment=segment, **data)
