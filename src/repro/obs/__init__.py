"""Flight-recorder observability for the streaming tuner.

Lynceus's whole argument is about the cost of the optimization *process* —
every probe, abort and re-seat has a billed price — so the serving stack
must be able to say where a segment's wall time went, which ticket caused
a preemption cascade, and what a drifting run looked like when a parity
gate tripped.  This package is that substrate:

* ``recorder``  — :class:`FlightRecorder`: bounded thread-safe structured
  event log (ring buffer -> JSONL) of every lifecycle transition and
  segment dispatch, emitted by ``service/broker.py`` + ``service/
  engine.py`` behind ``ServiceConfig.trace``
* ``spans``     — :func:`phase_span`: per-phase timing around the segment
  loop (stage/seat/inject/dispatch/device_block/harvest, outcome inside
  harvest) with compile-vs-execute attribution via
  ``episode_cache_size()``/``selector_cache_size()`` and optional
  ``jax.profiler`` annotations (``ServiceConfig.trace_profiler``)
* ``scopes``    — :data:`SCOPES`: the device-scope vocabulary the segment
  program's operations carry (``jax.named_scope``: fits and their parts,
  the kernel, the lookahead), and :func:`segment_program_text`, the
  compiled program's HLO text to join a device trace to them
* ``export``    — Prometheus text renderer, JSONL trace writer/reader, and
  the trace validators (schema + per-ticket lifecycle state machine)
* ``forensics`` — :func:`dump_divergence`: one JSON artifact per parity
  failure (field diffs + flight record + canonical program signatures
  from ``repro.analysis``)

Zero-perturbation rule (docs/ARCHITECTURE.md "Observability"): this layer
watches the determinism contract, it never joins it.  Nothing here touches
a PRNG key or an Outcome, and the scopes only name a traced program's
operations; a trace-on service replays the trace-off service bit for bit
(``tests/test_obs.py``).  Traced on the chip (TPU v5e, the benchmark's
``tf384.lone`` cell) a window took 0.2% more per decision (285.96 against
285.38 ms) than an untraced one on the same seed.
"""

from repro.obs.export import (COUNTER_FIELDS, metrics_to_prometheus,
                              read_trace_jsonl, validate_lifecycle,
                              validate_trace, write_trace_jsonl)
from repro.obs.forensics import (PINNED_OUTCOME_FIELDS, diff_outcomes,
                                 dump_divergence, outcome_to_dict,
                                 registry_signatures)
from repro.obs.recorder import (EVENT_KINDS, TERMINAL_KINDS, Event,
                                FlightRecorder)
from repro.obs.scopes import (SCOPES, scope, segment_program,
                               segment_program_text)
from repro.obs.spans import PHASES, phase_span

__all__ = [
    "COUNTER_FIELDS", "EVENT_KINDS", "Event", "FlightRecorder", "PHASES",
    "PINNED_OUTCOME_FIELDS", "SCOPES", "TERMINAL_KINDS", "diff_outcomes",
    "dump_divergence", "metrics_to_prometheus", "outcome_to_dict",
    "phase_span", "read_trace_jsonl", "registry_signatures", "scope",
    "segment_program", "segment_program_text", "validate_lifecycle",
    "validate_trace", "write_trace_jsonl",
]
