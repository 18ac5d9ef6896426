"""Device-scope vocabulary of the segment program, and what it compiled.

:data:`PHASES` (``spans.py``) names the host's work around a segment; the
scopes here name the *device's* work inside it.  Each is a
``jax.named_scope`` applied where the work is traced, so every HLO
instruction of the compiled segment program carries its scope in its
``op_name`` metadata (``.../lynceus/segment/.../speculate/fit/bootstrap/
...``) and a profiler trace of the chip can be read by layer rather than by
instruction name, which changes with every edit of the program.

=====================  ==================================================
scope                  what it holds
=====================  ==================================================
``lynceus``            the whole body of ``optimizer._episode_segment``
``segment``            the segment outside the selector: Alg. 1 step,
                       banking, queue refill, prologue eviction
``speculate``          the selector outside fits and kernel: child
                       states, G-H expectation, key splits, ratio argmax
``fit``                the whole of ``trees.fit_forest``, root and
                       speculative
``fit/bootstrap``      Poisson bootstrap draws (all points, or the
                       observed ones) and the dead-tree guard
``fit/split_stats``    the ``HIGHEST`` node-statistics product per level
``fit/split_choice``   gains, noise floor, quantized argmax, threshold
                       select
``fit/route``          routing, child sums and means (the pinned fold)
``select_step``        the fused Pallas kernel (``name="select_step"``)
                       with its padding and slicing
=====================  ==================================================

A scope below another (``fit/...``) is entered inside its parent; an
operation belongs to the deepest scope on its ``op_name`` path.  Scopes
are op metadata only: they add no jaxpr equation and change no number
(``tests/test_obs.py`` pins both).
"""

from __future__ import annotations

import jax

__all__ = ["SCOPES", "scope", "segment_program", "segment_program_text"]

SCOPES = ("lynceus", "segment", "speculate", "fit", "fit/bootstrap",
          "fit/split_stats", "fit/split_choice", "fit/route", "select_step")


def scope(name: str):
    """The ``jax.named_scope`` of vocabulary scope ``name``: its last path
    part, so ``fit/bootstrap`` must be entered inside ``fit``."""
    if name not in SCOPES:
        raise ValueError(f"unknown scope {name!r} (known: {SCOPES})")
    return jax.named_scope(name.rsplit("/", 1)[-1])


def segment_program(jobs, settings, config):
    """The compiled segment program of the deployment ``(jobs, settings,
    config)`` — the one ``StreamingTuner`` dispatches — lowered at an idle
    engine's arguments.  Nothing runs on the device; with the persistent
    compile cache on, a program the service already compiled is loaded."""
    from repro.core.optimizer import _episode_segment
    from repro.service.engine import SegmentEngine
    engine = SegmentEngine(jobs, settings, config)
    return _episode_segment.lower(*engine.segment_args()).compile()


def segment_program_text(jobs, settings, config) -> str:
    """HLO text of :func:`segment_program`: every instruction with its
    result shape and ``op_name`` metadata, to join a device trace's
    operations to the scopes above."""
    return segment_program(jobs, settings, config).as_text()
