"""Determinism-contract auditor: static analysis over traced programs + repo lint.

Two layers mechanically enforce the ROADMAP's standing determinism contract
(every batched/padded/streamed program replays the sequential oracle bit for
bit) instead of leaving it to convention:

* ``jaxpr_audit`` + ``rules`` — trace a program with ``jax.make_jaxpr`` and
  walk the ClosedJaxpr (recursing into ``jit``/``while``/``scan``/``cond``/
  ``pallas_call`` sub-jaxprs) with value-level taint propagation, enforcing
  the rules keyed to the repo's real historical failure modes:

  - **R1** every selection ``argmax`` runs on ``quantize_scores``-dominated
    values (the unquantized-argmax wobble bug);
  - **R2** no ``random_split`` wider than the literal key-chaining pair —
    per-index derivations must be ``fold_in`` (the shape-dependent ``split``
    / ``poisson`` bug, PR 5's size-invariant PRNG contract);
  - **R3** in padded programs every reduction over the candidate (M) axis
    is dominated by the validity/observation masks (the unmasked padded
    reduce bug);
  - **R4** no f64 promotion and no host callbacks inside jitted episode
    bodies.

* ``ast_lint`` — custom AST rules over the source tree (compat-bypassing
  jax APIs, raw argmaxes on scores, non-literal split counts, Python-float
  budget accumulation) with the comment-justified allowlist in
  ``allowlist.py``.

``registry`` enumerates every audited entry point (native + padded selector
per policy, both episode bodies, the streaming segment, the pallas kernels
and their refs); ``scripts/lint_repro.py`` runs the whole gate in CI and
``fixtures`` holds the deliberately-broken variants that self-test each
rule.  docs/DETERMINISM.md is the human-readable contract.
"""

from repro.analysis.jaxpr_audit import (Finding, Labels, audit, audit_jaxpr,
                                        program_signature, signature)
from repro.analysis.rules import (ForbiddenPrimitivesRule,
                                  MaskedReduceRule, NoF64NoCallbackRule,
                                  QuantizedArgmaxRule, SizeInvariantPRNGRule,
                                  default_rules)
from repro.analysis.registry import (ProgramSpec, audit_all, audit_program,
                                     registered_programs)

__all__ = [
    "Finding", "Labels", "audit", "audit_jaxpr", "program_signature",
    "signature", "QuantizedArgmaxRule", "SizeInvariantPRNGRule",
    "MaskedReduceRule", "NoF64NoCallbackRule", "ForbiddenPrimitivesRule",
    "default_rules", "ProgramSpec", "registered_programs", "audit_program",
    "audit_all",
]
