"""Mutation self-test fixtures: one deliberately-broken program per rule.

The auditor gate is only trustworthy if it still *fires*: each fixture is a
compact padded-selector variant seeded with exactly one contract violation —
the same bug class the rule was written for — and ``check_fixtures``
asserts the audit of each produces **exactly one Finding of exactly the
expected rule** (a false negative or a cross-rule misfire both fail), while
the unbroken twin audits clean.  scripts/lint_repro.py runs this as the
mutation self-check step of the CI gate.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.experimental
import jax.numpy as jnp

from repro.analysis.jaxpr_audit import Finding, audit
from repro.analysis.rules import default_rules

__all__ = ["Fixture", "fixtures", "audit_fixture", "check_fixtures"]

_M = 16           # padded candidate width of the mini selector


@dataclasses.dataclass(frozen=True)
class Fixture:
    name: str
    rule: str                 # the one rule expected to fire
    build: Callable[[], tuple[Callable, tuple, list]]
    x64: bool = False         # trace under enable_x64 (f64 fixtures)


def _mini_selector(broken: str | None):
    """A compact padded selector sharing the real programs' op patterns:
    masked posterior, incumbent fallback, per-index PRNG jitter, masked +
    quantized argmax.  ``broken`` seeds one violation."""
    from repro.core.acquisition import quantize_scores

    def fn(key, y, obs, valid, beta):
        w = obs.astype(jnp.float32)
        n = jnp.maximum(w.sum(), 1.0)
        mean = (y * w).sum() / n
        mu = jnp.where(obs, y, mean)
        sigma = jnp.abs(y - mean) + 0.1
        untested = ~obs & valid
        if broken == "r3":
            # Historical bug class: the untested-sigma fallback term forgot
            # the validity mask — a padding lane's posterior spread moves y*.
            spread = jnp.max(jnp.where(~obs, sigma, -jnp.inf))
        else:
            spread = jnp.max(jnp.where(untested, sigma, -jnp.inf))
        ystar = jnp.max(jnp.where(obs, y, -jnp.inf)) + 3.0 * spread
        ei = jnp.maximum(ystar - mu, 0.0) + sigma
        if broken == "r2":
            # Historical bug class: the per-point key tree derives from the
            # (geometry-dependent) point count via split.
            keys = jax.random.split(key, _M)
        else:
            keys = jax.vmap(jax.random.fold_in, (None, 0))(key,
                                                           jnp.arange(_M))
        jitter = jax.vmap(lambda k: jax.random.uniform(k, ()))(keys)
        score = jnp.where(untested, ei + 1e-6 * jitter, -jnp.inf)
        if broken != "r1":
            # Historical bug class when skipped: raw-score argmax breaks
            # near-ties differently per compilation geometry.
            score = quantize_scores(score)
        sel = jnp.argmax(score)
        out_beta = beta - mu[sel]
        if broken == "r4_callback":
            out_beta = jax.pure_callback(
                lambda b: b, jax.ShapeDtypeStruct((), jnp.float32), out_beta)
        return sel, jnp.any(untested), out_beta

    args = (jax.random.PRNGKey(0), jnp.zeros(_M, jnp.float32),
            jnp.zeros(_M, bool), jnp.zeros(_M, bool), jnp.float32(3.0))
    rules = default_rules(m=_M, mask_argnums=(2, 3))
    return fn, args, rules


def _pallas_argmax(broken: bool):
    """Mini fused-selector step: masked scores + quantized argmax *inside a
    Pallas kernel body* (interpret mode, so the fixture traces anywhere).
    ``broken=True`` seeds the in-kernel variant of the R1 bug class — the
    kernel argmaxes raw float scores, which the walker must still catch
    through the ``pallas_call`` ref-label seeding."""
    from jax.experimental import pallas as pl
    from repro.core.acquisition import quantize_scores

    def kernel(score_ref, valid_ref, sel_ref):
        score = jnp.where(valid_ref[...], score_ref[...], -jnp.inf)
        if not broken:
            score = quantize_scores(score)
        sel_ref[...] = jnp.argmax(score, axis=-1, keepdims=True).astype(
            jnp.int32)

    def fn(score, valid):
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((1,), jnp.int32),
            interpret=True,
        )(score, valid)

    args = (jnp.zeros(_M, jnp.float32), jnp.zeros(_M, bool))
    return fn, args, default_rules(m=_M, mask_argnums=(1,))


def _f64_leak():
    """Historical bug class: Python-float / f64 arithmetic leaking into a
    jitted episode state update.  Minimal on purpose — under ``enable_x64``
    a whole traced selector promotes everywhere, which would drown the one
    seeded violation in dozens of findings."""
    fn = lambda beta: beta.astype(jnp.float64).astype(jnp.float32)
    args = (jnp.float32(3.0),)
    return fn, args, default_rules(m=_M, mask_argnums=())


def fixtures() -> list[Fixture]:
    return [
        Fixture("fixture/r1_unquantized_argmax", "R1",
                lambda: _mini_selector("r1")),
        Fixture("fixture/r2_shape_dependent_split", "R2",
                lambda: _mini_selector("r2")),
        Fixture("fixture/r3_unmasked_sigma_max", "R3",
                lambda: _mini_selector("r3")),
        Fixture("fixture/r4_f64_promotion", "R4",
                _f64_leak, x64=True),
        Fixture("fixture/r4_host_callback", "R4",
                lambda: _mini_selector("r4_callback")),
        Fixture("fixture/r1_unquantized_kernel_argmax", "R1",
                lambda: _pallas_argmax(True)),
    ]


def audit_fixture(fx: Fixture) -> list[Finding]:
    fn, args, rules = fx.build()
    if fx.x64:
        with jax.enable_x64(True):
            return audit(fn, args, rules, program=fx.name)
    return audit(fn, args, rules, program=fx.name)


def check_fixtures() -> list[str]:
    """Run the mutation self-test; returns error strings (empty = healthy).

    Checks, per fixture: exactly one finding, of exactly the expected rule.
    Plus: the unbroken twins (mini selector, mini kernel) audit clean.
    """
    errors: list[str] = []
    for tag, (fn, args, rules) in (("fixture/clean", _mini_selector(None)),
                                   ("fixture/clean_kernel",
                                    _pallas_argmax(False))):
        clean = audit(fn, args, rules, program=tag)
        if clean:
            errors.append(f"{tag}: unbroken twin produced findings: "
                          f"{[str(f) for f in clean]}")
    for fx in fixtures():
        found = audit_fixture(fx)
        rules_hit = sorted({f.rule for f in found})
        if not found:
            errors.append(f"{fx.name}: expected a {fx.rule} finding, "
                          "got none (false negative)")
        elif rules_hit != [fx.rule]:
            errors.append(f"{fx.name}: expected only {fx.rule}, got "
                          f"{rules_hit}: {[str(f) for f in found]}")
        elif len(found) != 1:
            errors.append(f"{fx.name}: expected exactly one finding, got "
                          f"{len(found)}: {[str(f) for f in found]}")
    return errors
