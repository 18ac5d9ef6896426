"""Tests for the jaxpr-level determinism auditor (analysis layer 1).

The heavyweight gates — all 20 registered programs audit clean, every
mutation fixture fires exactly its rule — run in CI via
``scripts/lint_repro.py --all``; here we keep a fast cross-section: the
mutation self-check (the auditor's own regression suite), small targeted
programs per rule, and the canonical-signature contract.
"""

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import (ForbiddenPrimitivesRule, MaskedReduceRule,
                            QuantizedArgmaxRule, SizeInvariantPRNGRule,
                            audit, default_rules, signature)
from repro.analysis.fixtures import check_fixtures
from repro.analysis.registry import audit_program, registered_programs


def test_mutation_self_check_is_healthy():
    """Clean twin audits clean; every broken fixture produces exactly one
    finding of exactly its rule (false negatives and cross-rule misfires
    both surface here)."""
    assert check_fixtures() == []


# --------------------------------------------------------------------------- #
# Targeted per-rule programs (small, trace in milliseconds)
# --------------------------------------------------------------------------- #
def test_r1_flags_raw_float_argmax_but_not_quantized_or_integer():
    from repro.core.acquisition import quantize_scores

    raw = audit(lambda s: jnp.argmax(s), (jnp.ones(8),),
                [QuantizedArgmaxRule()])
    assert [f.rule for f in raw] == ["R1"]

    quant = audit(lambda s: jnp.argmax(quantize_scores(s)), (jnp.ones(8),),
                  [QuantizedArgmaxRule()])
    assert quant == []

    ints = audit(lambda s: jnp.argmax(s), (jnp.ones(8, jnp.int32),),
                 [QuantizedArgmaxRule()])
    assert ints == []


def test_r1_sees_through_where_passthrough():
    """The NaN/validity select around a quantized score keeps the quant
    flag — the real selectors all argmax over a where()."""
    from repro.core.acquisition import quantize_scores

    def fn(s, ok):
        q = quantize_scores(s)
        return jnp.argmax(jnp.where(ok, q, -jnp.inf))

    assert audit(fn, (jnp.ones(8), jnp.ones(8, bool)),
                 [QuantizedArgmaxRule()]) == []


def test_r2_flags_geometry_dependent_split_only():
    def bad(key):
        return jax.random.split(key, 8)

    def good(key):
        ks = jax.vmap(jax.random.fold_in, (None, 0))(key, jnp.arange(8))
        return ks

    key = jax.random.PRNGKey(0)
    assert [f.rule for f in audit(bad, (key,),
                                  [SizeInvariantPRNGRule()])] == ["R2"]
    assert audit(good, (key,), [SizeInvariantPRNGRule()]) == []
    # a plain 2-way split is size-invariant and allowed
    assert audit(lambda k: jax.random.split(k), (key,),
                 [SizeInvariantPRNGRule()]) == []


def test_r3_requires_mask_domination_of_m_reductions():
    m = 8

    def bad(y, obs):
        return jnp.sum(y)                       # unmasked M-reduce

    def good(y, obs):
        return jnp.sum(y * obs.astype(y.dtype))

    args = (jnp.ones(m), jnp.zeros(m, bool))
    rules = [MaskedReduceRule(m=m, mask_argnums=(1,))]
    assert [f.rule for f in audit(bad, args, rules)] == ["R3"]
    assert audit(good, args, rules) == []


def test_r3_understands_antimask_negation():
    """~mask is True at padding (antimask); `where(~obs & valid, ...)` must
    still count as mask-dominated."""
    m = 8

    def fn(y, obs, valid):
        untested = ~obs & valid
        return jnp.max(jnp.where(untested, y, -jnp.inf))

    args = (jnp.ones(m), jnp.zeros(m, bool), jnp.zeros(m, bool))
    assert audit(fn, args, [MaskedReduceRule(m=m, mask_argnums=(1, 2))]) == []


def test_r3_lowest_index_pick_is_a_selection_index():
    """The lowest index among the top scores, min(where(s == top, iota,
    m)), selects like an argmax: a one-hot of it masks an M-reduce.  With
    the fill drawn from data instead of a scalar, it does not."""
    m = 8

    def pick(y, valid, fill):
        s = jnp.where(valid, y, -jnp.inf)
        iota = jax.lax.broadcasted_iota(jnp.int32, (1, m), 1)
        top = jnp.max(s, axis=1, keepdims=True)
        sel = jnp.min(jnp.where(s == top, iota, fill), axis=1)
        return jnp.sum(jnp.where(iota == sel[:, None], y, 0.0))

    args = (jnp.ones((1, m)), jnp.zeros((1, m), bool))
    rules = [MaskedReduceRule(m=m, mask_argnums=(1,))]
    assert audit(lambda y, valid: pick(y, valid, m), args, rules) == []
    found = audit(lambda y, valid: pick(y, valid, y.astype(jnp.int32)),
                  args, rules)
    assert {(f.rule, f.primitive) for f in found} == {
        ("R3", "reduce_min"), ("R3", "reduce_sum")}


def test_r4_flags_f64_and_callbacks():
    from repro.analysis import NoF64NoCallbackRule

    with jax.enable_x64(True):
        f64 = audit(lambda x: x.astype(jnp.float64).astype(jnp.float32),
                    (jnp.float32(1.0),), [NoF64NoCallbackRule()])
    assert [f.rule for f in f64] == ["R4"]

    def cb(x):
        return jax.pure_callback(lambda v: v,
                                 jax.ShapeDtypeStruct((), jnp.float32), x)

    found = audit(cb, (jnp.float32(1.0),), [NoF64NoCallbackRule()])
    assert [f.rule for f in found] == ["R4"]


def test_forbidden_primitives_rule_recurses_into_subjaxprs():
    """A str(jaxpr) pin would miss an erf buried inside a jitted callee."""
    from jax.scipy.stats import norm

    inner = jax.jit(lambda z: norm.cdf(z))
    findings = audit(lambda z: inner(z), (jnp.ones(4),),
                     [ForbiddenPrimitivesRule(("erf",))])
    assert findings and all(f.rule == "FORBID" for f in findings)
    assert any(f.path for f in findings), "sub-jaxpr path not recorded"


# --------------------------------------------------------------------------- #
# Canonical program signatures
# --------------------------------------------------------------------------- #
def test_signature_stable_under_retrace_and_distinct_for_distinct_programs():
    f = lambda x: jnp.sum(x * 2.0)
    g = lambda x: jnp.sum(x * 3.0)
    x = jnp.ones(4)
    assert signature(f, x) == signature(f, x)
    assert signature(f, x) != signature(g, x)
    # shape changes are program changes
    assert signature(f, x) != signature(f, jnp.ones(5))


def test_signature_ignores_cosmetic_names():
    """Wrapping in jit with a different function name must not change the
    canonical signature (the `name` param is cosmetic)."""
    def body(x):
        return x * 2.0

    def renamed_body(x):
        return x * 2.0

    x = jnp.ones(4)
    assert signature(jax.jit(body), x) == signature(jax.jit(renamed_body), x)


# --------------------------------------------------------------------------- #
# Registry cross-section (full 20-program audit runs in the CI gate)
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("name", [
    "selector/lynceus/native",
    "selector/lynceus/padded",
    "episode/segment/bucketed",
])
def test_registered_program_audits_clean(name):
    spec = {s.name: s for s in registered_programs()}[name]
    findings = audit_program(spec)
    assert findings == [], [str(f) for f in findings]


def test_registry_names_unique_and_nonempty():
    names = [s.name for s in registered_programs()]
    assert len(names) == len(set(names))
    assert len(names) >= 20
