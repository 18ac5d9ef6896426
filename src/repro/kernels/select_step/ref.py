"""Pure-jnp oracle of the fused selector step — same contract, no Pallas.

Mirrors the unfused selector's expressions exactly: gather-based forest
traversal (``trees.predict_forest``), the shared acquisition functions,
``take_along_axis`` gathers at the argmax pick.  The Pallas kernel
evaluates the same expressions; its values can differ from this
reference's in the last ulp, while its decisions and quantized
diagnostics replay the unfused *selector* bit for bit
(tests/test_kernels.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import acquisition as acq
from repro.core import trees

__all__ = ["select_step_ref"]

_EPS = 1e-9


def select_step_ref(feat, thr, leaf, y, obs, beta, bf, points, u, t_max,
                    floor, xi=None, cens=None, valid=None, *, conf=0.99,
                    cens_rel=0.5, score_mode="eic", use_budget=True,
                    emit_full=False, want_nodes=False):
    """See ``kernel.select_step_call`` for shapes and the two output modes."""
    if want_nodes and xi is None:
        raise ValueError("want_nodes=True requires xi")
    points = points.astype(jnp.float32)

    def one(f, t, l):
        p = trees.predict_forest(trees.ForestParams(f, t, l), points)
        return trees.forest_mu_sigma(p, floor)

    mu, sigma = jax.vmap(one)(feat, thr.astype(jnp.float32),
                              leaf.astype(jnp.float32))       # [S, M]
    if cens is not None:
        mu, sigma = acq.censored_adjust(mu, sigma, y, cens, cens_rel)
    ystar = acq.incumbent_fallback(bf, y, obs, sigma, valid)
    eic = acq.ei_constrained(mu, sigma, ystar[:, None], u[None, :], t_max)
    untested = ~obs.astype(bool)
    if valid is not None:
        untested = untested & valid.astype(bool)
    cand = untested
    if use_budget:
        cand = cand & acq.budget_ok(mu, sigma, beta[:, None], conf)
    raw = eic if score_mode == "eic" else eic / jnp.maximum(mu, _EPS)
    score = acq.quantize_scores(jnp.where(cand, raw, -jnp.inf))
    sel = jnp.argmax(score, axis=1).astype(jnp.int32)
    has_cand = jnp.any(cand, axis=1)

    if emit_full:
        out = (mu, sigma, eic, ystar, cand, sel, has_cand)
        if want_nodes:
            out += (acq.gh_cost_nodes(mu, sigma, xi.astype(jnp.float32)),)
        return out
    take = lambda a: jnp.take_along_axis(a, sel[:, None], axis=1)[:, 0]
    eic_sel, mu_sel, sig_sel = take(eic), take(mu), take(sigma)
    out = (sel, has_cand, eic_sel, mu_sel, sig_sel)
    if want_nodes:
        out += (acq.gh_cost_nodes(mu_sel, sig_sel,
                                  xi.astype(jnp.float32)),)
    return out
