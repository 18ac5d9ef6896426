"""Plain reference of one Lynceus selection step (paper §3–4, Algs. 1–2).

Straightforward ``jax.numpy``: no kernel, no lane batching, no pinned
summation order.  It imports nothing of the program.  Given one run's
state (observed costs, which points are observed or censored, remaining
budget, PRNG key) it returns, for every candidate configuration, the
score the budget-aware lookahead ranks by, the budget filter Γ, and the
root posterior — everything the benchmark needs to judge a probe the
service handed out.

The semantics it follows, as the paper and the repo's design documents
state them:

* surrogate: a bagging ensemble of ``n_trees`` regression trees of fixed
  ``depth`` fit on the whole space with per-point weights.  Bootstrap
  weights are Poisson(1) per (tree, point), point ``i``'s drawn under
  ``fold_in(key, i)`` by Knuth's product-of-uniforms count (24 uniforms,
  threshold e^-1); a tree whose weights sum below 1 uses the plain
  observation mask.  Splits maximise the weighted variance reduction over
  (feature, threshold) pairs, thresholds being midpoints of consecutive
  distinct values; a split needs weight >= 1 on each side; gains below
  1e-10 of the node's ``w·mean²`` count as 0; a node with no valid split
  sends every point left.  A child keeps its own weighted mean only when
  its weight exceeds 1, else its parent's.  Prediction is the mean over
  trees, spread their population standard deviation, floored at
  ``1e-6 + sigma_floor_rel · std(observed y)``;
* timeouts (mechanism i): at a censored point the mean is raised to the
  billed lower bound and the spread to ``cens_sigma_rel · y``;
* acquisition: EI against y* (cheapest feasible uncensored observation,
  else the highest observed cost plus 3 times the largest untested
  spread) times P(cost <= t_max · unit price), the normal pdf and cdf
  being the ones the repo's selector specifies (a polynomial exp, and
  Abramowitz & Stegun 26.2.17); Γ holds untested points with
  ``(β - μ)/σ >= Φ⁻¹(conf)``;
* lookahead: Gauss–Hermite nodes ``μ + √2 σ ξ_k`` (weights normalised)
  speculate the cost of each root; each speculative state picks its own
  best EI_c in Γ, to depth ``la``; reward and cost accumulate with the
  discount γ on future reward; the root score is reward / cost over Γ.
  States take keys ``fold_in(k, index)`` with ``k_root, k_path =
  split(key)`` and each level splitting its key into (fit, next).
* every argmax compares scores rounded to 12 mantissa bits and takes the
  lowest index among equals — the tie rule that makes the decision a
  function of the state alone.

``precision`` and ``dtype`` exist for the control (the same reference in
a lower precision), never for the reference itself.
"""

from __future__ import annotations

import functools
import statistics

import jax
import jax.numpy as jnp
import numpy as np

EPS_W = 1e-12
EPS_COST = 1e-9
BOOT_DRAWS = 24


def round_mantissa(x, bits: int = 12):
    """Round float32 values to ``bits`` mantissa bits, half away from zero
    in magnitude; infinities pass through."""
    u = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    keep = jnp.uint32((0xFFFFFFFF << (23 - bits)) & 0xFFFFFFFF)
    r = (u + jnp.uint32(1 << (22 - bits))) & keep
    return jnp.where(jnp.isfinite(x), jax.lax.bitcast_convert_type(
        r, jnp.float32), x.astype(jnp.float32))


def first_max(x, axis=-1):
    """Index of the largest 12-bit-rounded value, lowest index on ties."""
    q = round_mantissa(x)
    top = jnp.max(q, axis=axis, keepdims=True)
    idx = jnp.arange(x.shape[axis]).reshape(
        [-1 if a == (axis % x.ndim) else 1 for a in range(x.ndim)])
    return jnp.min(jnp.where(q == top, idx, x.shape[axis]), axis=axis)


def fence(x):
    """``x`` unchanged, but no longer a bare product a backend could fuse
    into a multiply-add with its consumer."""
    return jnp.where(x == x, x, jnp.zeros_like(x))


def pairwise_sum(x, axis: int = -1):
    """Balanced pairwise sum over ``axis``: zero-pad to a power of two and
    add the halves until one is left — the summation order the repo's
    selector fixes for every weighted-target sum."""
    axis = axis % x.ndim
    size = 1
    while size < x.shape[axis]:
        size *= 2
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, size - x.shape[axis])
    x = jnp.pad(x, pad)
    while x.shape[axis] > 1:
        h = x.shape[axis] // 2
        x = (jax.lax.slice_in_dim(x, 0, h, axis=axis)
             + jax.lax.slice_in_dim(x, h, 2 * h, axis=axis))
    return jnp.squeeze(x, axis)


def ordered_sum(terms):
    """Left-to-right sum of a short list of arrays."""
    acc = terms[0]
    for t in terms[1:]:
        acc = acc + t
    return acc


def poisson_weights(key, n_trees: int, m: int):
    """[n_trees, m] Poisson(1) counts, point i drawn under fold_in(key, i)."""
    def point(i):
        u = jax.random.uniform(jax.random.fold_in(key, i),
                               (BOOT_DRAWS, n_trees))
        return jnp.sum(jnp.cumprod(u, axis=0) > np.float32(np.exp(-1.0)),
                       axis=0)
    return jax.vmap(point)(jnp.arange(m)).T.astype(jnp.float32)


def forest(key, y, obs, left, *, n_trees, depth, precision, dtype):
    """Per-tree predictions [n_trees, M] of the forest fit on (y, obs)."""
    m, ft = left.shape
    obs_f = obs.astype(jnp.float32)
    w = poisson_weights(key, n_trees, m) * obs_f[None]
    w = jnp.where(jnp.sum(w, axis=1, keepdims=True) < 1.0, obs_f[None], w)
    w = w.astype(dtype)
    wy = fence(w * y.astype(dtype)[None])
    left = left.astype(dtype)
    node = jnp.zeros((n_trees, m), jnp.int32)
    sw = jnp.sum(w, axis=1, keepdims=True)
    swy = pairwise_sum(wy, axis=1)[:, None]
    val = swy / jnp.maximum(sw, EPS_W)
    for level in range(depth):
        n = 2 ** level
        at = (node[:, None, :] == jnp.arange(n)[None, :, None]).astype(dtype)
        lw = jnp.einsum("bnm,mk->bnk", at * w[:, None], left,
                        precision=precision)
        lwy = jnp.einsum("bnm,mk->bnk", at * wy[:, None], left,
                         precision=precision)
        rw, rwy = sw[..., None] - lw, swy[..., None] - lwy
        ml = lwy / jnp.maximum(lw, EPS_W)
        mr = rwy / jnp.maximum(rw, EPS_W)
        gain = lw * rw / jnp.maximum(sw[..., None], EPS_W) * (ml - mr) ** 2
        scale = (swy * swy / jnp.maximum(sw, EPS_W))[..., None]
        gain = jnp.where(gain < scale * 1e-10, 0.0, gain)
        gain = jnp.where((lw >= 1.0) & (rw >= 1.0), gain, -jnp.inf)
        gain = gain.astype(jnp.float32)
        best = first_max(gain)                                 # [B, n]
        none = ~jnp.isfinite(jnp.max(gain, axis=-1))           # [B, n]
        # Each point reads its node's split by an exact one-hot select.
        at_node = node[..., None] == jnp.arange(n)             # [B, M, n]
        split = jnp.sum(jnp.where(at_node, best[:, None], 0), axis=-1)
        goes_left = jnp.any((split[..., None] == jnp.arange(ft))
                            & (left[None] > 0.5), axis=-1)
        goes_left |= jnp.any(at_node & none[:, None], axis=-1)
        node = 2 * node + (~goes_left).astype(jnp.int32)
        kid = (node[:, None, :] == jnp.arange(2 * n)[None, :, None]
               ).astype(dtype)
        cw = jnp.sum(kid * w[:, None], axis=-1)
        cwy = pairwise_sum(kid * wy[:, None], axis=-1)
        val = jnp.where(cw > 1.0 - 1e-9, cwy / jnp.maximum(cw, EPS_W),
                        jnp.repeat(val, 2, axis=1))
        sw, swy = cw, cwy
    leaf = node[..., None] == jnp.arange(2 ** depth)
    return jnp.sum(jnp.where(leaf, val[:, None], 0.0), axis=-1)


def posterior(key, y, obs, cens, left, floor, st):
    preds = forest(key, y, obs, left, n_trees=st["n_trees"],
                   depth=st["depth"], precision=st["precision"],
                   dtype=st["dtype"])
    n = preds.shape[0]
    mu = ordered_sum([preds[i] for i in range(n)]) / n
    sigma = jnp.sqrt(ordered_sum([fence((preds[i] - mu) ** 2)
                                  for i in range(n)]) / n)
    mu, sigma = mu.astype(jnp.float32), sigma.astype(jnp.float32)
    sigma = jnp.maximum(sigma, floor)
    if cens is not None:
        mu = jnp.where(cens, jnp.maximum(mu, y), mu)
        sigma = jnp.where(cens, jnp.maximum(sigma, st["cens_sigma_rel"]
                                            * jnp.abs(y)), sigma)
    return mu, sigma


# The normal pdf and cdf as the repo's selector specifies them: exp by a
# Cody-Waite reduction and a degree-6 polynomial, the cdf by Abramowitz &
# Stegun 26.2.17 (absolute error under 7.5e-8 — its tail is what EI ranks),
# every product kept apart from the sum that follows it.
LOG2E = np.float32(1.4426950408889634)
LN2_HI = np.float32(0.693359375)
LN2_LO = np.float32(-2.12194440e-4)
EXP_C = tuple(np.float32(c) for c in
              (1 / 720, 1 / 120, 1 / 24, 1 / 6, 0.5, 1.0, 1.0))
AS_P = np.float32(0.2316419)
AS_B = tuple(np.float32(b) for b in (1.330274429, -1.821255978, 1.781477937,
                                     -0.356563782, 0.319381530))
INV_SQRT_2PI = np.float32(1.0 / np.sqrt(2.0 * np.pi))


def exp_nonpositive(x):
    """exp(x) for x <= 0 (0 below -86)."""
    x = x.astype(jnp.float32)
    n = jnp.round(x * LOG2E)
    r = (x - fence(n * LN2_HI)) - fence(n * LN2_LO)
    acc = jnp.full_like(r, EXP_C[0])
    for c in EXP_C[1:]:
        acc = fence(acc * r) + c
    bits = (jax.lax.bitcast_convert_type(acc, jnp.int32)
            + (n.astype(jnp.int32) << 23))
    return jnp.where(x < -86.0, 0.0,
                     jax.lax.bitcast_convert_type(bits, jnp.float32))


def normal_pdf(z):
    z = z.astype(jnp.float32)
    return INV_SQRT_2PI * exp_nonpositive(np.float32(-0.5) * z * z)


def normal_cdf(z):
    z = z.astype(jnp.float32)
    a = jnp.abs(z)
    t = 1.0 / (fence(AS_P * a) + 1.0)
    poly = jnp.full_like(t, AS_B[0])
    for b in AS_B[1:]:
        poly = fence(poly * t) + b
    tail = fence(normal_pdf(a) * (poly * t))
    return jnp.where(z >= 0, 1.0 - tail, tail)


def acquisition(mu, sigma, y, obs, best_feas, beta, u, t_max, st):
    """(EI_c [M], Γ [M]) for one state."""
    dt = st["dtype"]
    mu_, sig_ = mu.astype(dt), jnp.maximum(sigma.astype(dt), EPS_W)
    untested = ~obs
    fallback = (jnp.max(jnp.where(obs, y, -jnp.inf))
                + fence(3.0 * jnp.max(jnp.where(untested, sigma, -jnp.inf))))
    ystar = jnp.where(jnp.isfinite(best_feas), best_feas, fallback).astype(dt)
    z = (ystar - mu_) / sig_
    cdf = lambda x: normal_cdf(x).astype(dt)
    ei = jnp.maximum(fence((ystar - mu_) * cdf(z))
                     + fence(sig_ * normal_pdf(z).astype(dt)), 0.0)
    p_ok = cdf((fence(t_max * u).astype(dt) - mu_) / sig_)
    gamma = untested & ((jnp.asarray(beta, dt) - mu_) / sig_ >= st["z_conf"])
    return (ei * p_ok).astype(jnp.float32), gamma


def _gh(vals, st):
    """Gauss-Hermite expectation over the last axis, left to right."""
    w = st["w_gh"]
    return ordered_sum([fence(vals[..., i] * w[i]) for i in range(len(w))])


def _states(fn, keys, *arrays, block):
    """fn over the leading (state) axis, ``block`` states at a time."""
    return jax.lax.map(lambda a: fn(*a), (keys,) + arrays, batch_size=block)


def _speculate(key, y, obs, beta, bf, depth_left, ctx, st):
    """Reward and cost [S] of each speculative state's own best pick."""
    left, u, t_max, floor, cens = ctx
    k_fit, k_next = jax.random.split(key)
    s_dim, m = y.shape
    keys = jax.vmap(jax.random.fold_in, (None, 0))(k_fit, jnp.arange(s_dim))

    def one(k, y1, o1, b1, f1):
        mu, sig = posterior(k, y1, o1, cens, left, floor, st)
        eic, gam = acquisition(mu, sig, y1, o1, f1, b1, u, t_max, st)
        sel = first_max(jnp.where(gam, eic, -jnp.inf))
        return eic[sel], mu[sel], sig[sel], sel, jnp.any(gam)

    eic_s, mu_s, sig_s, sel, has = _states(one, keys, y, obs, beta, bf,
                                           block=st["block"])
    r0 = jnp.where(has, eic_s, 0.0)
    c0 = jnp.where(has, mu_s, 0.0)
    if depth_left == 0:
        return r0, c0
    k = st["k_gh"]
    nodes = mu_s[:, None] + fence(np.float32(np.sqrt(2.0)) * sig_s[:, None]
                                  * st["xi"][None])            # [S, K]
    hot = jnp.arange(m)[None] == sel[:, None]                  # [S, M]
    y2 = jnp.where(hot[:, None], nodes[..., None], y[:, None])
    o2 = jnp.broadcast_to((obs | hot)[:, None], (s_dim, k, m))
    b2 = beta[:, None] - nodes
    f2 = jnp.minimum(bf[:, None], jnp.where(
        nodes <= (t_max * u[sel])[:, None], nodes, jnp.inf))
    flat = lambda a: a.reshape((s_dim * k,) + a.shape[2:])
    rc, cc = _speculate(k_next, flat(y2), flat(o2), flat(b2), flat(f2),
                        depth_left - 1, ctx, st)
    reward = r0 + fence(st["gamma"] * _gh(rc.reshape(s_dim, k), st))
    cost = c0 + _gh(cc.reshape(s_dim, k), st)
    return jnp.where(has, reward, 0.0), jnp.where(has, cost, 0.0)


def _root(k_root, y, obs, cens, left, u, t_max, st):
    """Root posterior, its sigma floor and the best feasible observation."""
    obs_f = obs.astype(jnp.float32)
    n = jnp.maximum(jnp.sum(obs_f), 1.0)
    mean = jnp.sum(y * obs_f) / n
    floor = 1e-6 + st["sigma_floor_rel"] * jnp.sqrt(
        jnp.maximum(jnp.sum((y - mean) ** 2 * obs_f) / n, 0.0))
    cens_ = cens if st["timeout"] else None
    mu0, sig0 = posterior(k_root, y, obs, cens_, left, floor, st)
    feas = obs & (y <= t_max * u) & ~cens
    best_feas = jnp.min(jnp.where(feas, y, jnp.inf))
    return mu0, sig0, floor, best_feas


def _root_only(key, y, obs, cens, left, u, t_max, st):
    mu0, sig0, _, best_feas = _root(jax.random.split(key)[0], y, obs, cens,
                                    left, u, t_max, st)
    return {"sigma": sig0, "mu": mu0, "best_feas": best_feas}


def _decide(key, y, obs, cens, beta, left, u, t_max, st):
    m = y.shape[0]
    k_root, k_path = jax.random.split(key)
    mu0, sig0, floor, best_feas = _root(k_root, y, obs, cens, left, u, t_max,
                                        st)
    cens_ = cens if st["timeout"] else None
    eic0, gam0 = acquisition(mu0, sig0, y, obs, best_feas, beta, u, t_max, st)
    z0 = (beta - mu0) / jnp.maximum(sig0, EPS_W)
    reward, cost = eic0, mu0
    if st["la"] > 0:
        k = st["k_gh"]
        nodes = mu0[:, None] + fence(np.float32(np.sqrt(2.0)) * sig0[:, None]
                                     * st["xi"][None])         # [M, K]
        eye = jnp.eye(m, dtype=bool)
        y1 = jnp.where(eye[:, None], nodes[..., None], y[None, None])
        o1 = jnp.broadcast_to((obs[None] | eye)[:, None], (m, k, m))
        b1 = beta - nodes
        f1 = jnp.minimum(best_feas, jnp.where(nodes <= (t_max * u)[:, None],
                                              nodes, jnp.inf))
        flat = lambda a: a.reshape((m * k,) + a.shape[2:])
        rc, cc = _speculate(k_path, flat(y1), flat(o1), flat(b1), flat(f1),
                            st["la"] - 1, (left, u, t_max, floor, cens_), st)
        reward = eic0 + fence(st["gamma"] * _gh(rc.reshape(m, k), st))
        cost = mu0 + _gh(cc.reshape(m, k), st)
    score = reward / jnp.maximum(cost, EPS_COST)
    return {"score": score.astype(jnp.float32), "gamma": gam0, "z": z0,
            "sigma": sig0, "mu": mu0, "best_feas": best_feas}


class Reference:
    """The reference bound to one space's split table and the selector
    settings of a configuration (``settings`` as the configuration file
    states them)."""

    def __init__(self, settings: dict, *, precision="highest",
                 dtype="float32", block: int = 256):
        xi, om = np.polynomial.hermite.hermgauss(int(settings["k_gh"]))
        self.st = {
            "n_trees": int(settings["n_trees"]),
            "depth": int(settings["depth"]),
            "la": int(settings["la"]),
            "k_gh": int(settings["k_gh"]),
            "gamma": float(settings["gamma"]),
            "sigma_floor_rel": float(settings["sigma_floor_rel"]),
            "cens_sigma_rel": float(settings["cens_sigma_rel"]),
            "timeout": bool(settings["timeout"]),
            "z_conf": statistics.NormalDist().inv_cdf(float(settings["conf"])),
            "xi": xi.astype(np.float32),
            "w_gh": (om / np.sqrt(np.pi)).astype(np.float32),
            "precision": {"highest": jax.lax.Precision.HIGHEST,
                          "high": jax.lax.Precision.HIGH,
                          "default": jax.lax.Precision.DEFAULT}[precision],
            "dtype": jnp.dtype(dtype),
            "block": block,
        }
        self._run = jax.jit(functools.partial(_decide, st=self.st))
        self._root = jax.jit(functools.partial(_root_only, st=self.st))

    def decide(self, key, y, obs, cens, beta, left, u, t_max) -> dict:
        out = self._run(jnp.asarray(key, jnp.uint32),
                        jnp.asarray(y, jnp.float32), jnp.asarray(obs, bool),
                        jnp.asarray(cens, bool), jnp.float32(beta),
                        jnp.asarray(left, jnp.float32),
                        jnp.asarray(u, jnp.float32), jnp.float32(t_max))
        out = {k: np.asarray(v) for k, v in out.items()}
        out["z_conf"] = self.st["z_conf"]
        return out

    def root(self, key, y, obs, cens, left, u, t_max) -> dict:
        """Only the root posterior (what a probe's timeout needs)."""
        out = self._root(jnp.asarray(key, jnp.uint32),
                         jnp.asarray(y, jnp.float32), jnp.asarray(obs, bool),
                         jnp.asarray(cens, bool),
                         jnp.asarray(left, jnp.float32),
                         jnp.asarray(u, jnp.float32), jnp.float32(t_max))
        return {k: np.asarray(v) for k, v in out.items()}
