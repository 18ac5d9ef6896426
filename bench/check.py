"""What decides ``correct``: the served probes against the plain reference.

Every outcome the window produced — resolved in full, or the partial of a
ticket cancelled at the window's close — is a sequence of probes.  The run
is rebuilt probe by probe from what the outcome reports (teacher forcing:
the service's probes, censor flags and spend), with every bill recomputed
by the reference: a completed probe costs its table cost, a probe cut at
its timeout the cap times the unit price, the cap taken from the
reference's root posterior at that state.  Then, for a sample of the
selection steps drawn from the run's seed (the longest outcome's last step
always in it), the reference scores every candidate at the state before
that step, and the service's probe is judged by what the reference says:

``probe_gap``      how far the probe's lookahead score lies below the
                   reference's best at that state, as a share of the best
                   — the widest over the sample.  A step reads 1 where the
                   probe lies outside the reference's budget filter Γ,
                   repeats a point already observed, or where the run
                   stopped while Γ still held an untested point (each
                   beyond ``Z_ROUNDING`` in z, rounding's reach);
``billing_errors`` probes, over every probe of every outcome, whose step
                   in the reported spend is not a bill they can have (to
                   within ``SPEND_ULPS`` float32 ulps of the budget and
                   ``BILL_REL`` of the bill): a bootstrap probe's bill at
                   the constraint cap, the table cost of a probe that ran
                   out, and for a probe cut at its timeout a bill above 0
                   and below its full cost;
``cap_miss_share`` the share of the probes cut at their timeout whose
                   bill is not the reference's own cap times the unit
                   price (the cap's posterior spread on its 4-bit grid, or
                   a step either side: a single split that falls the other
                   way in the root forest on the chip moves the spread by
                   more than a grid step).  A wrong cap (its slack, its
                   spread, its grid) misses on most cut probes;
``outcome_errors`` outcomes whose table arithmetic (budget, bootstrap,
                   recommendation, CNO, trajectory, spent, stopping)
                   disagrees with the reference's.

The same judging applies to the control — the reference in a lower
precision put in the service's place at the same states — where it is its
own pick and bill that are judged.
"""

from __future__ import annotations

import numpy as np

# Each number's limit, set from the readings in PERF.md ("How correct is
# decided"): above the largest of sound runs, below the smallest reading of
# the control or of a planted fault.
LIMITS = {
    "probe_gap": 0.03,
    "billing_errors": 0,
    "cap_miss_share": 0.02,
    "outcome_errors": 0,
}
MIN_STEPS = 8
Z_ROUNDING = 1e-3
SPEND_ULPS = 2
BILL_REL = 1e-5


def _f32(x):
    return np.float32(x)


def _round_bits(x, bits: int = 12):
    """Round float32 values to ``bits`` mantissa bits (the selector's score
    grid); infinities pass through."""
    x = np.asarray(x, np.float32)
    keep = np.uint32((0xFFFFFFFF << (23 - bits)) & 0xFFFFFFFF)
    r = ((x.view(np.uint32) + np.uint32(1 << (22 - bits))) & keep
         ).view(np.float32)
    return np.where(np.isfinite(x), r, x)


class Ledger:
    """Float32 views of one job's columns, as the tuner bills them."""

    def __init__(self, job, settings: dict):
        self.job = job
        self.cost = (job.runtime * job.unit_price).astype(np.float32)
        self.u = job.unit_price.astype(np.float32)
        self.runtime = job.runtime.astype(np.float32)
        self.t_max = _f32(job.t_max)
        self.mult = _f32(settings["timeout_tmax_mult"])
        self.kappa = _f32(settings["timeout_kappa"])
        self.timeout = bool(settings["timeout"])
        self.feasible = job.runtime <= job.t_max
        self.opt = float(np.where(self.feasible, job.cost, np.inf).min())

    def bill(self, i: int, tau) -> tuple[np.float32, bool]:
        """(bill, cut): the full cost, or ``tau`` times the unit price when
        the probe's runtime passes ``tau``."""
        if self.timeout and self.runtime[i] > tau:
            return _f32(tau * self.u[i]), True
        return self.cost[i], False

    def boot_tau(self):
        """The cap of a probe no model chose: the constraint cap alone."""
        return _f32(self.t_max * self.mult) if self.timeout else _f32(np.inf)

    def tau(self, best_feas, sigma_sel, u_sel, beta, step: int = 0):
        """The predictive timeout (paper §3, mechanism i): the least of
        ``mult · t_max``, the budget over the unit price, and, once a
        feasible incumbent exists, (incumbent + kappa · sigma) over the
        unit price, sigma on a 4-bit grid — moved ``step`` grid steps."""
        if not self.timeout:
            return _f32(np.inf)
        u_sel = max(u_sel, _f32(1e-12))
        cap = min(_f32(self.t_max * self.mult), _f32(max(beta, _f32(0))
                                                     / u_sel))
        sq = np.asarray(_round_bits(sigma_sel, 4), np.float32)
        sq = _f32((sq.view(np.uint32) + np.uint32(step << 19)
                   if step >= 0 else sq.view(np.uint32)
                   - np.uint32(-step << 19)).view(np.float32))
        pred = _f32(_f32(best_feas + _f32(self.kappa * sq)) / u_sel)
        return min(cap, pred) if np.isfinite(best_feas) else cap

    def bills(self, i: int, best_feas, sigma_sel, beta) -> list:
        """The admissible (bill, cut) pairs of probe ``i``: its cap with
        the spread on its 4-bit grid, or one step either side."""
        return [self.bill(i, self.tau(best_feas, sigma_sel, self.u[i], beta,
                                      step)) for step in (0, -1, 1)]

    def recommend(self, explored, cflags) -> int:
        """The cheapest feasible completed probe (Alg. 1 line 12), else the
        cheapest completed, else the cheapest."""
        arr = np.asarray(explored, int)
        c = np.asarray(cflags, bool)
        feas = self.feasible[arr] & ~c
        pool = arr[feas] if feas.any() else (arr[~c] if (~c).any() else arr)
        return int(pool[self.job.cost[pool].argmin()])

    def cno(self, i: int) -> float:
        return float(self.job.cost[i] / self.opt)


class Replay:
    """One served outcome, rebuilt probe by probe with the reference's
    bills (:meth:`resolve` walks it forward)."""

    def __init__(self, ledger: Ledger, req, outcome, done: bool):
        self.ledger, self.req, self.o, self.done = ledger, req, outcome, done
        self.n_boot = ledger.job.space.bootstrap_size()
        self.budget = ledger.job.budget(req.b)
        self.explored = list(outcome.explored)
        cens = set(outcome.censored)
        self.cflags = [i in cens for i in self.explored]
        self.spend = [float(s) for s in outcome.spend_trajectory]
        self.n_sel = max(len(self.explored) - self.n_boot, 0)
        self.bills: list = []
        self.beta = [_f32(self.budget)]   # beta before probe k, then after
        self.bill_errors = 0
        self.cap_misses = 0

    def spend_after(self, beta) -> float:
        """The spend the tuner reports for a budget left of ``beta``: its
        ``budget - beta`` is float32 arithmetic (NumPy's promotion of a
        Python float against a float32)."""
        return float(_f32(_f32(self.budget) - beta))

    def _arrays(self, upto: int):
        m = self.ledger.job.space.m
        y = np.zeros(m, np.float32)
        obs = np.zeros(m, bool)
        cens = np.zeros(m, bool)
        for k in range(upto):
            i = self.explored[k]
            y[i], obs[i], cens[i] = self.bills[k], True, self.cflags[k]
        return y, obs, cens

    def matches(self, k: int, bill, cut: bool) -> bool:
        """Whether probe ``k``'s reported spend step and censor flag are
        the ones ``bill`` gives: the step between two float32 spends, to
        within ``SPEND_ULPS`` ulps of the budget (each spend and the budget
        left are rounded at that scale) and ``BILL_REL`` of the bill (the
        chip's division, inside the cap, is not correctly rounded)."""
        before = self.spend[k - 1] if k else 0.0
        ulp = float(np.spacing(_f32(self.budget)))
        tol = SPEND_ULPS * ulp + BILL_REL * float(bill)
        return (cut == self.cflags[k]
                and abs(self.spend[k] - before - float(bill)) <= tol)

    def resolve(self, ref, upto_step: int | None = None) -> None:
        """Bill every probe before selection step ``upto_step`` (all when
        None), in order: count the probes whose spend step no bill they
        can have gives, and those cut at a cap other than the reference's.
        The state carries the reference's cap bill where it matches, else
        the reported step."""
        led = self.ledger
        stop = len(self.explored) if upto_step is None else min(
            self.n_boot + upto_step, len(self.explored))
        for k in range(len(self.bills), stop):
            i = self.explored[k]
            before = self.beta[k]
            step = _f32(self.spend[k] - (self.spend[k - 1] if k else 0.0))
            if k < self.n_boot or not self.cflags[k]:
                tau = led.boot_tau() if k < self.n_boot else _f32(np.inf)
                bill, cut = led.bill(i, tau)
                self.bill_errors += not self.matches(k, bill, cut)
            else:
                y, obs, cens = self._arrays(k)
                out = ref.root(key_for_step(self.req.seed, k - self.n_boot),
                               y, obs, cens, led.job.space.left, led.u,
                               led.t_max)
                options = led.bills(i, _f32(out["best_feas"]),
                                    _f32(out["sigma"][i]),
                                    _f32(max(before, _f32(0))))
                good = [b for b, c in options if self.matches(k, b, c)]
                self.cap_misses += not good
                self.bill_errors += not 0 < step < led.cost[i]
                bill = good[0] if good else step
            self.bills.append(bill)
            self.beta.append(_f32(before - bill))

    def state(self, j: int):
        """(y, observed, censored, beta) before selection step ``j``."""
        k = min(self.n_boot + j, len(self.explored))
        y, obs, cens = self._arrays(k)
        return y, obs, cens, _f32(max(self.beta[k], _f32(0.0)))

    def table_errors(self) -> int:
        """1 when any table arithmetic of the outcome disagrees (call after
        a full :meth:`resolve`)."""
        o, led, expl = self.o, self.ledger, self.explored
        bad = len(expl) != o.nex or len(self.spend) != len(expl)
        bad |= abs(o.budget - self.budget) > 1e-9 * self.budget
        bad |= tuple(expl[:self.n_boot]) != tuple(self.req.bootstrap)[
            :min(self.n_boot, len(expl))]
        if expl:
            rec = led.recommend(expl, self.cflags)
            bad |= rec != o.recommended or o.cno != led.cno(rec)
            for k in range(len(expl)):
                r = led.recommend(expl[:k + 1], self.cflags[:k + 1])
                bad |= o.trajectory[k] != led.cno(r)
            # A run stops once a selected probe spends its budget: no probe
            # follows one that left beta <= 0.
            bad |= any(b <= 0 for b in self.beta[self.n_boot + 1:-1])
            if self.done:
                bad |= o.spent != self.spend[-1]
        return int(bad)


_KEY_AT = None


def key_for_step(seed: int, j: int):
    """The key of selection step ``j`` of a run seeded ``seed``: the run
    key splits once per step into (next, this step's)."""
    global _KEY_AT
    import jax
    if _KEY_AT is None:
        def at(key, j):
            def body(_, ks):
                nxt = jax.random.split(ks[0])
                return nxt[0], nxt[1]
            return jax.lax.fori_loop(0, j + 1, body, (key, key))[1]
        _KEY_AT = jax.jit(at)
    return np.asarray(_KEY_AT(jax.random.PRNGKey(seed), j))


def stopped_with_budget(rp: Replay) -> bool:
    return rp.done and len(rp.beta) > 1 and rp.beta[-1] > 0


def sample_steps(replays: list[Replay], n: int, seed: int) -> list:
    """(replay index, step) pairs: ``n`` drawn from ``seed``, the longest
    outcome's last step always among them; step ``n_sel`` of a finished
    run with budget left is its stop."""
    pool = []
    for r, rp in enumerate(replays):
        pool += [(r, j) for j in range(rp.n_sel)]
        if stopped_with_budget(rp):
            pool.append((r, rp.n_sel))
    if not pool:
        return []
    longest = max(range(len(replays)), key=lambda r: replays[r].n_sel)
    last = max((p for p in pool if p[0] == longest), default=pool[-1],
               key=lambda p: p[1])
    rest = [p for p in pool if p != last]
    rng = np.random.default_rng([seed, 7])
    pick = [rest[i] for i in rng.permutation(len(rest))[:max(n - 1, 0)]]
    return sorted([last] + pick)


def judge_step(ref_out: dict, rp: Replay, j: int, pick) -> dict:
    """Numbers of one step: ``pick`` is the probe the judged system took
    at this state, None where it stopped."""
    z_conf, gam, z = ref_out["z_conf"], ref_out["gamma"], ref_out["z"]
    _, obs, _, _ = rp.state(j)
    if pick is None:
        inside = np.where(~obs, z, -np.inf).max() > z_conf + Z_ROUNDING
        return {"probe_gap": 1.0 if gam.any() and inside else 0.0}
    if obs[pick] or z[pick] < z_conf - Z_ROUNDING:
        return {"probe_gap": 1.0}
    score = ref_out["score"]
    pool = gam.copy()
    pool[pick] = True
    best = float(np.max(np.where(pool, score, -np.inf)))
    gap = (best - float(score[pick])) / best if best > 0 else 0.0
    return {"probe_gap": max(0.0, gap)}


def served_step(rp: Replay, j: int):
    """The probe the service took at step ``j`` (None: it stopped)."""
    return rp.explored[rp.n_boot + j] if j < rp.n_sel else None


def control_step(ctrl_out: dict, rp: Replay, j: int):
    """The probe the control takes at the same state: its own best pick
    in its own Γ (None: Γ is empty)."""
    gam = ctrl_out["gamma"]
    if not gam.any():
        return None
    q = _round_bits(np.where(gam, ctrl_out["score"], -np.inf))
    return int(np.flatnonzero(q == q.max())[0])


def combine(rows: list[dict], replays: list[Replay],
            served: bool = True) -> dict:
    """The run's numbers: the widest probe gap over the judged steps; for
    the served outcomes also the billing and table-arithmetic counts."""
    gaps = [r["probe_gap"] for r in rows]
    out = {"probe_gap": max([0.0] + gaps), "billing_errors": 0,
           "cap_miss_share": 0.0, "outcome_errors": 0}
    if served:
        out["billing_errors"] = sum(rp.bill_errors for rp in replays)
        out["outcome_errors"] = sum(rp.table_errors() for rp in replays)
        out["cap_misses"] = sum(rp.cap_misses for rp in replays)
        out["cut_probes"] = sum(sum(rp.cflags[rp.n_boot:])
                                for rp in replays)
        if out["cut_probes"]:
            out["cap_miss_share"] = out["cap_misses"] / out["cut_probes"]
    out["steps_checked"] = len(rows)
    out["steps_off"] = sum(g > 0 for g in gaps)
    out["gaps_off"] = sorted(g for g in gaps if g > 0)
    return out


def verdict(numbers: dict, min_steps: int = MIN_STEPS) -> bool:
    ok = numbers["steps_checked"] >= min_steps
    return ok and all(numbers[k] <= lim for k, lim in LIMITS.items())


def lines(numbers: dict, min_steps: int = MIN_STEPS) -> list[str]:
    """``name number limit`` for each number compared."""
    out = [f"{k} {numbers[k]!r} limit {lim!r}" for k, lim in LIMITS.items()]
    out.append(f"steps_checked {numbers['steps_checked']} limit >= "
               f"{min_steps}")
    if "cap_misses" in numbers:
        out.insert(0, f"cut probes billed at another cap than the "
                      f"reference's: {numbers['cap_misses']} of "
                      f"{numbers['cut_probes']}")
    return out
