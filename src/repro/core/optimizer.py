"""The Lynceus optimization loop (paper Alg. 1), its baselines, and the
batched execution backends that make figure-scale sweeps cheap.

``optimize`` drives one full optimization of a :class:`~repro.jobs.tables.
JobTable` (the paper's simulation substrate): LHS bootstrap, then iterate
``select_next → run(config) → update state`` until the budget filter comes
back empty.  The recommendation is the cheapest *feasible* config tried
(Alg. 1 line 12).

Policies
--------
* ``lynceus`` — the paper's budget-aware, long-sighted selector (LA ≥ 1);
* ``la0``     — cost-normalized greedy `argmax EI_c/E[cost]` (paper's LA = 0);
* ``bo``      — CherryPick-style greedy `argmax EI_c`, cost-unaware but
  budget-terminated (runs until the *spent* budget would be exceeded);
* ``rnd``     — uniform random exploration under the same budget.

All policies consume the budget identically (bootstrap included), so CNO/NEX
comparisons are at parity of spend — exactly the paper's methodology (§5.2).

Execution backends
------------------
Three backends run identical Alg. 1 semantics and are pinned bit-identical
on audited configs (tests/test_batched_harness.py, scripts/ci.sh):

* :func:`run_many` — the sequential oracle, one Python-driven run at a time;
* :func:`run_many_batched` with ``scheduler="lockstep"`` — fixed lane
  assignment, one jitted ``lax.while_loop`` per chunk
  (:func:`_batched_episode`); a chunk ends when its *last* lane's budget
  empties;
* ``scheduler="compact"`` (default) / :func:`run_queue_batched` — the
  lane-compacting work queue (:func:`_episode_segment`): lanes are
  *slots* that bank a finished run's state into run-indexed output buffers
  and immediately load the next pending run from a device-side queue head,
  so short runs never idle behind long ones.  Queues built from
  :class:`RunRequest` entries may mix budgets and jobs freely — jobs whose
  spaces differ in geometry are padded into one
  :class:`~repro.core.space.GeometryBucket` (one compiled episode per
  bucket instead of per geometry).

The compacting episode runs as bounded *segments* (low-water-mark and
step-quota exits next to the natural queue-drained exit) so a host-side
broker can inject new :class:`RunRequest`\\ s and harvest finished
:class:`Outcome`\\ s while the episode state stays device-resident — that
streaming front-end lives in ``src/repro/service/``; the one-shot entry
points here simply run a single unbounded segment.

See docs/ARCHITECTURE.md for the data-flow picture and the determinism
contract, and docs/KNOBS.md for every tuning knob.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import TYPE_CHECKING, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import lookahead, trees
from repro.core.space import GeometryBucket, latin_hypercube_indices
from repro.obs.scopes import scope

if TYPE_CHECKING:  # avoid the core <-> jobs import cycle at runtime
    from repro.jobs.tables import JobTable

__all__ = ["Outcome", "RunRequest", "episode_cache_size", "optimize",
           "run_many", "run_many_batched", "run_queue", "run_queue_batched"]


@dataclasses.dataclass(frozen=True)
class Outcome:
    """Result of one optimization run."""

    job: str
    policy: str
    recommended: int            # config index recommended at the end
    cno: float                  # cost(recommended) / cost(optimum)
    nex: int                    # number of explorations (bootstrap included)
    spent: float                # total profiling spend ($)
    budget: float               # the budget B it ran under
    found_optimum: bool
    explored: tuple[int, ...]   # exploration order (config indices)
    select_seconds: float       # mean wall-time of next-config selection
    trajectory: tuple[float, ...]  # best feasible CNO after each exploration
    censored: tuple[int, ...] = ()  # explored configs aborted at the timeout
    spend_trajectory: tuple[float, ...] = ()  # cumulative billed spend ($)


def _recommend(job: JobTable, explored: list[int], cens=None) -> int:
    """Cheapest feasible *completed* explored config (Alg. 1 line 12).

    A censored run never finished, so its runtime — and hence feasibility —
    was never observed: it is not recommendable.  (This never worsens the
    recommendation: a predictively censored run's true cost provably exceeds
    the then-incumbent, which is itself explored and uncensored, and a
    constraint-cap censored run is infeasible in truth.)  Fallbacks when
    nothing qualifies keep the historical order: cheapest completed, then —
    degenerate, every run censored — cheapest explored by table cost.
    """
    arr = np.array(explored, dtype=int)
    cost = job.cost[arr]
    c = (np.asarray(cens, dtype=bool) if cens is not None
         else np.zeros(arr.size, dtype=bool))
    feas = job.feasible[arr] & ~c
    if feas.any():
        return int(arr[feas][cost[feas].argmin()])
    if (~c).any():
        return int(arr[~c][cost[~c].argmin()])
    return int(arr[cost.argmin()])


def _trajectory_point(job: JobTable, explored: list[int], cens=None) -> float:
    return job.cno(_recommend(job, explored, cens))


def _boot_tau(job: JobTable, settings: lookahead.Settings) -> np.float32:
    """Timeout for model-less runs (bootstrap, RND): the constraint cap only.

    Exactly ``f32(t_max)·f32(mult)`` — the same arithmetic
    ``acq.timeout_cap`` performs for its constraint branch on device, so a
    run capped here and a run capped by the selector bill identically.
    """
    if not settings.timeout:
        return np.float32(np.inf)
    return np.float32(np.float32(job.t_max)
                      * np.float32(settings.timeout_tmax_mult))


def optimize(job: JobTable, settings: lookahead.Settings, *, budget_b: float = 3.0,
             seed: int = 0, bootstrap: np.ndarray | None = None,
             selector: Callable | None = None) -> Outcome:
    """Run one optimization of ``job`` under policy ``settings.policy``.

    Args:
      job: fully profiled job table (the simulator looks costs up).
      settings: selector knobs; ``settings.policy`` picks the algorithm.
      budget_b: the paper's ``b`` multiplier — B = N·m̃·b.
      seed: drives LHS bootstrap, bootstrap resampling and RND.
      bootstrap: optional explicit bootstrap indices (paper: all optimizers
        share the same i-th bootstrap for fairness — pass the same array).
      selector: pre-built ``make_selector`` closure to reuse compiled code
        across runs on the same space.

    With ``settings.timeout`` each exploration runs under a cap τ — the
    constraint cap for model-less runs (bootstrap, RND), the selector's
    predictive cap otherwise.  A run whose table runtime exceeds τ is
    aborted: billed ``τ·U`` instead of its full cost, recorded as a
    *censored* observation (its billed cost is a lower bound the model
    keeps learning from — paper §3, mechanism i).
    """
    rng = np.random.default_rng(seed)
    n_boot = job.bootstrap_size()
    budget = job.budget(budget_b)
    # Budget accounting runs in float32 — the same IEEE arithmetic the
    # device-resident batched harness performs — so the two paths stay
    # bit-identical (the selector only ever sees float32 anyway).
    host = job.host_view()
    cost = host.cost

    if bootstrap is None:
        bootstrap = latin_hypercube_indices(job.space, n_boot, rng)

    m = job.space.n_points
    y = np.zeros(m, dtype=np.float32)
    mask = np.zeros(m, dtype=bool)
    cens = np.zeros(m, dtype=bool)
    cens_order: list[bool] = []
    explored: list[int] = []
    beta = np.float32(budget)
    trajectory: list[float] = []
    spend_traj: list[float] = []
    tau_boot = _boot_tau(job, settings)

    def run_config(i: int, tau=np.float32(np.inf)) -> None:
        nonlocal beta
        t = host.runtime[i]
        cut = bool(t > tau)
        billed = np.float32(tau * host.unit_price[i]) if cut else cost[i]
        y[i] = billed
        mask[i] = True
        cens[i] = cut
        explored.append(int(i))
        cens_order.append(cut)
        beta -= billed
        trajectory.append(_trajectory_point(job, explored, cens_order))
        spend_traj.append(float(budget - beta))

    for i in bootstrap:                       # Alg. 1 lines 6-8
        run_config(int(i), tau_boot)

    select_times: list[float] = []
    if settings.policy == "rnd":
        # Random exploration at parity of budget: keep drawing affordable,
        # untested configs (true-cost check — RND has no model, so timeouts
        # only apply the constraint cap to it).
        while True:
            free = np.where(~mask & (cost <= beta))[0]
            if free.size == 0:
                break
            run_config(int(rng.choice(free)), tau_boot)
    else:
        sel = selector or lookahead.make_selector(
            job.space, job.unit_price, job.t_max, settings)
        key = jax.random.PRNGKey(seed)
        while True:
            key, sub = jax.random.split(key)
            t0 = time.perf_counter()
            if settings.timeout:
                idx, valid, diag = sel(sub, y, mask, max(beta, 0.0), cens)
                tau = np.float32(diag["timeout"])
            else:
                idx, valid, _ = sel(sub, y, mask, max(beta, 0.0))
                tau = np.float32(np.inf)
            idx = int(idx)
            valid = bool(valid)
            select_times.append(time.perf_counter() - t0)
            if not valid:                     # Gamma empty -> stop (line 11)
                break
            if settings.policy == "bo" and cost[idx] > beta:
                # Cost-unaware greedy BO stops when its pick is unaffordable
                # (CherryPick terminates on budget depletion in our harness).
                break
            run_config(idx, tau)
            if beta <= 0:
                break

    rec = _recommend(job, explored, cens_order)
    return Outcome(
        job=job.name, policy=settings.policy, recommended=rec,
        cno=job.cno(rec), nex=len(explored), spent=float(budget - beta),
        budget=float(budget), found_optimum=(rec == job.optimum_index),
        explored=tuple(explored),
        select_seconds=float(np.mean(select_times)) if select_times else 0.0,
        trajectory=tuple(trajectory),
        censored=tuple(i for i, c in zip(explored, cens_order) if c),
        spend_trajectory=tuple(spend_traj))


def optimize_live(evaluator, space, unit_price, t_max: float,
                  settings: lookahead.Settings, *, budget: float,
                  n_bootstrap: int | None = None, seed: int = 0,
                  log=None) -> dict:
    """Sequential optimization against a LIVE evaluator (no precomputed table).

    This is the framework-integration path (launch/autotune.py): each "run"
    of a configuration actually profiles it (a dry-run compile + roofline
    estimate, or a timed real step) and charges its cost against the budget.

    With ``settings.timeout`` every probe runs under a cap τ — the
    constraint cap ``timeout_tmax_mult·t_max`` for bootstrap probes, the
    selector's predictive cap afterwards.  A probe whose runtime exceeds τ
    is billed pro rata (``c·τ/t`` — the cost accrued up to the abort) and
    recorded as a censored lower bound; censored probes are never
    recommendable (their runtime was not observed to meet the SLO).

    Args:
      evaluator: f(index) -> (runtime_seconds, cost_dollars) for config i.
      unit_price: [M] $/h while a config runs (for the EI_c constraint).
      t_max: runtime SLO in the same units as evaluator's runtime.
      budget: total profiling budget in cost units.
    Returns dict with explored, costs, runtimes, recommended, trajectory.
    """
    import numpy as np
    rng = np.random.default_rng(seed)
    m = space.n_points
    n_boot = n_bootstrap or max(int(np.ceil(0.03 * m)), space.n_dims)
    y = np.zeros(m, np.float32)
    runtimes = np.zeros(m, np.float32)
    mask = np.zeros(m, bool)
    cens = np.zeros(m, bool)
    explored: list[int] = []
    # f32 bookkeeping, same as optimize(): the remaining budget feeds the
    # jitted selector, so host-side accumulation must replay f32 exactly.
    beta = np.float32(budget)
    tau_boot = (float(np.float32(t_max) * np.float32(settings.timeout_tmax_mult))
                if settings.timeout else float("inf"))

    def run_config(i: int, tau: float = float("inf")):
        nonlocal beta
        t, c = evaluator(int(i))
        cut = settings.timeout and t > tau
        if cut:
            c = float(c) * tau / max(float(t), 1e-12)
        y[i] = c
        runtimes[i] = t
        mask[i] = True
        cens[i] = bool(cut)
        explored.append(int(i))
        beta = np.float32(beta - np.float32(c))
        if log:
            log(f"[tune] cfg {i}: runtime {t:.4f}s cost {c:.4f} "
                f"beta {beta:.3f}" + (f" CENSORED at tau {tau:.3f}s" if cut
                                      else ""))

    for i in latin_hypercube_indices(space, n_boot, rng):
        run_config(i, tau_boot)

    sel = lookahead.make_selector(space, unit_price, t_max, settings)
    key = jax.random.PRNGKey(seed)
    while beta > 0:
        key, sub = jax.random.split(key)
        if settings.timeout:
            idx, valid, diag = sel(sub, y, mask, max(beta, 0.0), cens)
            tau = float(diag["timeout"])
        else:
            idx, valid, _ = sel(sub, y, mask, max(beta, 0.0))
            tau = float("inf")
        if not bool(valid):
            break
        run_config(int(idx), tau)

    arr = np.array(explored)
    feas = (runtimes[arr] <= t_max) & ~cens[arr]
    if feas.any():
        sub_arr = arr[feas]
    elif (~cens[arr]).any():
        sub_arr = arr[~cens[arr]]
    else:
        sub_arr = arr
    rec = int(sub_arr[y[sub_arr].argmin()])
    return {"recommended": rec, "explored": explored,
            "costs": y[arr].tolist(), "runtimes": runtimes[arr].tolist(),
            "censored": [int(i) for i in arr[cens[arr]]],
            "spent": float(budget - beta), "budget": budget,
            "best_runtime": float(runtimes[rec]), "best_cost": float(y[rec])}


def _per_run_seeds(seed: int, n_runs: int) -> list[int]:
    return [seed * 100003 + r for r in range(n_runs)]


def _per_run_bootstraps(job: JobTable, seeds) -> list[np.ndarray]:
    """The i-th bootstrap is a pure function of the i-th seed, so every
    policy handed the same seeds sees the same bootstraps (paper fairness)."""
    return [latin_hypercube_indices(job.space, job.bootstrap_size(),
                                    np.random.default_rng(s)) for s in seeds]


def run_many(job: JobTable, settings: lookahead.Settings, *, n_runs: int = 100,
             budget_b: float = 3.0, seed: int = 0, seeds=None,
             bootstraps=None) -> list[Outcome]:
    """Paper methodology: ≥100 runs, each with a different bootstrap; all
    policies see the same i-th bootstrap (pass the same seed across policies).

    This is the sequential oracle — one Python-driven run at a time.  The
    production path is :func:`run_many_batched`, which produces bit-identical
    outcomes; keep this one as the reference the batched harness is audited
    against.  ``seeds``/``bootstraps`` override the derived per-run values
    (both length n_runs; ``seeds`` alone re-derives the bootstraps from it).
    ``budget_b`` may be a scalar or a per-run sequence — tail-heavy sweeps
    mix long- and short-budget runs in one call.
    """
    seeds, bootstraps = _resolve_runs(job, seed, n_runs, seeds, bootstraps)
    budgets_b = _resolve_budget_b(budget_b, len(seeds))
    selector = None
    if settings.policy != "rnd":
        selector = lookahead.make_selector(
            job.space, job.unit_price, job.t_max, settings)
    return [optimize(job, settings, budget_b=b, seed=s, bootstrap=boot,
                     selector=selector)
            for s, boot, b in zip(seeds, bootstraps, budgets_b)]


def _resolve_budget_b(budget_b, n_runs: int) -> list[float]:
    """Scalar -> broadcast; sequence -> validated per-run b multipliers."""
    if np.ndim(budget_b) == 0:
        return [float(budget_b)] * n_runs
    budgets = [float(b) for b in budget_b]
    if len(budgets) != n_runs:
        raise ValueError(f"{n_runs} runs but {len(budgets)} budget_b values; "
                         "pass a scalar or a matching sequence")
    return budgets


def _resolve_runs(job: JobTable, seed: int, n_runs: int, seeds, bootstraps):
    """Materialize per-run seeds/bootstraps; reject mismatched overrides
    (a silent zip-truncation would under-sample a figure sweep)."""
    seeds = list(seeds) if seeds is not None else _per_run_seeds(seed, n_runs)
    if bootstraps is None:
        bootstraps = _per_run_bootstraps(job, seeds)
    if len(bootstraps) != len(seeds):
        raise ValueError(f"{len(seeds)} seeds but {len(bootstraps)} "
                         "bootstraps; pass matching lists")
    return seeds, list(bootstraps)


# --------------------------------------------------------------------------- #
# Batched, device-resident harness
# --------------------------------------------------------------------------- #
def _alg1_step(st, idx, c, t_run, u_at, valid, tau, s: lookahead.Settings,
               lanes, m_dim):
    """One masked Alg. 1 step on lane-stacked state — the piece both
    episode bodies (:func:`_batched_episode`, :func:`_episode_segment`)
    share, factored out so the billing/censoring semantics cannot drift
    between the lockstep baseline and the compacting scheduler.

    ``st`` carries y/mask/beta/explored/n_exp/active (+ cens/cexpl/bexpl
    when ``s.timeout``); ``idx``/``valid`` come from the caller's selection,
    ``c``/``t_run``/``u_at`` are the per-lane table rows of the selected
    configs (t_run/u_at/tau only consulted when ``s.timeout``).  Returns
    the updated fields plus ``alive`` (Alg. 1 line 11: still active after
    this step).
    """
    run = st["active"] & valid                          # Gamma empty -> stop
    if s.policy == "bo":
        # Cost-unaware greedy stops when its pick is unaffordable.
        run = run & (c <= st["beta"])
    if s.timeout:
        # Abort at the predictive cap: bill τ·U, learn the lower bound.
        cut = run & (t_run > tau)
        billed = jnp.where(cut, tau * u_at, c)
    else:
        billed = c
    hit = run[:, None] & (jnp.arange(m_dim)[None, :] == idx[:, None])
    pos = jnp.minimum(st["n_exp"], m_dim - 1)
    nxt = {"y": jnp.where(hit, billed[:, None], st["y"]),
           "mask": st["mask"] | hit,
           "beta": jnp.where(run, st["beta"] - billed, st["beta"]),
           "explored": st["explored"].at[lanes, pos].set(
               jnp.where(run, idx, st["explored"][lanes, pos])),
           "n_exp": st["n_exp"] + run.astype(jnp.int32)}
    if s.timeout:
        nxt["cens"] = st["cens"] | (hit & cut[:, None])
        nxt["cexpl"] = st["cexpl"].at[lanes, pos].set(
            jnp.where(run, cut, st["cexpl"][lanes, pos]))
        nxt["bexpl"] = st["bexpl"].at[lanes, pos].set(
            jnp.where(run, billed, st["bexpl"][lanes, pos]))
    alive = run & (nxt["beta"] > 0.0)                   # Alg. 1 line 11
    return nxt, alive


@functools.partial(jax.jit, static_argnames=("s",))
def _batched_episode(keys, y, mask, beta, explored, n_exp, cens, cexpl,
                     bexpl, cost, runtime, points, left, thresholds, u, t_max,
                     s: lookahead.Settings):
    """Advance R simulated optimizations to completion in lockstep.

    One ``lax.while_loop`` over exploration steps; every iteration selects
    for all R lanes at once and applies Alg. 1's budget accounting and
    stopping rule as masked lane updates — no host round trip anywhere.

    keys: [R, 2]; y/mask: [R, M]; beta: [R]; explored: [R, M] int32 (-1
    padded, bootstrap prefix already written); n_exp: [R] int32.
    With ``s.timeout``: cens [R, M] bool (censor mask, bootstrap prefix
    replayed), cexpl [R, M] bool (censored-at-exploration-position, aligned
    with ``explored``), bexpl [R, M] f32 (billed-spend-at-position — the
    post-hoc spend-trajectory reconstruction cannot look billed bounds up
    in a table the way it can full costs), and ``runtime`` [M] f32
    (``device_view().runtime``, gathered per lane to evaluate the censoring
    compare on device); all four are None — and absent from the loop state,
    leaving the compiled program unchanged — when timeouts are off.
    Returns (beta, explored, n_exp, steps[, cexpl, bexpl]).
    """
    r_dim, m_dim = y.shape
    lanes = jnp.arange(r_dim)

    def cond(st):
        return st["active"].any()

    def body(st):
        split = jax.vmap(jax.random.split)(st["key"])       # [R, 2, 2]
        key, sub = split[:, 0], split[:, 1]
        idx, valid, diag = lookahead.select_next_batched(
            sub, st["y"], st["mask"], jnp.maximum(st["beta"], 0.0),
            points, left, thresholds, u, t_max, s,
            st["cens"] if s.timeout else None)
        c = cost[idx]                                       # [R] f32
        nxt, alive = _alg1_step(
            st, idx, c, runtime[idx] if s.timeout else None,
            u[idx] if s.timeout else None, valid,
            diag["timeout"] if s.timeout else None, s, lanes, m_dim)
        nxt.update(key=key, active=alive, steps=st["steps"] + 1)
        return nxt

    st0 = {"key": keys, "y": y, "mask": mask, "beta": beta,
           "explored": explored, "n_exp": n_exp,
           "active": jnp.ones((r_dim,), bool), "steps": jnp.int32(0)}
    if s.timeout:
        st0["cens"] = cens
        st0["cexpl"] = cexpl
        st0["bexpl"] = bexpl
    st = jax.lax.while_loop(cond, body, st0)
    base = (st["beta"], st["explored"], st["n_exp"], st["steps"])
    return base + (st["cexpl"], st["bexpl"]) if s.timeout else base


def _auto_lane_chunk(job: JobTable, s: lookahead.Settings, n_runs: int,
                     m: int | None = None) -> int:
    """Slot-count sizing: bound the deepest speculative tensor
    (n_trees × M × M·k^la per slot).  Used both as the lockstep chunk width
    and as the compacting scheduler's seat count.  ``m`` overrides the
    job's native point count (a geometry-bucketed queue pays the *bucket*
    width per slot, not the native one)."""
    m = job.space.n_points if m is None else m
    states = m * (s.k_gh ** max(s.la, 0) if s.policy == "lynceus" else 1)
    budget_elems = 1.5e8
    return int(max(1, min(n_runs, budget_elems // (s.n_trees * m * states))))


@dataclasses.dataclass(frozen=True)
class RunRequest:
    """One pending simulated optimization in a work queue.

    ``bootstrap`` is derived from ``seed`` when None — the same
    ``latin_hypercube_indices(space, N, default_rng(seed))`` derivation
    :func:`optimize` performs, so a queue and the sequential oracle replay
    identical bootstraps for the same seed (paper fairness protocol).
    Queued jobs and budgets may differ freely per request; jobs whose
    spaces differ in geometry are padded into one
    :class:`~repro.core.space.GeometryBucket` automatically (see
    :func:`run_queue_batched`).
    """

    job: JobTable
    seed: int
    budget_b: float = 3.0
    bootstrap: np.ndarray | None = None

    def resolved_bootstrap(self) -> np.ndarray:
        if self.bootstrap is not None:
            return np.asarray(self.bootstrap)
        return latin_hypercube_indices(
            self.job.space, self.job.bootstrap_size(),
            np.random.default_rng(self.seed))


def _init_run_states(requests: list[RunRequest],
                     settings: lookahead.Settings,
                     m_pad: int | None = None) -> dict:
    """Host-side bootstrap replay for a batch of pending runs, float32 —
    Alg. 1 lines 6-8, the exact arithmetic `optimize` performs before its
    selection loop starts (including the constraint-cap censoring of
    bootstrap runs).  Returns [R, ...] numpy/JAX initial-state arrays plus
    the per-run budgets the outcome reconstruction needs.

    ``m_pad`` widens the per-run state rows to a geometry bucket's point
    width; bootstrap replay writes native indices only, so the padding tail
    stays unobserved (never censored, never explored) by construction.
    """
    r_tot = len(requests)
    m = requests[0].job.space.n_points if m_pad is None else m_pad
    y0 = np.zeros((r_tot, m), np.float32)
    m0 = np.zeros((r_tot, m), bool)
    c0 = np.zeros((r_tot, m), bool)
    cx0 = np.zeros((r_tot, m), bool)
    bx0 = np.zeros((r_tot, m), np.float32)
    beta0 = np.zeros(r_tot, np.float32)
    expl0 = np.full((r_tot, m), -1, np.int32)
    n_exp0 = np.zeros(r_tot, np.int32)
    budgets = np.zeros(r_tot, np.float64)
    for r, req in enumerate(requests):
        host = req.job.host_view()
        tau_boot = _boot_tau(req.job, settings)
        budget = req.job.budget(req.budget_b)
        budgets[r] = budget
        beta0[r] = np.float32(budget)
        boot = req.resolved_bootstrap()
        for j, i in enumerate(boot):
            i = int(i)
            cut = bool(host.runtime[i] > tau_boot)
            billed = (np.float32(tau_boot * host.unit_price[i]) if cut
                      else host.cost[i])
            y0[r, i] = billed
            m0[r, i] = True
            c0[r, i] = cut
            cx0[r, j] = cut
            bx0[r, j] = billed
            beta0[r] = beta0[r] - billed
            expl0[r, j] = i
        n_exp0[r] = len(boot)
    keys0 = jnp.stack([jax.random.PRNGKey(req.seed) for req in requests])
    return {"keys": keys0, "y": y0, "mask": m0, "beta": beta0,
            "explored": expl0, "n_exp": n_exp0, "cens": c0, "cexpl": cx0,
            "bexpl": bx0, "budgets": budgets}


def _reconstruct_outcome(job: JobTable, settings: lookahead.Settings,
                         budget: float, explored: list[int],
                         cflags: list[bool], billed, beta_final: float,
                         sel_s: float) -> Outcome:
    """Post-hoc :class:`Outcome` from a recorded exploration trace — pure
    table math, identical to what the sequential loop computes inline.

    ``spend_trajectory`` replays the run's float32 budget subtraction
    host-side — the same op order the episode executed — so it is
    bit-identical to the sequential oracle's inline bookkeeping.
    """
    rec = _recommend(job, explored, cflags)
    trajectory = [_trajectory_point(job, explored[:j + 1], cflags[:j + 1])
                  for j in range(len(explored))]
    beta_r = np.float32(budget)
    spend_traj = []
    for b in billed:
        beta_r = np.float32(beta_r - b)
        spend_traj.append(float(budget - beta_r))
    return Outcome(
        job=job.name, policy=settings.policy, recommended=rec,
        cno=job.cno(rec), nex=len(explored),
        spent=float(budget - beta_final), budget=float(budget),
        found_optimum=(rec == job.optimum_index),
        explored=tuple(explored), select_seconds=sel_s,
        trajectory=tuple(trajectory),
        censored=tuple(i for i, f in zip(explored, cflags) if f),
        spend_trajectory=tuple(spend_traj))


# --------------------------------------------------------------------------- #
# Lane-compacting work-queue scheduler (segment-driven)
# --------------------------------------------------------------------------- #
# A step quota that a terminating queue can never hit: "run to completion".
_STEPS_UNBOUNDED = np.int32(np.iinfo(np.int32).max)

# Slot-carry fields present only when ``s.timeout`` (the no-timeout
# program carries none of them, leaving its compiled episode unchanged).
_CARRY_TIMEOUT_KEYS = ("cens", "cexpl", "bexpl")


def _fresh_slot_carry(l_dim: int, m_dim: int, s: lookahead.Settings,
                      device=None) -> dict:
    """All-idle slot carry for a segment-driven episode: every seat empty
    (``rid = -1``, inactive), queue head at 0.  The streaming service starts
    from this and keeps the carry device-resident between segments.

    ``device`` (a ``jax.Device`` or ``Sharding``) commits the carry there —
    how the sharded service births each shard's resident state on its own
    device (``service/placement.py``).  None keeps the default-device,
    uncommitted behaviour of the single-engine service.  Placement cannot
    change the carry's values, only where they live."""
    carry = {"key": jnp.zeros((l_dim, 2), jnp.uint32),
             "y": jnp.zeros((l_dim, m_dim), jnp.float32),
             "mask": jnp.zeros((l_dim, m_dim), bool),
             "beta": jnp.zeros((l_dim,), jnp.float32),
             "explored": jnp.full((l_dim, m_dim), -1, jnp.int32),
             "n_exp": jnp.zeros((l_dim,), jnp.int32),
             "rid": jnp.full((l_dim,), -1, jnp.int32),
             "active": jnp.zeros((l_dim,), bool),
             "qhead": jnp.int32(0)}
    if s.timeout:
        carry["cens"] = jnp.zeros((l_dim, m_dim), bool)
        carry["cexpl"] = jnp.zeros((l_dim, m_dim), bool)
        carry["bexpl"] = jnp.zeros((l_dim, m_dim), jnp.float32)
    if device is not None:
        carry = {k: jax.device_put(v, device) for k, v in carry.items()}
    return carry


def _seed_carry_from_queue(queue: dict, l_dim: int,
                           s: lookahead.Settings) -> dict:
    """Seat the first ``l_dim`` queue rows in the slots (``qhead = l_dim``,
    ``rid = l_dim + row``) — the one-shot entry's initial state, equivalent
    to the streaming broker's host-side seating of idle slots."""
    load = lambda a: jnp.asarray(a)[:l_dim]
    carry = {"key": load(queue["keys"]), "y": load(queue["y"]),
             "mask": load(queue["mask"]), "beta": load(queue["beta"]),
             "explored": load(queue["explored"]),
             "n_exp": load(queue["n_exp"]),
             "rid": l_dim + jnp.arange(l_dim, dtype=jnp.int32),
             "active": jnp.ones((l_dim,), bool),
             "qhead": jnp.int32(l_dim)}
    if s.timeout:
        for k in _CARRY_TIMEOUT_KEYS:
            carry[k] = load(queue[k])
    return carry


@functools.partial(jax.jit, static_argnames=("s",))
def _episode_segment(carry, queue, qtail, evict, low_water, step_quota,
                     job_ids, cost, runtime, points, left, thresholds,
                     valid, u, t_max, s: lookahead.Settings):
    """Advance ``l_dim`` lane *slots* through one bounded episode segment.

    One ``lax.while_loop``; each iteration selects for every slot at once
    (same vmapped kernel as the lockstep episode) and applies Alg. 1's
    budget accounting and stopping rule as masked updates.  A slot holds a
    *seat*, not a fixed run: when its run terminates (Gamma empty,
    unaffordable BO pick, or budget empty), the slot scatters the run's
    final state into run-id-indexed output buffers and immediately gathers
    the next pending run's initial state from the device-resident queue
    head — fixed-width selector programs throughout, so nothing recompiles
    as lanes repack.

    The segment exits when any of these holds (`cond`):

    * **drained** — the queue head passed ``qtail`` and every slot is idle
      (the one-shot entry :func:`run_queue_batched` runs exactly one such
      segment to completion);
    * **low water** — fewer than ``low_water`` pending rows remain, giving
      the host a chance to refill the device queue from its admission
      backlog (pass 0 to disable; a segment always runs at least one step
      so a host driving segments in a loop cannot livelock);
    * **step quota** — ``step_quota`` iterations elapsed (the streaming
      service's responsiveness bound: the host harvests finished runs and
      admits new ones between segments).

    ``carry`` holds the persistent slot state (:func:`_fresh_slot_carry` /
    :func:`_seed_carry_from_queue`); ``queue`` holds [C, ...] pending
    initial run states of which rows ``qhead..qtail`` are still unconsumed;
    ``qtail``/``low_water``/``step_quota`` are traced scalars so segment
    pacing never recompiles.  A run seated from queue row ``j`` banks into
    output row ``l_dim + j``; rows below ``l_dim`` are banking targets for
    runs already seated at segment start (the streaming broker re-keys
    in-flight runs to their slot index between segments).

    ``evict`` is a traced [l_dim] bool "evict at boundary" flag (pass
    all-False for the one-shot drain): before the first step, any seated
    slot whose flag is set banks its *partial* run state into its
    run-id-indexed output row — the exact buffers a finished run banks
    into, with ``out_done`` left False so the caller can tell an evicted
    run from a completed one — and the seat is freed for refill inside the
    same segment.  The service layer uses this for cancellation of seated
    runs (the banked row becomes the partial :class:`Outcome`) and for
    preemption under queue pressure (the host snapshots the evicted slot's
    carry rows into a resumable request; bootstrap replay makes resume
    bit-identical to an uninterrupted run).  Because the flag is traced,
    cancel/preempt decisions never recompile the segment program.

    ``job_ids`` is None for a single-job queue (``cost``/``runtime``/``u``
    are [M] rows and ``t_max`` a scalar, shared by every slot — the same
    selector geometry as the lockstep episode).  For a mixed-job queue it
    is [l_dim + C] int32 over *run ids* into [J, M]-stacked tables, and
    each slot gathers its current run's job row every iteration
    (slot-indexed selection: per-slot ``u``/``t_max`` via
    :func:`lookahead.slot_price_rows`).

    ``valid`` is None for a native shared-geometry queue (the historical
    program, traced unchanged).  For a geometry-bucketed queue (jobs of
    *different* native [M, F, T] padded to one bucket — see
    :func:`run_queue_batched`) ``points``/``left``/``thresholds`` are
    [J, ...]-stacked padded space tensors, ``valid`` is the [J, M]
    point-validity mask, and each slot gathers its run's space rows by job
    id alongside the price rows, so one compiled segment serves every
    member geometry of the bucket.

    Returns ``(carry', report)``: the updated persistent slot state and the
    per-segment report (``out_done``/``out_beta``/``out_nexp``/``out_expl``
    [+ ``out_cexpl``/``out_bexpl`` with timeouts] banking buffers, plus
    ``steps`` and ``busy`` — active-slot-steps, the lane-occupancy
    numerator).  Seating order is deterministic (queue order by slot index)
    but — because every run's PRNG chain, budget arithmetic and decision
    pipeline are functions of its own state only — outcomes are independent
    of seating *and* arrival order; the caller re-keys results by run id,
    never by slot.
    """
    with scope("lynceus"), scope("segment"):
        l_dim, m_dim = carry["y"].shape
        c_dim = queue["y"].shape[0]
        n_out = l_dim + c_dim
        lanes = jnp.arange(l_dim)

        def cond(st):
            pending = qtail - st["qhead"]
            has_work = st["active"].any() | (pending > 0)
            return (has_work & (st["steps"] < step_quota)
                    & ((st["steps"] == 0) | (pending >= low_water)))

        def body(st):
            split = jax.vmap(jax.random.split)(st["key"])       # [L, 2, 2]
            key, sub = split[:, 0], split[:, 1]
            rid_safe = jnp.maximum(st["rid"], 0)
            u_l, t_l, jid = lookahead.slot_price_rows(job_ids, rid_safe, u,
                                                      t_max)
            if jid is not None and points.ndim == 3:
                # Geometry-bucketed queue: each seat selects on its own job's
                # padded space tensors and validity row.
                pts_l, left_l, thr_l = points[jid], left[jid], thresholds[jid]
                val_l = valid[jid]
            else:
                pts_l, left_l, thr_l, val_l = points, left, thresholds, valid
            idx, sel_ok, diag = lookahead.select_next_batched(
                sub, st["y"], st["mask"], jnp.maximum(st["beta"], 0.0),
                pts_l, left_l, thr_l, u_l, t_l, s,
                st["cens"] if s.timeout else None, val_l)
            if jid is None:
                c = cost[idx]
                t_run = runtime[idx] if s.timeout else None
                u_at = u[idx] if s.timeout else None
            else:
                pick = lambda tab: jnp.take_along_axis(
                    tab[jid], idx[:, None], axis=1)[:, 0]
                c = pick(cost)
                t_run = pick(runtime) if s.timeout else None
                u_at = pick(u) if s.timeout else None
            step, alive = _alg1_step(
                st, idx, c, t_run, u_at, sel_ok,
                diag["timeout"] if s.timeout else None, s, lanes, m_dim)

            # A slot's run terminated this step -> bank it by run id.
            finished = st["active"] & ~alive
            tgt = jnp.where(finished, rid_safe, n_out)      # OOB rows dropped
            out = {"out_done": st["out_done"].at[tgt].set(True, mode="drop"),
                   "out_beta": st["out_beta"].at[tgt].set(step["beta"],
                                                          mode="drop"),
                   "out_nexp": st["out_nexp"].at[tgt].set(step["n_exp"],
                                                          mode="drop"),
                   "out_expl": st["out_expl"].at[tgt].set(step["explored"],
                                                          mode="drop")}
            if s.timeout:
                out["out_cexpl"] = st["out_cexpl"].at[tgt].set(step["cexpl"],
                                                               mode="drop")
                out["out_bexpl"] = st["out_bexpl"].at[tgt].set(step["bexpl"],
                                                               mode="drop")

            # Refill seatless slots (just finished, or idle from an earlier
            # drain) from the queue head, in slot order: the k-th seatable slot
            # (k = rank among seatable) takes queue row qhead + k.
            seatable = ~alive
            rank = jnp.cumsum(seatable.astype(jnp.int32)) - 1
            cand = st["qhead"] + rank
            got = seatable & (cand < qtail)
            src = jnp.where(got, cand, 0)
            fill = lambda init, cur: jnp.where(
                got.reshape((l_dim,) + (1,) * (cur.ndim - 1)), init[src], cur)
            nxt = {"key": fill(queue["keys"], key),
                   "rid": jnp.where(got, l_dim + cand,
                                    jnp.where(finished, -1, st["rid"])),
                   "active": alive | got,
                   "qhead": st["qhead"] + got.sum(dtype=jnp.int32),
                   "steps": st["steps"] + 1,
                   "busy": st["busy"] + st["active"].sum(dtype=jnp.int32)}
            for k, v in step.items():
                nxt[k] = fill(queue[k], v)
            nxt.update(out)
            return nxt

        st0 = dict(carry)
        st0.update(steps=jnp.int32(0), busy=jnp.int32(0),
                   out_done=jnp.zeros((n_out,), bool),
                   out_beta=jnp.zeros((n_out,), jnp.float32),
                   out_nexp=jnp.zeros((n_out,), jnp.int32),
                   out_expl=jnp.full((n_out, m_dim), -1, jnp.int32))
        if s.timeout:
            st0["out_cexpl"] = jnp.zeros((n_out, m_dim), bool)
            st0["out_bexpl"] = jnp.zeros((n_out, m_dim), jnp.float32)

        # Boundary eviction: bank flagged seats' partial state by run id
        # (out_done stays False — these rows are partial, not completed) and
        # free the seat before the loop so it refills like any drained slot.
        kill = carry["active"] & evict
        tgt0 = jnp.where(kill, jnp.maximum(carry["rid"], 0), n_out)
        st0["out_beta"] = st0["out_beta"].at[tgt0].set(carry["beta"],
                                                       mode="drop")
        st0["out_nexp"] = st0["out_nexp"].at[tgt0].set(carry["n_exp"],
                                                       mode="drop")
        st0["out_expl"] = st0["out_expl"].at[tgt0].set(carry["explored"],
                                                       mode="drop")
        if s.timeout:
            st0["out_cexpl"] = st0["out_cexpl"].at[tgt0].set(carry["cexpl"],
                                                             mode="drop")
            st0["out_bexpl"] = st0["out_bexpl"].at[tgt0].set(carry["bexpl"],
                                                             mode="drop")
        st0["active"] = carry["active"] & ~kill
        st0["rid"] = jnp.where(kill, -1, carry["rid"])

        st = jax.lax.while_loop(cond, body, st0)
        report = {k: st.pop(k) for k in list(st)
                  if k.startswith("out_") or k in ("steps", "busy")}
        return st, report


def _spaces_shared(jobs: list[JobTable]) -> bool:
    """True when every job's space is bit-identical to the first's —
    the condition for the native shared-tensor selector program."""
    ref = jobs[0].space
    return all(job.space.n_points == ref.n_points
               and np.array_equal(job.space.points, ref.points)
               and np.array_equal(job.space.thresholds, ref.thresholds)
               for job in jobs[1:])


def _resolve_bucket(jobs: list[JobTable], bucket) -> GeometryBucket | None:
    """The geometry bucket a queue must run under, or None for the native
    shared-space program.

    ``bucket`` may be None (auto: pad only when the jobs' spaces actually
    differ), a ``(m, f, t)`` tuple, or a :class:`GeometryBucket` — an
    explicit bucket forces padding even for a single geometry (that is how
    a service pre-compiles one program for jobs it has not seen yet, and
    how the padding-invariance suites audit a single job against its
    padded self).  A bucket narrower than a member geometry raises in
    :func:`_queue_spaces`' ``pad_to`` calls, which both callers run
    immediately after this.
    """
    if bucket is None:
        if _spaces_shared(jobs):
            return None
        return GeometryBucket.for_spaces([j.space for j in jobs])
    if not isinstance(bucket, GeometryBucket):
        bucket = GeometryBucket(*bucket)
    return bucket


def _queue_spaces(jobs: list[JobTable], bucket: GeometryBucket):
    """[J, ...]-stacked padded space tensors + validity masks for a
    geometry-bucketed queue: ``(points [J, M, F], left [J, M, F, T],
    thresholds [J, F, T], valid [J, M])`` at the bucket widths."""
    pads = [j.space.pad_to(bucket) for j in jobs]
    return (jnp.stack([jnp.asarray(p.points) for p in pads]),
            jnp.stack([trees.make_left_table(p.points, p.thresholds)
                       for p in pads]),
            jnp.stack([jnp.asarray(p.thresholds) for p in pads]),
            jnp.stack([jnp.asarray(p.valid) for p in pads]))


def _queue_tables(jobs: list[JobTable], u0, bucket: GeometryBucket | None = None):
    """Device job tables for a (possibly mixed-job) queue — shared by the
    one-shot entry and the streaming service engine so the two drivers
    cannot drift.

    Single job, no bucket: shared [M] rows and a scalar t_max — the
    lockstep selector geometry (``u0`` is the space-bound price row from
    ``lookahead.space_arrays``).  Otherwise: [J, M]-stacked tables and
    [J] t_max for run-id-indexed gathers, padded to ``bucket.m`` rows when
    a bucket is active (a bucketed queue always stacks, even for J = 1, so
    one code path serves every bucket member).  Returns
    ``(cost, runtime, u, t_max, single)``.
    """
    if len(jobs) == 1 and bucket is None:
        dev = jobs[0].device_view()
        return dev.cost, dev.runtime, u0, jnp.float32(jobs[0].t_max), True
    m_pad = None if bucket is None else bucket.m
    devs = [j.device_view(m_pad) for j in jobs]
    return (jnp.stack([d.cost for d in devs]),
            jnp.stack([d.runtime for d in devs]),
            jnp.stack([d.unit_price for d in devs]),
            jnp.asarray([j.t_max for j in jobs], jnp.float32), False)


def episode_cache_size() -> int:
    """Compiled-entry count of the jitted episode programs (segment +
    lockstep bodies) — the compile-count observable of the geometry-bucket
    claim: draining a queue that mixes J native geometries padded into one
    bucket must add exactly **one** entry here (one program per bucket),
    where J per-geometry sub-queues would add J.  The per-step selector is
    inlined into these programs, so ``lookahead.selector_cache_size`` must
    not grow at all during a bucketed drain; scripts/ci.sh and
    benchmarks/batched_vs_sequential.py gate both counts.
    """
    return int(_episode_segment._cache_size()
               + _batched_episode._cache_size())


def run_queue(requests: list[RunRequest],
              settings: lookahead.Settings) -> list[Outcome]:
    """Sequential oracle over a heterogeneous work queue — one
    :func:`optimize` call per request, selectors cached per job.  The
    reference :func:`run_queue_batched` is audited against."""
    selectors: dict[int, Callable] = {}
    outs = []
    for req in requests:
        sel = None
        if settings.policy != "rnd":
            sel = selectors.get(id(req.job))
            if sel is None:
                sel = lookahead.make_selector(
                    req.job.space, req.job.unit_price, req.job.t_max,
                    settings)
                selectors[id(req.job)] = sel
        outs.append(optimize(req.job, settings, budget_b=req.budget_b,
                             seed=req.seed,
                             bootstrap=req.resolved_bootstrap(),
                             selector=sel))
    return outs


def run_queue_batched(requests: list[RunRequest],
                      settings: lookahead.Settings, *,
                      lane_slots: int | None = None,
                      bucket=None) -> list[Outcome]:
    """Drain a mixed-budget, mixed-job run queue through compacting lanes.

    The device-resident counterpart of :func:`run_queue`: R pending runs,
    ``lane_slots`` seats, one jitted episode segment run to completion (see
    :func:`_episode_segment`).  Jobs and budgets may differ per request —
    this is the tail-heavy-sweep entry point, where lockstep lanes would
    idle behind the longest run.  Jobs whose spaces differ in *geometry*
    ([M, F, T]) are right-padded into one
    :class:`~repro.core.space.GeometryBucket` (auto-sized by
    ``GeometryBucket.for_spaces``, or forced via ``bucket`` — a
    ``(m, f, t)`` tuple or ``GeometryBucket``): the selector compiles once
    per bucket instead of once per geometry, and the padding-invariant
    selection stack (masked candidates/incumbent/budget filter, prefix-
    stable bootstrap and speculation keys) keeps every run's decisions
    identical to its native program.  Outcomes are returned in request
    order and are bit-identical to :func:`run_queue` on the audited
    configurations (same contract, and the same caveats, as
    :func:`run_many_batched`; the padding-invariance suites in
    tests/test_padded_space.py and tests/test_batched_harness.py pin the
    bucketed path).
    """
    if not requests:
        return []
    if settings.policy == "rnd":
        return run_queue(requests, settings)
    jobs: list[JobTable] = []
    for req in requests:
        if not any(req.job is j for j in jobs):
            jobs.append(req.job)
    bucket = _resolve_bucket(jobs, bucket)
    job0 = jobs[0]
    r_tot = len(requests)
    m_sel = job0.space.n_points if bucket is None else bucket.m
    if lane_slots is None:
        lane_slots = _auto_lane_chunk(job0, settings, r_tot, m=m_sel)
    lane_slots = max(1, min(lane_slots, r_tot))

    if bucket is None:
        points, left, thresholds, u0 = lookahead.space_arrays(
            job0.space, job0.unit_price)
        valid_t = None
    else:
        # Also validates bucket >= every member geometry (pad_to raises)
        # before any bucket-width state array is built.
        points, left, thresholds, valid_t = _queue_spaces(jobs, bucket)
        u0 = None
    queue = _init_run_states(requests, settings,
                             None if bucket is None else bucket.m)
    budgets = queue.pop("budgets")
    cost_t, runtime_t, u_t, tmax_t, single = _queue_tables(jobs, u0, bucket)
    if single:
        job_ids = None
    else:
        index_of = {id(j): k for k, j in enumerate(jobs)}
        # Run-id indexed: rows below lane_slots are seat-section padding
        # (the one-shot entry seats straight from the queue, so in-flight
        # runs keep their queue-row run id l_dim + r).
        job_ids = jnp.asarray(
            [0] * lane_slots + [index_of[id(req.job)] for req in requests],
            jnp.int32)

    qarrays = {k: jnp.asarray(v) for k, v in queue.items()
               if settings.timeout or k not in _CARRY_TIMEOUT_KEYS}
    carry = _seed_carry_from_queue(qarrays, lane_slots, settings)
    t0 = time.perf_counter()
    # One unbounded segment (no low-water mark, no step quota) drains the
    # whole queue — the streaming service drives the same compiled body in
    # bounded slices instead (src/repro/service/).
    _, report = jax.block_until_ready(_episode_segment(
        carry, qarrays, np.int32(r_tot),
        jnp.zeros((lane_slots,), bool), np.int32(0), _STEPS_UNBOUNDED,
        job_ids, cost_t, runtime_t if settings.timeout else None, points,
        left, thresholds, valid_t, u_t, tmax_t, settings))
    steps = int(report["steps"])
    wall = time.perf_counter() - t0
    # Amortized wall time per selection (steps x slots selections per
    # episode), comparable with the sequential oracle's per-call mean.
    # Caveats: includes the queue refill machinery, and a cold call folds
    # in XLA compilation.
    sel_s = wall / max(steps * lane_slots, 1)

    # Runs seated from queue row r bank into report row lane_slots + r.
    beta_f = np.asarray(report["out_beta"])[lane_slots:]
    expl_f = np.asarray(report["out_expl"])[lane_slots:]
    n_exp_f = np.asarray(report["out_nexp"])[lane_slots:]
    if settings.timeout:
        cexpl_f = np.asarray(report["out_cexpl"])[lane_slots:]
        bexpl_f = np.asarray(report["out_bexpl"])[lane_slots:]
    outs: list[Outcome] = []
    for r, req in enumerate(requests):
        explored = [int(i) for i in expl_f[r, :n_exp_f[r]]]
        if settings.timeout:
            cflags = [bool(f) for f in cexpl_f[r, :n_exp_f[r]]]
            billed = bexpl_f[r, :n_exp_f[r]]
        else:
            cflags = [False] * len(explored)
            billed = req.job.host_view().cost[explored]
        outs.append(_reconstruct_outcome(
            req.job, settings, float(budgets[r]), explored, cflags, billed,
            beta_f[r], sel_s))
    return outs


def run_many_batched(job: JobTable, settings: lookahead.Settings, *,
                     n_runs: int = 100, budget_b: float = 3.0, seed: int = 0,
                     seeds=None, bootstraps=None, lane_chunk: int | None = None,
                     scheduler: str = "compact", bucket=None) -> list[Outcome]:
    """Batched ``run_many``: R device-resident runs on shared lane slots.

    Each run executes the exact Alg. 1 semantics of the sequential oracle —
    identical PRNG key schedule, float32 budget accounting, bootstrap replay
    and stopping rule — but the whole sweep is a handful of compiled XLA
    programs instead of a Python loop with host<->device sync points per
    exploration step.

    Two schedulers share that contract:

    * ``"compact"`` (default) — the lane-compacting work queue
      (:func:`_episode_segment`, run as one unbounded segment): runs are
      queued, ``lane_chunk`` slots
      drain the queue, and a slot whose run terminates immediately loads the
      next pending run inside the same ``lax.while_loop``.  The segment ends
      when the queue is drained and every slot is idle, so short runs never
      hold the device hostage to the longest lane — the tail-heavy win is
      measured in ``benchmarks/batched_vs_sequential.py``.
    * ``"lockstep"`` — the PR-1 fixed-assignment episode
      (:func:`_batched_episode`): each chunk of ``lane_chunk`` runs advances
      in lockstep until the *last* lane's budget empties.  Kept as the
      refill-free baseline the compacting scheduler is audited against.

    Equivalence contract: outcomes are bit-identical to :func:`run_many` on
    the audited configurations (the synthetic job is exact across thousands
    of runs for every policy and both schedulers; see
    tests/test_batched_harness.py and scripts/ci.sh).  XLA recompiles the
    selector per batch geometry and its fusion choices wobble scores in the
    last ulps; every *decision* in the pipeline is hardened against that
    (z-space budget filter, cancellation-free split gains, quantized
    argmaxes — see ``acquisition.quantize_scores``), but on larger spaces a
    sub-percent fraction of runs can still step onto a near-tied,
    statistically equivalent branch.  Use ``run_many`` when strict per-run
    reproduction against the oracle is required.

    Timeout-censored exploration (``settings.timeout``) holds the same
    contract: the censoring compare ``t_run > τ`` and the billed bound
    ``τ·U`` run on quantized, geometry-hardened values (see
    ``acquisition.timeout_cap``), the per-config run times are gathered from
    ``device_view().runtime`` on device, and per-step censor flags are
    recorded alongside the exploration order so outcomes — including the
    ``censored`` tuple — stay bit-identical to the sequential oracle.

    ``rnd`` has no model to amortize and is driven by host-side numpy RNG, so
    it falls through to the sequential path.  ``lane_chunk`` bounds how many
    runs share one compiled episode (memory control on big spaces); the
    default is sized from the lookahead state tensor.  ``budget_b`` may be a
    scalar or a per-run sequence (mixed-budget sweeps).  ``trajectory``, CNO
    and NEX are reconstructed post hoc from the recorded exploration order —
    pure table math, identical to what the sequential loop computes inline.
    """
    if scheduler not in ("compact", "lockstep"):
        raise ValueError(f"unknown scheduler {scheduler!r}; "
                         "expected 'compact' or 'lockstep'")
    if bucket is not None and scheduler != "compact":
        raise ValueError("geometry buckets run on the compacting "
                         "scheduler only (lockstep is the native-geometry "
                         "audit baseline)")
    if settings.policy == "rnd":
        return run_many(job, settings, n_runs=n_runs, budget_b=budget_b,
                        seed=seed, seeds=seeds, bootstraps=bootstraps)
    seeds, bootstraps = _resolve_runs(job, seed, n_runs, seeds, bootstraps)
    budgets_b = _resolve_budget_b(budget_b, len(seeds))
    n_runs = len(seeds)
    requests = [RunRequest(job, s, b, boot)
                for s, b, boot in zip(seeds, budgets_b, bootstraps)]
    if scheduler == "compact":
        # Slot sizing is deferred to run_queue_batched when lane_chunk is
        # None: it must account for the *bucket* point width, not the
        # native one (a forced bucket can widen the per-slot speculative
        # tensor by (bucket.m / M)^2).
        return run_queue_batched(requests, settings, lane_slots=lane_chunk,
                                 bucket=bucket)
    if lane_chunk is None:
        lane_chunk = _auto_lane_chunk(job, settings, n_runs)

    m = job.space.n_points
    host = job.host_view()
    dev = job.device_view()
    points, left, thresholds, u = lookahead.space_arrays(
        job.space, job.unit_price)
    t_max32 = jnp.float32(job.t_max)

    outs: list[Outcome] = []
    for lo in range(0, n_runs, lane_chunk):
        chunk = requests[lo:lo + lane_chunk]
        r_dim = len(chunk)
        st = _init_run_states(chunk, settings)
        budgets = st["budgets"]

        t0 = time.perf_counter()
        res = jax.block_until_ready(
            _batched_episode(st["keys"], jnp.asarray(st["y"]),
                             jnp.asarray(st["mask"]),
                             jnp.asarray(st["beta"]),
                             jnp.asarray(st["explored"]),
                             jnp.asarray(st["n_exp"]),
                             jnp.asarray(st["cens"]) if settings.timeout
                             else None,
                             jnp.asarray(st["cexpl"]) if settings.timeout
                             else None,
                             jnp.asarray(st["bexpl"]) if settings.timeout
                             else None,
                             dev.cost,
                             dev.runtime if settings.timeout else None,
                             points, left, thresholds, u, t_max32, settings))
        beta_f, expl_f, n_exp_f, steps = res[:4]
        cexpl_f = np.asarray(res[4]) if settings.timeout else st["cexpl"]
        bexpl_f = np.asarray(res[5]) if settings.timeout else None
        wall = time.perf_counter() - t0
        # Amortized wall time per selection (steps x lanes selections per
        # episode), to stay comparable with the sequential oracle's per-call
        # mean.  Caveats: includes the masked-lane state update, and the
        # first chunk folds in XLA compilation.
        sel_s = wall / max(int(steps) * r_dim, 1)

        beta_f = np.asarray(beta_f)
        expl_f = np.asarray(expl_f)
        n_exp_f = np.asarray(n_exp_f)
        for r in range(r_dim):
            explored = [int(i) for i in expl_f[r, :n_exp_f[r]]]
            cflags = [bool(f) for f in cexpl_f[r, :n_exp_f[r]]]
            billed = (bexpl_f[r, :n_exp_f[r]] if bexpl_f is not None
                      else host.cost[explored])
            outs.append(_reconstruct_outcome(
                job, settings, float(budgets[r]), explored, cflags, billed,
                beta_f[r], sel_s))
    return outs
