"""What decides ``correct``, on the CPU at a tiny size: the reference agrees
with the program at every step of its runs, and the bfloat16 control, or
an altered probe or bill, does not pass."""

import numpy as np
import pytest

import _tiny
import check
import data
import traffic
from reference import Reference

SEED = 2 ** 31 + 12345


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    root = _tiny.make_root(tmp_path_factory.mktemp("tiny"))
    cfg = data.load_json(root / "bench" / "configs" / "tiny.json")
    return root, cfg, data.make_jobs(root, cfg)


def _served(cfg, jobs, n):
    """``n`` requests served by the program's sequential oracle."""
    from repro.core import Settings, optimize
    pj = data.program_jobs(jobs)
    s = Settings(**cfg["settings"])
    mix = dict(_tiny.CELLS["tiny.closed"], budgets=[3, 5])
    out = []
    for i in range(n):
        req = traffic.request(mix, jobs, SEED, 1, i)
        o = optimize(pj[req.job], s, budget_b=req.b, seed=req.seed,
                     bootstrap=np.asarray(req.bootstrap))
        out.append((req, o))
    return out


def _judge_all(cfg, jobs, served, decider=None):
    ref = Reference(cfg["settings"])
    rows, replays = [], []
    for req, o in served:
        rp = check.Replay(check.Ledger(jobs[req.job], cfg["settings"]), req,
                          o, True)
        rp.resolve(ref)
        replays.append(rp)
        for j in range(rp.n_sel + check.stopped_with_budget(rp)):
            y, obs, cens, beta = rp.state(j)
            args = (check.key_for_step(req.seed, j), y, obs, cens, beta,
                    rp.ledger.job.space.left, rp.ledger.u, rp.ledger.t_max)
            out = ref.decide(*args)
            pick = (check.served_step(rp, j) if decider is None else
                    check.control_step(decider.decide(*args), rp, j))
            rows.append(check.judge_step(out, rp, j, pick))
    return check.combine(rows, replays, served=decider is None)


def test_reference_agrees_with_the_program_at_every_step(tiny):
    _, cfg, jobs = tiny
    numbers = _judge_all(cfg, jobs, _served(cfg, jobs, 4))
    assert numbers["steps_checked"] >= 16
    assert all(numbers[k] == 0 for k in check.LIMITS), numbers
    assert check.verdict(numbers, 16)


def test_bfloat16_control_is_not_correct(tiny):
    _, cfg, jobs = tiny
    ctrl = Reference(cfg["settings"], dtype="bfloat16")
    numbers = _judge_all(cfg, jobs, _served(cfg, jobs, 4), ctrl)
    assert not check.verdict(numbers, 12), numbers


def test_a_wrong_bill_or_probe_is_seen(tiny):
    """An outcome altered after the fact: a probe swapped for another
    untested point, and a completed probe billed a cent over its cost."""
    import dataclasses
    _, cfg, jobs = tiny
    (req, o), = _served(cfg, jobs, 1)
    n_boot = jobs[req.job].space.bootstrap_size()
    assert o.nex > n_boot + 1
    free = [i for i in range(jobs[req.job].space.m) if i not in o.explored]
    expl = list(o.explored)
    expl[n_boot] = free[0]
    moved = dataclasses.replace(o, explored=tuple(expl))
    assert not check.verdict(_judge_all(cfg, jobs, [(req, moved)]), 1)
    cut = set(o.censored)
    k = next(k for k, i in enumerate(o.explored) if i not in cut)
    spend = [s + 0.01 * (j >= k) for j, s in enumerate(o.spend_trajectory)]
    billed = dataclasses.replace(o, spend_trajectory=tuple(spend))
    numbers = _judge_all(cfg, jobs, [(req, billed)])
    assert numbers["billing_errors"] > check.LIMITS["billing_errors"]

