"""Shard placement for the sharded streaming tuner: who serves a ticket.

The sharded service (``ServiceConfig.num_shards > 1``) keeps one resident
segment engine per shard — its own slot carry, device queue, tables and
metrics, committed to its own device — and the broker routes each admitted
ticket to exactly one shard, JetStream/MaxText-style (engine-per-device,
one host broker).  This module is the host-side half of that routing:

* :func:`choose_shard` — the placement policies.  ``least_backlog`` picks
  the shard with the fewest unfinished tickets (backlog + staged +
  in-flight), lowest shard id breaking ties; ``round_robin`` rotates.
  Both are pure functions of host-side integers — placement can never
  consult device state, so it can never perturb a traced program.
* **Sticky affinity** — a ticket that has ever been placed keeps its
  ``ticket.shard`` for life: cancel, preempt and resume are single-shard
  operations (the banked carry rows a preempted run resumes from live in
  its home engine's bookkeeping, and the flight-record validator rejects
  any cross-shard ticket stream — ``repro.obs.validate_lifecycle``).
* :func:`shard_meshes` / :func:`shard_shardings` — the device mapping:
  shard ``d`` owns a single-device ``Mesh`` over ``jax.devices()[d % n]``
  (modulo, so ``num_shards`` may exceed the device count — shards then
  share devices, which keeps doc examples and single-device CI runnable)
  and every resident array is committed there with a replicated
  ``NamedSharding`` built through the seeded ``repro.shard.api`` rule
  table.  Replicated-per-shard keeps the per-shard jaxpr free of
  collectives — bit-identical to the audited single-device segment
  program, which is the whole determinism story: sharding is *placement*,
  never a program change.

Determinism contract: placement decides only *where* (and therefore when)
a run executes.  Per-run PRNG keys, bootstrap replay and f32 billing are
placement-independent, so every Outcome — ``spend_trajectory`` included —
is byte-identical to the sequential oracle regardless of ``num_shards`` or
which shard served it (``tests/test_sharded_service.py`` pins it).
"""

from __future__ import annotations

import numpy as np

__all__ = ["PLACEMENT_POLICIES", "choose_shard", "shard_meshes",
           "shard_shardings", "shard_segment"]

PLACEMENT_POLICIES = ("least_backlog", "round_robin")


def choose_shard(policy: str, loads, home: int | None = None,
                 rr: int = 0) -> int:
    """Pick the shard for one ticket.

    ``loads`` is the per-shard unfinished-work vector (backlog depth +
    staged-unseated tickets + in-flight seats) at decision time; ``home``
    is the ticket's existing shard, if any — sticky affinity
    short-circuits every policy, so a preempted/resumed ticket never
    migrates.  ``rr`` is the broker's
    monotone round-robin cursor.  Deterministic: equal loads resolve to
    the lowest shard id.
    """
    n = len(loads)
    if n < 1:
        raise ValueError("need at least one shard")
    if home is not None:
        if not 0 <= home < n:
            raise ValueError(f"home shard {home} out of range [0, {n})")
        return home
    if n == 1:                       # degenerate: everything on shard 0
        return 0
    if policy == "least_backlog":
        return int(np.argmin(np.asarray(loads)))   # ties -> lowest id
    if policy == "round_robin":
        return rr % n
    raise ValueError(f"unknown placement_policy {policy!r} "
                     f"(known: {PLACEMENT_POLICIES})")


def shard_meshes(num_shards: int):
    """One single-device ``Mesh(("shard",))`` per shard, shard ``d`` on
    ``jax.devices()[d % len(devices)]`` (modulo: shards beyond the device
    count share devices rather than fail — placement degrades, programs
    don't change)."""
    import jax
    from jax.sharding import Mesh
    devs = jax.devices()
    return [Mesh(np.array([devs[d % len(devs)]]), ("shard",))
            for d in range(num_shards)]


def shard_shardings(num_shards: int):
    """Per-shard replicated ``NamedSharding`` — what every resident array
    of shard ``d`` (slot carry, device queue, space/table tensors) is
    committed with.  Built through the ``repro.shard.api`` rule table with
    no logical axes, i.e. ``PartitionSpec()`` on the shard's own
    single-device mesh: replicated within the shard, so the traced segment
    stays collective-free and the jaxpr auditable."""
    from repro.shard.api import BASE_RULES, sharding_for
    return [sharding_for((), (), BASE_RULES, mesh)
            for mesh in shard_meshes(num_shards)]


def shard_segment(carry, queue, qtail, evict, low_water, step_quota,
                  job_ids, cost, runtime, points, left, thresholds, valid,
                  u, t_max, s):
    """The per-shard segment entry point the registry audits
    (``episode/segment/sharded`` in ``repro.analysis.registry``).

    Delegates to ``_episode_segment`` unchanged: a shard runs the *same*
    jitted program as the single-device service on inputs committed to its
    own device — placement is the only difference, and placement is not
    part of the program.  Registering this wrapper (traced with
    shard-committed example inputs) pins exactly that: the sharded path
    can never grow shard-local math the auditor has not seen.
    """
    from repro.core.optimizer import _episode_segment
    return _episode_segment(carry, queue, qtail, evict, low_water,
                            step_quota, job_ids, cost, runtime, points,
                            left, thresholds, valid, u, t_max, s)
