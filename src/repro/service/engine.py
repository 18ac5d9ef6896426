"""Device-side half of the streaming tuner: a resident segment engine.

One :class:`SegmentEngine` owns the persistent slot carry of a
lane-compacting episode (``_episode_segment`` in ``core/optimizer.py``)
and, per pump, performs the host/device handshake around one bounded
segment:

1. **seat** — copy the head of the staged admission list straight into
   idle lane slots (pure array copies of the exact per-run initial states
   ``_init_run_states`` replays; no arithmetic, so no parity risk);
2. **inject** — materialize the remaining staged runs as the device-side
   pending queue (up to ``queue_capacity`` rows);
3. **dispatch** — run one jitted segment (low-water + step-quota exits are
   traced scalars: pacing never recompiles);
4. **harvest** — pull the ``out_*`` banking buffers, rebuild each finished
   run's :class:`~repro.core.Outcome` via ``_reconstruct_outcome`` (the
   same post-hoc table math every other backend uses), and re-key in-flight
   runs to their slot index so the next segment's banking targets stay
   stable while queue rows are recycled.

Everything here runs on the broker's pump thread; the engine itself is not
thread-safe (see ``broker.py`` for the locking story).
"""

from __future__ import annotations

import dataclasses
import time
from typing import TYPE_CHECKING

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import lookahead
from repro.core.optimizer import (_CARRY_TIMEOUT_KEYS, _episode_segment,
                                  _fresh_slot_carry, _init_run_states,
                                  _queue_spaces, _queue_tables,
                                  _reconstruct_outcome, _resolve_bucket)
from repro.obs import FlightRecorder, phase_span

if TYPE_CHECKING:  # service <-> jobs import hygiene mirrors core's
    from repro.core.optimizer import Outcome
    from repro.jobs.tables import JobTable
    from repro.service.config import ServiceConfig

__all__ = ["SegmentEngine", "SegmentReport", "ShardedEngine"]

_STATE_FIELDS = ("keys", "y", "mask", "beta", "explored", "n_exp")
# queue-row field -> slot-carry field (only "keys" differs)
_CARRY_NAME = {"keys": "key"}


@dataclasses.dataclass(frozen=True)
class SegmentReport:
    """Host-visible facts about one executed segment."""

    steps: int              # while-loop iterations this segment
    busy_slot_steps: int    # sum over iterations of seated slots
    lane_slots: int
    wall_seconds: float
    seated: int             # staged runs copied into idle slots host-side
    injected: int           # staged runs materialized as device queue rows
    consumed: int           # device queue rows seated on device mid-segment
    completed: int          # runs banked + reconstructed this segment
    in_flight: int          # seats still holding a live run afterwards
    evicted: int = 0        # seats banked partial + freed at the boundary
    resumed: int = 0        # previously preempted runs re-seated on device
    dropped: int = 0        # cancel-requested staged runs filtered pre-seat

    @property
    def occupancy(self) -> float:
        """Seated-slot fraction of this segment's slot-steps."""
        return self.busy_slot_steps / max(self.steps * self.lane_slots, 1)


class SegmentEngine:
    """Resident episode state + the seat/inject/dispatch/harvest cycle.

    ``jobs`` fixes the table stack (and therefore the compiled segment
    geometry) for the service's lifetime: every submitted request must
    reference one of these :class:`JobTable` objects.  Jobs sharing one
    space geometry run the native shared-tensor program; jobs of different
    geometries are right-padded into one geometry bucket (auto-sized, or
    forced via ``config.bucket``) so the service still compiles exactly
    one segment program — the same contract as ``run_queue_batched``, held
    eagerly at registration instead of per call.
    """

    def __init__(self, jobs: list[JobTable], settings,
                 config: ServiceConfig, recorder: FlightRecorder | None = None,
                 *, shard_id: int = 0, device=None):
        if not jobs:
            raise ValueError("register at least one JobTable")
        if settings.policy == "rnd":
            raise ValueError(
                "policy 'rnd' is host-driven (no model to keep device-"
                "resident); stream it through run_queue instead")
        self.jobs = list(jobs)
        self.settings = settings
        self.config = config
        # Shard identity + placement (service/placement.py): a sharded
        # service runs one engine per shard, every resident array committed
        # to the shard's device.  device=None (single-engine service) keeps
        # arrays uncommitted on the default device, exactly as before.
        self.shard_id = int(shard_id)
        self._device = device
        self.bucket = _resolve_bucket(self.jobs, config.bucket)
        job0 = self.jobs[0]
        self.m_dim = (job0.space.n_points if self.bucket is None
                      else self.bucket.m)
        self.l_dim = config.lane_slots
        self.c_dim = config.queue_capacity

        if self.bucket is None:
            pts, left, thr, u0 = lookahead.space_arrays(job0.space,
                                                        job0.unit_price)
            self._valid = None
        else:
            pts, left, thr, self._valid = _queue_spaces(self.jobs,
                                                        self.bucket)
            u0 = None
        self._space = tuple(self._place(x) for x in (pts, left, thr))
        self._valid = self._place(self._valid)
        (self._cost, self._runtime, self._u, self._tmax,
         self._single) = _queue_tables(self.jobs, u0, self.bucket)
        self._cost = self._place(self._cost)
        self._runtime = self._place(self._runtime)
        self._u = self._place(self._u)
        self._tmax = self._place(self._tmax)

        self._carry = _fresh_slot_carry(self.l_dim, self.m_dim, settings,
                                        device=device)
        self._slot_tickets: list = [None] * self.l_dim
        self._slot_jids = np.zeros(self.l_dim, np.int32)
        # Cumulative wall/steps for the Outcome.select_seconds amortization
        # (same estimator as run_queue_batched's, accrued across segments).
        self._wall = 0.0
        self._steps = 0
        # Observability (zero-perturbation: the recorder watches the
        # handshake, it never feeds the traced program).  A disabled
        # recorder makes every emit/span a no-op.
        self._recorder = (recorder if recorder is not None
                          else FlightRecorder(enabled=False))
        self._profiler = config.trace_profiler
        self._segment_seq = 0

    # ------------------------------------------------------------------ #
    def _place(self, x):
        """Commit ``x`` to this shard's device (identity for the
        single-engine service).  Pure placement — values are untouched."""
        if self._device is None or x is None:
            return x
        return jax.device_put(x, self._device)

    def job_index(self, job) -> int:
        for k, j in enumerate(self.jobs):
            if job is j:
                return k
        raise ValueError(
            f"job {job.name!r} is not registered with this service; pass "
            "every JobTable at construction (the segment program stacks "
            "their tables once)")

    def prepare(self, tickets) -> None:
        """Replay bootstraps for newly staged tickets (Alg. 1 lines 6-8 via
        ``_init_run_states``, batched) and pin their per-run rows host-side.
        Idempotent per ticket — a ticket returned to the backlog keeps its
        rows."""
        fresh = [t for t in tickets if t.rows is None]
        if not fresh:
            return
        states = _init_run_states(
            [t.request for t in fresh], self.settings,
            None if self.bucket is None else self.bucket.m)
        budgets = states.pop("budgets")
        states["keys"] = np.asarray(states["keys"])
        fields = _STATE_FIELDS + (_CARRY_TIMEOUT_KEYS
                                  if self.settings.timeout else ())
        for r, t in enumerate(fresh):
            t.rows = {f: np.asarray(states[f][r:r + 1]) for f in fields}
            t.budget = float(budgets[r])
            t.jid = self.job_index(t.request.job)

    def in_flight(self) -> int:
        return sum(t is not None for t in self._slot_tickets)

    # ------------------------------------------------------------------ #
    def _seat(self, staged: list) -> tuple[list, int]:
        """Copy staged runs into idle slots host-side; returns the
        remainder (destined for the device queue) and the seat count."""
        idle = [i for i, t in enumerate(self._slot_tickets) if t is None]
        n = min(len(idle), len(staged))
        if n == 0:
            return staged, 0
        slots, seated = idle[:n], staged[:n]
        sl = jnp.asarray(slots, jnp.int32)
        carry = self._carry
        for f in seated[0].rows:
            name = _CARRY_NAME.get(f, f)
            stack = jnp.asarray(np.concatenate([t.rows[f] for t in seated]))
            carry[name] = carry[name].at[sl].set(stack)
        # A host-seated run banks into its slot's own output row.
        carry["rid"] = carry["rid"].at[sl].set(sl)
        carry["active"] = carry["active"].at[sl].set(True)
        for i, t in zip(slots, seated):
            self._slot_tickets[i] = t
            self._slot_jids[i] = t.jid
            self._recorder.emit("seat", ticket=t.id, slot=int(i),
                                segment=self._segment_seq, via="host",
                                shard=self.shard_id)
            if t._pending_resume:
                self._recorder.emit("resume", ticket=t.id, slot=int(i),
                                    segment=self._segment_seq,
                                    shard=self.shard_id)
        return staged[n:], n

    def _queue_arrays(self, staged: list) -> dict:
        """Materialize staged runs as the fixed-shape [C, ...] device queue
        (zero-padded; padding rows sit beyond qtail and are never read)."""
        c, m = self.c_dim, self.m_dim
        pad = {"keys": ((c, 2), np.uint32), "y": ((c, m), np.float32),
               "mask": ((c, m), bool), "beta": ((c,), np.float32),
               "explored": ((c, m), np.int32), "n_exp": ((c,), np.int32),
               "cens": ((c, m), bool), "cexpl": ((c, m), bool),
               "bexpl": ((c, m), np.float32)}
        fields = _STATE_FIELDS + (_CARRY_TIMEOUT_KEYS
                                  if self.settings.timeout else ())
        queue = {}
        for f in fields:
            shape, dtype = pad[f]
            buf = np.zeros(shape, dtype)
            if staged:
                buf[:len(staged)] = np.concatenate([t.rows[f]
                                                    for t in staged])
            queue[f] = self._place(jnp.asarray(buf))
        return queue

    def segment_args(self, staged: list = (), evict=None,
                     low_water: int = 0, step_quota: int = 1) -> tuple:
        """The positional arguments of this engine's next
        ``_episode_segment`` dispatch (settings last): the resident carry
        and tables plus ``staged`` as the device queue.  With the defaults
        it is an empty segment's — what ``_episode_segment.lower(*args)``
        needs to compile the engine's program ahead of time."""
        evict = np.zeros(self.l_dim, bool) if evict is None else evict
        if self._single:
            job_ids = None
        else:
            job_ids = self._place(jnp.asarray(np.concatenate(
                [self._slot_jids,
                 np.array([t.jid for t in staged], np.int32),
                 np.zeros(self.c_dim - len(staged), np.int32)])))
        return (self._carry, self._queue_arrays(list(staged)),
                np.int32(len(staged)), self._place(jnp.asarray(evict)),
                np.int32(low_water), np.int32(step_quota), job_ids,
                self._cost,
                self._runtime if self.settings.timeout else None,
                *self._space, self._valid, self._u, self._tmax,
                self.settings)

    def run_segment(self, staged: list, evict_tickets: list,
                    low_water: int, step_quota: int
                    ) -> tuple[list, list, list, list, SegmentReport]:
        """One seat/inject/dispatch/harvest cycle.

        ``staged`` must hold at most ``queue_capacity + idle slots``
        prepared tickets, in admission (priority) order; ``evict_tickets``
        names seated tickets whose slot must bank partial state and free at
        this boundary (cancellation or preemption — the traced evict flag
        means neither recompiles the segment).  Cancel-requested staged
        tickets are filtered out *here*, at seating time, which closes the
        cancel-between-stage-and-seat race: a tombstoned ticket can never
        reach a slot.  Returns ``(resolved, leftover, dropped, evicted,
        report)``: finished ``(ticket, Outcome)`` pairs, the staged tickets
        that neither seated nor started (back to the broker's backlog), the
        cancel-requested staged tickets that were dropped pre-seat, the
        ``(ticket, rows, partial_outcome)`` triples for evicted seats
        (``rows`` is the banked slot carry — reseating it resumes the run
        bit-identically), and the segment facts.
        """
        dropped = [t for t in staged if t._cancel_requested]
        staged = [t for t in staged if not t._cancel_requested]
        self.prepare(staged)
        rec, seg, prof = self._recorder, self._segment_seq, self._profiler
        t0 = time.perf_counter()
        with phase_span(rec, "seat", segment=seg, profiler=prof,
                        shard=self.shard_id):
            staged_q, seated = self._seat(staged)
        if len(staged_q) > self.c_dim:
            raise ValueError(f"staged {len(staged_q)} queue rows but device "
                             f"capacity is {self.c_dim}")
        if not staged_q and self.in_flight() == 0:
            return [], [], dropped, [], SegmentReport(
                0, 0, self.l_dim, 0.0, seated, 0, 0, 0, 0,
                dropped=len(dropped))

        # Evict mask + pre-segment carry snapshot (the banked state a
        # preempted run resumes from — identical to what the prologue banks
        # into the out rows, read host-side for the resumable request).
        ev = np.zeros(self.l_dim, bool)
        for t in evict_tickets:
            for i, held in enumerate(self._slot_tickets):
                if held is t:
                    ev[i] = True
        ev_slots = np.nonzero(ev)[0]
        ev_rows: dict[int, dict] = {}
        if len(ev_slots):
            fields = _STATE_FIELDS + (_CARRY_TIMEOUT_KEYS
                                      if self.settings.timeout else ())
            host = {f: np.asarray(self._carry[_CARRY_NAME.get(f, f)])
                    for f in fields}
            for i in ev_slots:
                ev_rows[int(i)] = {f: host[f][i:i + 1].copy()
                                   for f in fields}

        with phase_span(rec, "inject", segment=seg, profiler=prof,
                        shard=self.shard_id):
            args = self.segment_args(staged_q, ev, low_water, step_quota)
            for j, t in enumerate(staged_q):
                rec.emit("inject", ticket=t.id, segment=seg, row=j,
                         shard=self.shard_id)
        # dispatch = host-side trace/compile + launch; device_block = the
        # wait for the device to finish.  Splitting them is what lets the
        # report tell compile stalls from slow segments.
        with phase_span(rec, "dispatch", segment=seg, profiler=prof,
                        compiles=True, shard=self.shard_id):
            carry, report = _episode_segment(*args)
        with phase_span(rec, "device_block", segment=seg, profiler=prof,
                        shard=self.shard_id):
            carry, report = jax.block_until_ready((carry, report))
        wall = time.perf_counter() - t0
        report = {k: np.asarray(v) for k, v in report.items()}

        steps = int(report["steps"])
        self._wall += wall
        self._steps += steps
        sel_s = self._wall / max(self._steps * self.l_dim, 1)

        # Harvest banked runs: out row i < L is the run seated in slot i at
        # segment start, row L + j the run injected as queue row j.
        with phase_span(rec, "harvest", segment=seg, profiler=prof,
                        shard=self.shard_id):
            done = np.asarray(report["out_done"])
            rid = np.asarray(carry["rid"])
            active = np.asarray(carry["active"])
            consumed = int(carry["qhead"])
            # Queue rows the device consumed became seats mid-segment; the
            # host only learns it here, so the seat (and any resume) event
            # lands at harvest time — still before the row's harvest event.
            for t in staged_q[:consumed]:
                rec.emit("seat", ticket=t.id, segment=seg, via="queue",
                         shard=self.shard_id)
                if t._pending_resume:
                    rec.emit("resume", ticket=t.id, segment=seg,
                             shard=self.shard_id)
            row_ticket = dict(enumerate(self._slot_tickets))
            for j, t in enumerate(staged_q):
                row_ticket[self.l_dim + j] = t
            resolved = []
            for r in np.nonzero(done)[0]:
                t = row_ticket[int(r)]
                resolved.append((t, self._outcome_from_row(t, report, int(r),
                                                           sel_s)))
                rec.emit("harvest", ticket=t.id, segment=seg, row=int(r),
                         nex=int(report["out_nexp"][r]),
                         shard=self.shard_id)

            # Evicted seats banked into their own out row (rid == slot at
            # segment start; out_done stays False there, so the loop above
            # never double-harvests them).
            evicted = []
            for i in ev_slots:
                t = row_ticket[int(i)]
                evicted.append((t, ev_rows[int(i)],
                                self._outcome_from_row(t, report, int(i),
                                                       sel_s)))
                rec.emit("evict", ticket=t.id, slot=int(i), segment=seg,
                         cancel=bool(t._cancel_requested),
                         shard=self.shard_id)

            # Re-key in-flight runs to their seat and recycle queue rows.
            tickets = [row_ticket[int(rid[i])] if active[i] else None
                       for i in range(self.l_dim)]
            self._slot_tickets = tickets
            self._slot_jids = np.array([t.jid if t else 0 for t in tickets],
                                       np.int32)
            carry["rid"] = self._place(
                jnp.where(jnp.asarray(active),
                          jnp.arange(self.l_dim, dtype=jnp.int32),
                          jnp.int32(-1)))
            carry["qhead"] = self._place(jnp.int32(0))
            self._carry = carry

        leftover = staged_q[consumed:]
        started = staged[:seated] + staged_q[:consumed]
        resumed = 0
        for t in started:
            if t._pending_resume:
                t._pending_resume = False
                resumed += 1
        rep = SegmentReport(
            steps=steps, busy_slot_steps=int(report["busy"]),
            lane_slots=self.l_dim, wall_seconds=wall, seated=seated,
            injected=len(staged_q), consumed=consumed,
            completed=len(resolved), in_flight=self.in_flight(),
            evicted=len(evicted), resumed=resumed, dropped=len(dropped))
        rec.emit("dispatch", segment=seg, steps=steps,
                 busy=int(report["busy"]), seated=seated,
                 injected=len(staged_q), consumed=consumed,
                 completed=len(resolved), evicted=len(evicted),
                 in_flight=rep.in_flight, wall_s=wall,
                 shard=self.shard_id)
        self._segment_seq += 1
        return resolved, leftover, dropped, evicted, rep

    def partial_outcome(self, t) -> Outcome | None:
        """Partial :class:`Outcome` from a ticket's banked carry rows —
        what a cancelled-while-pending ticket that previously ran (was
        preempted) has already paid for.  None when the ticket never held
        a seat (its rows are the untouched bootstrap replay)."""
        if t.rows is None or t.preemptions == 0:
            return None
        n = int(t.rows["n_exp"][0])
        explored = [int(i) for i in t.rows["explored"][0, :n]]
        if self.settings.timeout:
            cflags = [bool(f) for f in t.rows["cexpl"][0, :n]]
            billed = np.asarray(t.rows["bexpl"][0, :n])
        else:
            cflags = [False] * len(explored)
            billed = t.request.job.host_view().cost[explored]
        sel_s = self._wall / max(self._steps * self.l_dim, 1)
        return _reconstruct_outcome(t.request.job, self.settings, t.budget,
                                    explored, cflags, billed,
                                    np.float32(t.rows["beta"][0]), sel_s)

    def _outcome_from_row(self, t, report, r: int, sel_s: float) -> Outcome:
        with phase_span(self._recorder, "outcome", segment=self._segment_seq,
                        profiler=self._profiler, shard=self.shard_id):
            n = int(report["out_nexp"][r])
            explored = [int(i)
                        for i in np.asarray(report["out_expl"][r, :n])]
            if self.settings.timeout:
                cflags = [bool(f)
                          for f in np.asarray(report["out_cexpl"][r, :n])]
                billed = np.asarray(report["out_bexpl"][r, :n])
            else:
                cflags = [False] * len(explored)
                billed = t.request.job.host_view().cost[explored]
            # beta stays an np.float32 scalar: _reconstruct_outcome's
            # ``budget - beta_final`` must run under the same f32 promotion
            # the sequential oracle's bookkeeping uses.
            return _reconstruct_outcome(t.request.job, self.settings,
                                        t.budget, explored, cflags, billed,
                                        report["out_beta"][r], sel_s)


class ShardedEngine:
    """Facade over one :class:`SegmentEngine` per shard (engine-per-device,
    the JetStream/MaxText serving pattern).

    ``config.num_shards`` engines share one job fleet, one ``settings``
    policy program and one flight recorder; each owns its *own* resident
    slot carry, device queue and table copies, committed to
    ``jax.devices()[shard % n]`` via ``service/placement.py`` shardings.
    ``num_shards=1`` degenerates to a single engine with uncommitted
    arrays — byte-identical to the pre-sharding service.

    The broker routes every ticket to exactly one shard (sticky — see
    ``placement.choose_shard``) and pumps each engine separately; this
    facade only fans harvest-side queries in: aggregate ``in_flight`` and
    home-shard ``partial_outcome`` lookups.  Every per-shard event the
    engines emit carries its ``shard`` id, so one merged trace stays
    attributable (``repro.obs.validate_lifecycle`` rejects cross-shard
    ticket streams).
    """

    def __init__(self, jobs, settings, config: ServiceConfig,
                 recorder: FlightRecorder | None = None):
        from repro.service.placement import shard_shardings
        n = config.num_shards
        devices = shard_shardings(n) if n > 1 else [None]
        self.shards = [SegmentEngine(jobs, settings, config,
                                     recorder=recorder, shard_id=d,
                                     device=devices[d])
                       for d in range(n)]

    @property
    def num_shards(self) -> int:
        return len(self.shards)

    @property
    def bucket(self):
        return self.shards[0].bucket

    def job_index(self, job) -> int:
        return self.shards[0].job_index(job)

    def in_flight(self) -> int:
        """Aggregate seated runs across every shard."""
        return sum(e.in_flight() for e in self.shards)

    def home(self, ticket) -> SegmentEngine:
        """The engine holding ``ticket``'s state (shard 0 before any
        placement — sticky affinity makes this stable for life)."""
        shard = getattr(ticket, "shard", None)
        return self.shards[0 if shard is None else shard]

    def partial_outcome(self, ticket):
        """Home-shard partial-Outcome lookup (harvest fan-in: the banked
        carry rows of a preempted run live only in its home engine)."""
        return self.home(ticket).partial_outcome(ticket)
