"""Async session API: ticketed multi-session serving with cross-session
batch packing (the FASTER lineage's session idea, tensorized).

Every other entry point in the repo is synchronous: one batch routes,
fans out, completes, and only then does the next enter — so a hot
shard's deferral rounds (real serialized dispatches) stall every caller
while the other shards' slabs run half-empty.  The source paper's FASTER
C# API solves this with *sessions*: callers enqueue operations and
collect completions out of order, and the store packs work from many
sessions into every internal round.  This module is that layer on top of
`ShardedKV`/`ReplicatedKV`.

Pool
----
Pending ops live in `SessionPool`: N fixed-capacity per-session rings
stored as ONE stacked pytree (the hierarchical named-tensor idiom —
stack heterogeneous per-session state on a leading axis and mask), with
per-session `head`/`tail` cursors and a per-slot lane state
(FREE -> PENDING -> DONE -> FREE).  Enqueue, completion scatter and slot
collection are all jitted scatters on that one structure.

The host keeps a mirror of the pool's completion state (`slot_state`,
`status`, `rvals`).  Only the completion scatter in `step()` changes it in
a way the host cannot know; enqueue (-> PENDING) and collection (-> FREE)
write through to the mirror.  So polls and drains fetch the pool at most
once per round, whichever session asks first, and every other poll reads
the mirror without touching the device.

Scheduler
---------
`step()` runs one routed round: the jitted packer
(`shard_router.pack_from_pool`) selects at most `lanes` pending ops per
*shard* (not per session) in global-ticket order, closed under
per-session FIFO prefixes, and lays them out in one batch that routes
with ZERO deferral — the slab slots a hot shard's deferral would leave
empty in the synchronous path are filled with other sessions' work
instead.  The batch executes through the store's `apply_round` (the
single-round entry the synchronous `apply` is itself built on, so the
pressure scheduler and rebalancer run exactly as they do for
synchronous batches), and completions scatter back into the pool.

Tickets and ordering
--------------------
`Session.enqueue` returns one monotonically increasing global ticket
per op; `poll(tickets)` collects whichever completions are ready,
`drain()` pumps the service until the session is empty.  Completions
surface out of order *across* sessions, but every session's ops are
packed — and therefore applied — in its FIFO enqueue order, and each
round's batch is emitted in ascending ticket order, so the realized
history is the round sequence with the store's documented batch
semantics (writes linearize in ticket order; reads observe the
round-entry snapshot — the same per-batch contract synchronous callers
get).  tests/test_sessions.py proves this bit-exactly: statuses, values
and state leaves of any enqueue/step/poll interleaving match a twin
store replaying the recorded round batches, and the client-visible
results match a dict model folded in ticket order.  Global-ticket
arbitration also gives the liveness bound: the oldest pending op in the
pool is packed every round, so no op — and no session — can starve.
"""
from __future__ import annotations

import functools
import time
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs

from ..core import shard_router
from ..core.types import (OP_DELETE, OP_NOOP, OP_READ, OP_RMW, OP_UPSERT,
                          ST_NONE)

SLOT_FREE, SLOT_PENDING, SLOT_DONE = 0, 1, 2


class SessionPool(NamedTuple):
    """All sessions' pending-op rings as one stacked pytree: N sessions x
    C slots, slot = (cursor mod C).  `head`/`tail` are monotone int32
    counters — [head, tail) is the in-use window; `slot_state` tracks
    each slot's lifecycle so the packer (PENDING mask), the completion
    scatter (-> DONE) and collection (-> FREE, head advance) compose as
    pure pytree -> pytree steps."""

    keys: jax.Array        # int32 [N, C]
    ops: jax.Array         # int32 [N, C]
    vals: jax.Array        # int32 [N, C, V]
    ticket: jax.Array      # int32 [N, C] global enqueue sequence number
    slot_state: jax.Array  # int32 [N, C] FREE / PENDING / DONE
    status: jax.Array      # int32 [N, C] completion status
    rvals: jax.Array       # int32 [N, C, V] completion values
    head: jax.Array        # int32 [N] collect cursor (monotone)
    tail: jax.Array        # int32 [N] enqueue cursor (monotone)


def create_pool(n_sessions: int, depth: int, value_width: int) -> SessionPool:
    N, C, V = n_sessions, depth, value_width
    z = functools.partial(jnp.zeros, dtype=jnp.int32)
    return SessionPool(
        keys=z((N, C)), ops=jnp.full((N, C), OP_NOOP, jnp.int32),
        vals=z((N, C, V)), ticket=z((N, C)), slot_state=z((N, C)),
        status=z((N, C)), rvals=z((N, C, V)), head=z((N,)), tail=z((N,)))


# -- jitted pool kernels (pure pytree -> pytree) -----------------------------

def _enqueue_kernel(pool: SessionPool, sid, keys, ops, vals, t0, n_acc):
    """Claim the next `n_acc` ring slots of session `sid` (host enforces
    capacity) and stamp them PENDING with tickets t0, t0+1, ...; lanes
    past n_acc are rejected (dropped).  Returns only the pool: the
    ticket values are deterministic on the host (t0 + lane, -1 past
    n_acc), so enqueue never has to round-trip to the device — the
    serving loop stays fully async-dispatched."""
    N, C = pool.keys.shape
    B = keys.shape[0]
    idx = jnp.arange(B, dtype=jnp.int32)
    ok = idx < n_acc
    row = jnp.where(ok, sid, jnp.int32(N))          # OOB row -> dropped
    col = jnp.where(ok, (pool.tail[sid] + idx) % C, 0)
    return pool._replace(
        keys=pool.keys.at[row, col].set(keys, mode="drop"),
        ops=pool.ops.at[row, col].set(ops, mode="drop"),
        vals=pool.vals.at[row, col].set(vals, mode="drop"),
        ticket=pool.ticket.at[row, col].set(t0 + idx, mode="drop"),
        slot_state=pool.slot_state.at[row, col].set(
            jnp.int32(SLOT_PENDING), mode="drop"),
        tail=pool.tail.at[sid].add(n_acc),
    )


def _commit_kernel(pool: SessionPool, sess, slot, valid, status, rvals):
    """Scatter one round's completions back into the pool: results land
    at (sess, slot) and those slots flip PENDING -> DONE."""
    N = pool.keys.shape[0]
    row = jnp.where(valid, sess, jnp.int32(N))
    col = jnp.where(valid, slot, 0)
    return pool._replace(
        status=pool.status.at[row, col].set(status, mode="drop"),
        rvals=pool.rvals.at[row, col].set(rvals, mode="drop"),
        slot_state=pool.slot_state.at[row, col].set(
            jnp.int32(SLOT_DONE), mode="drop"))


def _free_kernel(pool: SessionPool, sid, mask):
    """Collection: free the masked slots of session `sid` (mask bool [C],
    ring-indexed) and advance `head` over the contiguous FREE prefix of
    the in-use window — freed mid-window slots stay counted against
    capacity until everything older is collected (ring semantics)."""
    C = pool.keys.shape[1]
    state_row = jnp.where(mask, jnp.int32(SLOT_FREE), pool.slot_state[sid])
    idx = (pool.head[sid] + jnp.arange(C, dtype=jnp.int32)) % C
    used = jnp.arange(C, dtype=jnp.int32) < (pool.tail[sid] - pool.head[sid])
    run = jnp.cumprod(jnp.where(
        used, (state_row[idx] == SLOT_FREE).astype(jnp.int32), 0))
    return pool._replace(
        slot_state=pool.slot_state.at[sid].set(state_row),
        head=pool.head.at[sid].add(run.sum()))


class Session:
    """A caller's handle: enqueue ops, collect completions by ticket.
    One session's ops execute in FIFO order; different sessions' ops
    interleave freely inside the service's packed rounds.  Not
    thread-safe (like a FASTER session: one owner per session)."""

    def __init__(self, svc: "KVSessionService", sid: int):
        self._svc = svc
        self.sid = sid
        self.open = True
        self._head = 0                  # host mirrors of the device cursors
        self._tail = 0
        # outstanding tickets in enqueue order (ascending) and their ring
        # cursors (slot = cursor mod C)
        self._tk = np.zeros(0, np.int64)
        self._cur = np.zeros(0, np.int64)

    @property
    def capacity(self) -> int:
        return self._svc.depth

    @property
    def in_use(self) -> int:
        return self._tail - self._head

    @property
    def outstanding(self) -> int:
        """Ops enqueued and not yet collected (pending or done)."""
        return len(self._tk)

    def outstanding_tickets(self) -> np.ndarray:
        """The tickets enqueued and not yet collected, in enqueue order."""
        return self._tk.copy()

    def enqueue(self, keys, ops, vals=None) -> np.ndarray:
        """Submit a batch; returns one int32 ticket per lane, -1 for
        lanes that did not fit the ring (retry after poll/drain frees
        slots).  Tickets are the service-wide enqueue order — the
        scheduler's arbitration key."""
        assert self.open, "session is closed"
        return self._svc._enqueue(self, keys, ops, vals)

    def poll(self, tickets: Sequence[int]):
        """Non-blocking collection: returns (done [k] bool, status [k],
        vals [k, V]) aligned with `tickets`.  Completed tickets are
        collected exactly once — their slots free up for new enqueues;
        polling them again (or polling a rejected ticket -1) reads
        done=False."""
        assert self.open, "session is closed"
        return self._svc._poll(self, np.asarray(tickets, np.int64))

    def drain(self):
        """Pump the service until every outstanding op of THIS session
        completed, then collect them all.  Returns (tickets [m],
        status [m], vals [m, V]) in enqueue (FIFO) order."""
        assert self.open, "session is closed"
        return self._svc._drain(self)

    def close(self):
        self._svc.close_session(self)


class KVSessionService:
    """Ticketed multi-session serving over a sharded/replicated store.

    `open_session()` hands out up to `max_sessions` concurrent handles,
    each with a `depth`-slot ring in the shared `SessionPool`.  `step()`
    executes one cross-session packed round through the store's
    `apply_round`; `poll`/`drain` on the sessions pump it implicitly.
    The synchronous `KVProtocol` surface (apply/read/upsert/rmw/delete)
    is provided through a private session, so anything written against
    the protocol — benches, demos, conformance tests — runs unchanged on
    the async service."""

    _obs_facade = "sessions"

    def __init__(self, kv, max_sessions: int = 8, session_depth: int = 64,
                 pack_lanes: Optional[int] = None):
        assert hasattr(kv, "apply_round"), \
            "KVSessionService needs a routed store (ShardedKV/ReplicatedKV)"
        assert max_sessions >= 1 and session_depth >= 1
        self.kv = kv
        self.N = int(max_sessions)
        self.depth = int(session_depth)
        self.W = int(pack_lanes or kv.lanes or session_depth)
        assert kv.lanes is None or self.W <= kv.lanes, \
            "pack_lanes wider than the store's slab would defer rounds"
        self.V = kv.cfg.value_width
        self.pool = create_pool(self.N, self.depth, self.V)
        self._sessions: list = [None] * self.N
        self._sync: Optional[Session] = None    # lazy protocol-facade session
        self._next_ticket = 0
        self.tickets_issued = 0
        self.tickets_rejected = 0
        self.collected = 0
        self.pack_rounds = 0
        self.sessions_opened = 0
        self.pool_fetches = 0                   # device->host pool fetches
        self.polls = 0
        # host mirror of (slot_state, status, rvals); the pool starts all
        # zeros (every slot FREE), so the mirror starts fresh
        self._mirror = (np.zeros((self.N, self.depth), np.int32),
                        np.zeros((self.N, self.depth), np.int32),
                        np.zeros((self.N, self.depth, self.V), np.int32))
        self._mirror_stale = False
        self._pending_fill: list = []           # unfolded per-round fill [S]
        self._packed_lanes = 0                  # folded totals
        self._fill_rounds = 0
        self._fill_sum = np.zeros(kv.S, np.int64)
        self.trace_schedule = False             # test hook: record rounds
        self.schedule: list = []    # [(sess, valid, bkeys, bops, bvals,
        #                              status, rvals, ticket)] per round
        # ticket lifecycle stamps (enqueue -> packed -> applied ->
        # collected); round gathers queue device-side and fold with the
        # fill queue, so the armed path adds no hot-path sync either
        self._clock = obs.latency.TicketClock(fetch=jax.device_get)

        S, W = kv.S, self.W

        def pack(pool, bmap):
            return shard_router.pack_from_pool(
                pool.keys, pool.ops, pool.vals, pool.ticket,
                pool.slot_state == SLOT_PENDING, S, W, bmap)

        self._pack_j = jax.jit(pack)
        self._enqueue_j = jax.jit(_enqueue_kernel)
        self._commit_j = jax.jit(_commit_kernel)
        self._free_j = jax.jit(_free_kernel)

        def round_tickets(pool, sess, slot, valid):
            return jnp.where(valid, pool.ticket[jnp.maximum(sess, 0),
                                                jnp.maximum(slot, 0)],
                             jnp.int32(-1))

        # one fused dispatch instead of four eager ones per armed round
        self._round_tickets_j = jax.jit(round_tickets)

    # -- session lifecycle ----------------------------------------------------
    def open_session(self) -> Session:
        for sid in range(self.N):
            if self._sessions[sid] is None:
                s = Session(self, sid)
                # continue the ring cursors where the previous owner of
                # this sid left them (slots are FREE, cursors monotone)
                prev = jax.device_get((self.pool.head[sid],
                                       self.pool.tail[sid]))
                s._head, s._tail = int(prev[0]), int(prev[1])
                assert s._head == s._tail, "reused sid has in-use slots"
                self._sessions[sid] = s
                self.sessions_opened += 1
                obs.journal.emit("session.opened", sid=sid)
                obs.count("f2_sessions_opened_total",
                          facade=self._obs_facade)
                return s
        raise RuntimeError(f"all {self.N} sessions are open")

    def close_session(self, session: Session):
        assert session.outstanding == 0, \
            "close_session with outstanding ops: drain() first"
        self._sessions[session.sid] = None
        session.open = False
        obs.journal.emit("session.closed", sid=session.sid)

    # -- the scheduler round --------------------------------------------------
    def total_outstanding(self) -> int:
        return sum(s.outstanding for s in self._sessions if s is not None)

    def step(self, sync: bool = False):
        """One cross-session packed round: pack -> apply_round -> commit
        -> per-batch rebalance check.  With `sync=False` (the serving hot
        path) nothing round-trips to the host; `sync=True` returns the
        number of lanes packed (0 = the pool had nothing pending)."""
        with obs.span("sessions.step", cat="serve", round=self.pack_rounds):
            armed = obs.enabled()
            t_pack0 = time.perf_counter() if armed else 0.0
            (bkeys, bops, bvals, sess, slot, valid,
             fill) = self._pack_j(self.pool, self.kv._bucket_map_dev)
            t_pack1 = time.perf_counter() if armed else 0.0
            status, rvals, placed, _deferred = self.kv.apply_round(
                bkeys, bops, bvals)
            # by construction the packer never exceeds a shard's slab
            # width, so nothing defers; `placed` still gates the commit so
            # an (impossible) unexecuted lane could never read a stale
            # result
            self.pool = self._commit_j(self.pool, sess, slot,
                                       valid & placed, status, rvals)
            # the round's DONE marks exist only on the device: queue their
            # transfer behind the commit for the next poll's fetch
            self._mirror_stale = True
            for a in (self.pool.slot_state, self.pool.status,
                      self.pool.rvals):
                a.copy_to_host_async()
            t_applied = time.perf_counter() if armed else 0.0
            self.kv.maybe_rebalance()
            # durability hook: a DurableKV backing store snapshots on its
            # configured cadence at packed-round boundaries (between rounds
            # the pool rings hold every un-acked op, so the snapshot is
            # consistent)
            snap = getattr(self.kv, "maybe_snapshot", None)
            if snap is not None:
                snap()
            self.pack_rounds += 1
            self._pending_fill.append(fill)
            if armed or self.trace_schedule:
                tkt = self._round_tickets_j(self.pool, sess, slot, valid)
                if armed:       # queued device-side; folded with the fills
                    self._clock.note_round(tkt, t_pack0, t_pack1, t_applied)
                if self.trace_schedule:
                    self.schedule.append((sess, valid, bkeys, bops, bvals,
                                          status, rvals, tkt))
            if len(self._pending_fill) >= 128:
                self._fold_fill()
            if sync:
                return int(np.asarray(jax.device_get(fill)).sum())
            return None

    def run_until_idle(self, max_rounds: Optional[int] = None) -> int:
        """Pump packed rounds until the pool is empty of PENDING ops.
        Returns rounds executed.  Bounded: global-FIFO packing completes
        >= 1 op per round whenever anything is pending."""
        limit = max_rounds if max_rounds is not None else \
            self.total_outstanding() + self.N + 2
        rounds = 0
        for _ in range(limit):
            if not self._any_pending():
                return rounds
            self.step()
            rounds += 1
        if self._any_pending():
            raise RuntimeError(
                f"session scheduler made no progress in {limit} rounds")
        return rounds

    def _any_pending(self) -> bool:
        return bool(jax.device_get(
            (self.pool.slot_state == SLOT_PENDING).any()))

    # -- internals driven by the Session handles ------------------------------
    def _enqueue(self, s: Session, keys, ops, vals):
        with obs.span("sessions.enqueue", cat="serve", sid=s.sid,
                      ticket=self._next_ticket, n=len(keys)):
            keys = np.asarray(keys, np.int32)
            ops = np.asarray(ops, np.int32)
            if vals is None:
                vals = np.zeros((len(keys), self.V), np.int32)
            else:
                vals = np.asarray(vals, np.int32)
            assert (keys.shape == ops.shape
                    and vals.shape == keys.shape + (self.V,))
            assert (ops != OP_NOOP).all(), \
                "OP_NOOP cannot be enqueued (it would never complete)"
            B = len(keys)
            n_acc = min(B, self.depth - s.in_use)
            t0 = self._next_ticket
            self.pool = self._enqueue_j(
                self.pool, jnp.int32(s.sid), jnp.asarray(keys),
                jnp.asarray(ops), jnp.asarray(vals), jnp.int32(t0),
                jnp.int32(n_acc))
            self._next_ticket += n_acc
            self.tickets_issued += n_acc
            self.tickets_rejected += B - n_acc
            if n_acc:
                if obs.enabled():
                    self._clock.note_enqueue(t0, n_acc, time.perf_counter())
                lane = np.arange(n_acc, dtype=np.int64)
                s._tk = np.concatenate([s._tk, t0 + lane])
                s._cur = np.concatenate([s._cur, s._tail + lane])
                self._mirror[0][s.sid, (s._tail + lane) % self.depth] = \
                    SLOT_PENDING
            s._tail += n_acc
            # tickets are host-deterministic: no device round-trip on enqueue
            idx = np.arange(B, dtype=np.int32)
            return np.where(idx < n_acc, t0 + idx,
                            np.int32(-1)).astype(np.int32)

    def _completion(self):
        """The host mirror (slot_state, status, rvals), fetched from the
        device only if a round has committed since the last fetch."""
        if self._mirror_stale:
            with obs.span("sessions.fetch", cat="serve"):
                self._mirror = tuple(np.array(a) for a in jax.device_get(
                    (self.pool.slot_state, self.pool.status,
                     self.pool.rvals)))
            self._mirror_stale = False
            self.pool_fetches += 1
            obs.count("f2_sessions_pool_fetches_total",
                      help="device->host fetches of the session pool",
                      facade=self._obs_facade)
        return self._mirror

    def _all_done(self, s: Session) -> bool:
        if not len(s._tk):
            return True
        state = self._completion()[0]
        return bool((state[s.sid, s._cur % self.depth] == SLOT_DONE).all())

    def _collect(self, s: Session, pos: np.ndarray):
        """Collect the outstanding ops at positions `pos` of `s._tk`
        (distinct, all DONE in a fresh mirror): gather their results,
        free their slots, advance head.  Returns (tickets, status, vals)
        in the order of `pos`."""
        with obs.span("sessions.collect", cat="serve", n=len(pos)):
            C = self.depth
            state, status, rvals = self._mirror
            tickets = s._tk[pos]
            slot = s._cur[pos] % C
            st, v = status[s.sid, slot], rvals[s.sid, slot]
            state[s.sid, slot] = SLOT_FREE
            mask = np.zeros(C, bool)
            mask[slot] = True
            self.pool = self._free_j(self.pool, jnp.int32(s.sid),
                                     jnp.asarray(mask))
            # _free_kernel's rule: head advances over the contiguous FREE
            # prefix of the in-use window (the mirror's FREE marks are
            # exact even when it is stale: the device frees nothing itself)
            free = state[s.sid, (s._head + np.arange(s.in_use)) % C] \
                == SLOT_FREE
            s._head += len(free) if free.all() else int(np.argmin(free))
            keep = np.ones(len(s._tk), bool)
            keep[pos] = False
            s._tk, s._cur = s._tk[keep], s._cur[keep]
            self.collected += len(pos)
            if obs.enabled():
                self._clock.note_collected(tickets, time.perf_counter())
            return tickets, st, v

    def _poll(self, s: Session, tickets: np.ndarray):
        with obs.span("sessions.poll", cat="serve", sid=s.sid,
                      n=len(tickets)):
            self.polls += 1
            k = len(tickets)
            done = np.zeros(k, bool)
            out_st = np.full(k, ST_NONE, np.int32)
            out_v = np.zeros((k, self.V), np.int32)
            n = len(s._tk)
            if not (n and k):
                return done, out_st, out_v
            state = self._completion()[0]
            # tickets rise within a session: unknown, rejected (-1) and
            # collected tickets miss the sorted outstanding array
            pos = np.minimum(np.searchsorted(s._tk, tickets), n - 1)
            hit = (s._tk[pos] == tickets) & (
                state[s.sid, s._cur[pos] % self.depth] == SLOT_DONE)
            cand = np.flatnonzero(hit)
            if len(cand):
                # a ticket listed twice is answered at its first listing
                pos, first = np.unique(pos[cand], return_index=True)
                sel = cand[first]
                done[sel] = True
                _, out_st[sel], out_v[sel] = self._collect(s, pos)
            return done, out_st, out_v

    def _drain(self, s: Session):
        limit = self.total_outstanding() + self.N + 2
        for _ in range(limit):
            if self._all_done(s):
                break
            self.step()
        else:
            raise RuntimeError("drain made no progress")
        if not len(s._tk):
            return (np.zeros(0, np.int64), np.zeros(0, np.int32),
                    np.zeros((0, self.V), np.int32))
        return self._collect(s, np.arange(len(s._tk)))

    # -- slab-occupancy telemetry (the bench's before/after signal) ----------
    def _fold_fill(self):
        if not self._pending_fill:
            return
        with obs.span("sessions.fold", cat="serve",
                      rounds=len(self._pending_fill)):
            pending = jax.device_get(self._pending_fill)
            self._pending_fill = []
            for f in pending:
                f = np.asarray(f).astype(np.int64)
                self._fill_sum += f
                self._packed_lanes += int(f.sum())
                self._fill_rounds += 1
            if obs.enabled():       # mirror the folded packing signal
                denom = self._fill_rounds * self.kv.S * self.W
                obs.gauge_set("f2_slab_occupancy",
                              self._packed_lanes / denom if denom else 0.0,
                              help="mean fraction of slab lanes filled per "
                                   "packed round",
                              facade=self._obs_facade)
                obs.count_total("f2_packed_lanes_total", self._packed_lanes,
                                help="lanes packed into routed rounds",
                                facade=self._obs_facade)
                self._clock.fold()          # queued ticket rounds ride along
                obs.rules.maybe_evaluate()  # alert pass at the fold point

    @property
    def packed_lanes(self) -> int:
        self._fold_fill()
        return self._packed_lanes

    def slab_occupancy(self) -> float:
        """Mean fraction of the S*W slab lanes filled per packed round —
        the quantity deferral leaves low in the synchronous path and
        cross-session packing is meant to raise."""
        self._fold_fill()
        if not self._fill_rounds:
            return 0.0
        return self._packed_lanes / (self._fill_rounds * self.kv.S * self.W)

    # -- KVProtocol surface (synchronous facade over the async path) ---------
    def _sync_session(self) -> Session:
        if self._sync is None or not self._sync.open:
            self._sync = self.open_session()
        return self._sync

    def apply(self, keys, ops, vals=None):
        """Synchronous mixed batch through the session machinery: enqueue
        on a private session (chunked to its ring capacity), drain, and
        return per-lane (status, vals) in the original batch order."""
        s = self._sync_session()
        keys = np.asarray(keys, np.int32)
        ops = np.asarray(ops, np.int32)
        if vals is None:
            vals = np.zeros((len(keys), self.V), np.int32)
        else:
            vals = np.asarray(vals, np.int32)
        B = len(keys)
        status = np.zeros(B, np.int32)
        rvals = np.zeros((B, self.V), np.int32)
        lane_of = ([], [])                      # (tickets, lanes) per chunk
        start = 0
        while start < B:
            live = ops[start:] != OP_NOOP       # NOOP lanes complete as
            if not live.any():                  # ST_NONE without enqueue
                break
            nxt = start + int(np.argmax(live))
            n = min(B - nxt, self.depth - s.in_use)
            if n <= 0:
                self._drain_into(s, lane_of, status, rvals)
                continue
            chunk = slice(nxt, nxt + n)
            sel = ops[chunk] != OP_NOOP
            if not sel.all():
                n = int(np.argmin(sel))         # stop chunk at first NOOP
                chunk = slice(nxt, nxt + n)
            lane_of[0].append(s.enqueue(keys[chunk], ops[chunk],
                                        vals[chunk]))
            lane_of[1].append(np.arange(nxt, nxt + n))
            start = nxt + n
        self._drain_into(s, lane_of, status, rvals)
        return jnp.asarray(status), jnp.asarray(rvals)

    def _drain_into(self, s, lane_of, status, rvals):
        """Drain the private session; its outstanding ops are exactly the
        chunks in `lane_of`, in ticket order."""
        tk, st, v = s.drain()
        if not len(tk):
            return
        assert np.array_equal(np.concatenate(lane_of[0]), tk), \
            "sync session drained tickets it did not enqueue"
        lane = np.concatenate(lane_of[1])
        status[lane] = st
        rvals[lane] = v
        lane_of[0].clear()
        lane_of[1].clear()

    def read(self, keys):
        ops = np.full(len(keys), OP_READ, np.int32)
        return self.apply(keys, ops)

    def upsert(self, keys, vals):
        ops = np.full(len(keys), OP_UPSERT, np.int32)
        return self.apply(keys, ops, vals)

    def rmw(self, keys, deltas):
        ops = np.full(len(keys), OP_RMW, np.int32)
        return self.apply(keys, ops, deltas)

    def delete(self, keys):
        ops = np.full(len(keys), OP_DELETE, np.int32)
        return self.apply(keys, ops)

    # -- reporting ------------------------------------------------------------
    def io_stats(self) -> dict:
        return self.kv.io_stats()

    def _stats_tree(self) -> dict:
        """The raw nested telemetry tree; `stats()` folds it through the
        metrics registry (identity when observability is disabled)."""
        out = self.kv._stats_tree()
        self._fold_fill()
        out["sessions"] = dict(
            max_sessions=self.N,
            session_depth=self.depth,
            pack_lanes=self.W,
            open=sum(x is not None for x in self._sessions),
            opened=self.sessions_opened,
            tickets_issued=self.tickets_issued,
            tickets_rejected=self.tickets_rejected,
            collected=self.collected,
            outstanding=self.total_outstanding(),
            pack_rounds=self.pack_rounds,
            polls=self.polls,
            pool_fetches=self.pool_fetches,
            packed_lanes=self.packed_lanes,
            slab_occupancy=round(self.slab_occupancy(), 4),
        )
        return out

    def stats(self) -> dict:
        """The nested KVProtocol telemetry shape: the underlying store's
        `io`/`shards`(/`replicas`) sub-dicts plus the `sessions` view.
        With observability enabled, every leaf is mirrored into
        `f2_stats_*` gauges labeled by facade."""
        return obs.fold_stats(self._obs_facade, self._stats_tree())

    def check_invariants(self):
        """Store invariants plus pool/bookkeeping coherence: device
        cursors match the host mirrors, in-use windows fit the rings,
        every PENDING slot belongs to an outstanding ticket, the host
        mirror's FREE marks match the pool's, and a fresh mirror equals
        the pool's completion state."""
        self.kv.check_invariants()
        head, tail, state, status, rvals = map(np.asarray, jax.device_get(
            (self.pool.head, self.pool.tail, self.pool.slot_state,
             self.pool.status, self.pool.rvals)))
        assert np.array_equal(self._mirror[0] == SLOT_FREE,
                              state == SLOT_FREE), "FREE mirror drift"
        if not self._mirror_stale:
            for name, host, dev in zip(("slot_state", "status", "rvals"),
                                       self._mirror, (state, status, rvals)):
                assert np.array_equal(host, dev), (name, "pool mirror drift")
        for sid, s in enumerate(self._sessions):
            if s is None:
                continue
            assert s._head == int(head[sid]), (sid, "head mirror drift")
            assert s._tail == int(tail[sid]), (sid, "tail mirror drift")
            assert 0 <= s.in_use <= self.depth, (sid, "ring overflow")
            n_live = int((state[sid] != SLOT_FREE).sum())
            assert n_live == len(s._tk), (sid, "slot bookkeeping drift")
