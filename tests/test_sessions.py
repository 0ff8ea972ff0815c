"""Differential interleaving oracle for the async session layer
(serve/sessions + core.shard_router.pack_from_pool).

The contract under test: ANY interleaving of session enqueues, scheduler
steps, polls and drains is bit-exact with a deterministic serial
history.  Concretely, the service records every packed round it
executes, and the oracle proves three things:

(a) scheduling — every accepted ticket executes exactly once, each
    session's tickets execute in FIFO enqueue order, rounds emit lanes
    in ascending global-ticket order, and no round packs more than
    `lanes` ops per shard;
(b) store parity — a twin ShardedKV replaying the recorded round
    batches (with forced migrations replayed at the recorded
    boundaries) matches the serving store on per-round statuses/values
    and on EVERY state leaf, including schedules where the rounds
    overlap a masked pressure compaction and a forced rebalance;
(c) client parity — the results surfaced through poll()/drain() match
    the recorded rounds per ticket, and a dict model folded in ticket
    order (reads checked against the round-entry snapshot, the store's
    documented batch semantics) explains every read.

Liveness rides along: the globally-oldest pending ticket is packed
every round, and a session's ops complete within a bounded number of
rounds even while another session floods the same shard.

Per project convention, every hypothesis property here has a seeded
fallback that always runs (hypothesis is a CI-only dependency).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import (OP_DELETE, OP_NOOP, OP_READ, OP_RMW, OP_UPSERT,
                        ST_NOT_FOUND, ST_OK, F2Config, shard_router)
from repro.core.sharded import ShardedKV
from repro.serve.sessions import (SLOT_DONE, SLOT_PENDING, KVSessionService)

V = 2


def tiny_cfg(**kw):
    base = dict(hot_index_size=1 << 8, hot_capacity=1 << 9, hot_mem=1 << 6,
                cold_capacity=1 << 11, cold_mem=1 << 6, n_chunks=1 << 6,
                chunklog_capacity=1 << 9, chunklog_mem=1 << 5,
                rc_capacity=1 << 6, value_width=V, chain_max=48)
    base.update(kw)
    return F2Config(**base)


def make_service(S=4, W=8, N=3, C=8, trigger=0.6, **cfg_kw):
    """A traced session service and the kwargs to build its twin store."""
    cfg = tiny_cfg(**cfg_kw)
    store_kw = dict(mode="f2", trigger=trigger, compact_frac=0.3,
                    compact_batch=64, donate=False, lanes=W)
    svc = KVSessionService(ShardedKV(cfg, S, **store_kw),
                           max_sessions=N, session_depth=C)
    svc.trace_schedule = True
    return svc, cfg, store_kw


def mixed_enqueue(rng, n_keys, B):
    """A batch of enqueueable ops (no OP_NOOP — it cannot complete)."""
    keys = rng.integers(0, n_keys, B).astype(np.int32)
    ops = rng.choice([OP_READ, OP_UPSERT, OP_RMW, OP_DELETE], B,
                     p=[.25, .45, .15, .15]).astype(np.int32)
    vals = rng.integers(0, 100, (B, V)).astype(np.int32)
    return keys, ops, vals


def fold_write(ref, k, o, v):
    if o == OP_UPSERT:
        ref[k] = v.copy()
    elif o == OP_DELETE:
        ref.pop(k, None)
    elif o == OP_RMW:
        ref[k] = (ref.get(k, np.zeros(V, np.int32)) + v).astype(np.int32)


def verify_history(svc, cfg, store_kw, S, W, enq_log, results, migrations,
                   tag):
    """The oracle: fold the recorded schedule and prove (a) scheduling,
    (b) twin-store parity including state leaves, (c) client parity
    against the rounds and the dict model.  `migrations` is a list of
    (round_index, new_map) replayed into the twin at the same points."""
    sched = jax.device_get(svc.schedule)
    twin = ShardedKV(cfg, S, **store_kw)
    mig = list(migrations)
    executed = []                       # (ticket, sid, lane status, vals)
    per_session = {}
    ref = {}
    read_checks = 0
    for r, (sess, valid, bkeys, bops, bvals, status, rvals,
            tkt) in enumerate(sched):
        while mig and mig[0][0] == r:
            twin.migrate(mig.pop(0)[1])
        sess, valid, tkt = map(np.asarray, (sess, valid, tkt))
        bkeys, bops, bvals = map(np.asarray, (bkeys, bops, bvals))
        status, rvals = np.asarray(status), np.asarray(rvals)

        # (a) scheduling: ascending tickets, per-shard <= W, FIFO/session
        vt = tkt[valid]
        assert np.all(np.diff(vt) > 0), (tag, r, "tickets not ascending")
        sid = np.asarray(twin.bucket_map[np.asarray(
            shard_router.bucket_of(jnp.asarray(bkeys[valid]),
                                   twin.n_buckets))])
        assert np.bincount(sid, minlength=S).max() <= W, \
            (tag, r, "shard overpacked")
        for t, s in zip(vt, sess[valid]):
            per_session.setdefault(int(s), []).append(int(t))
            executed.append(int(t))

        # (b) twin-store parity: same batch -> same statuses/values/state
        st_t, rv_t, placed, deferred = twin.apply_round(bkeys, bops, bvals)
        twin.maybe_rebalance()
        assert not np.asarray(deferred).any(), (tag, r, "round deferred")
        assert np.array_equal(np.asarray(st_t), status), (tag, r)
        assert np.array_equal(np.asarray(rv_t), rvals), (tag, r)

        # (c) dict model: reads observe the round-entry snapshot, writes
        # fold in ticket order (= lane order: rounds emit ascending)
        for i in np.flatnonzero(valid):
            k, o = int(bkeys[i]), int(bops[i])
            if o == OP_READ:
                read_checks += 1
                if k in ref:
                    assert status[i] == ST_OK, (tag, r, k)
                    assert np.array_equal(rvals[i], ref[k]), (tag, r, k)
                else:
                    assert status[i] == ST_NOT_FOUND, (tag, r, k)
        for i in np.flatnonzero(valid):
            fold_write(ref, int(bkeys[i]), int(bops[i]), bvals[i])

        # client parity: what poll()/drain() surfaced per ticket is what
        # the round computed at that ticket's lane
        for i in np.flatnonzero(valid):
            t = int(tkt[i])
            if t in results:
                got_st, got_v = results[t]
                assert got_st == status[i], (tag, r, t)
                assert np.array_equal(got_v, rvals[i]), (tag, r, t)

    while mig:          # migrations after the last traced round
        twin.migrate(mig.pop(0)[1])

    # every accepted ticket executed exactly once, FIFO per session
    assert sorted(executed) == sorted(enq_log), (tag, "lost/dup tickets")
    assert len(set(executed)) == len(executed), (tag, "double execution")
    for s, ts in per_session.items():
        assert ts == sorted(ts), (tag, s, "session FIFO violated")
    assert read_checks > 0, (tag, "oracle exercised no reads")

    # state leaves bit-exact with the twin replay
    a, b = jax.device_get((svc.kv.state, twin.state))
    for la, lb in zip(jax.tree_util.tree_leaves(a),
                      jax.tree_util.tree_leaves(b)):
        assert np.array_equal(np.asarray(la), np.asarray(lb)), \
            (tag, "state leaves diverged from the twin replay")
    assert np.array_equal(svc.kv.compactions, twin.compactions), tag
    assert np.array_equal(svc.kv.bucket_map, twin.bucket_map), tag
    return ref


def drive(svc, sessions, rng, n_events, n_keys, enq_log, results):
    """Random interleaving of enqueues, steps, polls and drains."""
    for _ in range(n_events):
        act = rng.choice(["enq", "enq", "enq", "step", "poll", "drain"])
        s = sessions[int(rng.integers(0, len(sessions)))]
        if act == "enq":
            keys, ops, vals = mixed_enqueue(rng, n_keys,
                                            int(rng.integers(1, 9)))
            tk = s.enqueue(keys, ops, vals)
            for i, t in enumerate(tk):
                if t >= 0:
                    enq_log[int(t)] = (s.sid, int(keys[i]), int(ops[i]),
                                       vals[i].copy())
        elif act == "step":
            svc.step()
        elif act == "poll" and s.outstanding:
            out = s.outstanding_tickets()
            pick = rng.choice(out, size=min(len(out), 4), replace=False)
            done, st, v = s.poll(pick)
            for i, t in enumerate(pick):
                if done[i]:
                    results[int(t)] = (int(st[i]), np.asarray(v[i]).copy())
        elif act == "drain":
            tk, st, v = s.drain()
            for i, t in enumerate(tk):
                results[int(t)] = (int(st[i]), np.asarray(v[i]).copy())


def finish(svc, sessions, results):
    for s in sessions:
        tk, st, v = s.drain()
        for i, t in enumerate(tk):
            results[int(t)] = (int(st[i]), np.asarray(v[i]).copy())


# ---------------------------------------------------------------------------
# The interleaving oracle (the acceptance criterion)
# ---------------------------------------------------------------------------

def test_session_interleaving_oracle_differential():
    """Three sessions, random enqueue/step/poll/drain interleavings, with
    enough write volume that masked pressure compactions fire INSIDE the
    packed rounds, and a forced rebalance flipped mid-stream while ops
    sit pending in the rings: statuses, values, client results and every
    state leaf bit-exact with the twin replay + dict model."""
    # a small hot log so the session stream's write volume crosses the
    # pressure trigger mid-schedule (masked compaction inside the rounds)
    svc, cfg, store_kw = make_service(S=4, W=8, N=3, C=8, trigger=0.5,
                                      hot_capacity=1 << 6, hot_mem=1 << 5)
    sessions = [svc.open_session() for _ in range(3)]
    rng = np.random.default_rng(61)
    enq_log, results, migrations = {}, {}, []
    n_keys = 400

    drive(svc, sessions, rng, 240, n_keys, enq_log, results)

    # forced rebalance while sessions hold PENDING ops: migrate buckets
    # off the busiest shard, record the round boundary for the twin
    assert any(s.outstanding for s in sessions)
    nm = svc.kv.bucket_map.copy()
    src = int(np.argmax(np.bincount(nm, minlength=4)))
    nm[np.flatnonzero(nm == src)[:3]] = (src + 1) % 4
    migrations.append((len(svc.schedule), nm.copy()))
    svc.kv.migrate(nm)

    drive(svc, sessions, rng, 240, n_keys, enq_log, results)
    finish(svc, sessions, results)

    assert svc.kv.compactions.sum() > 0, \
        "no masked compaction overlapped the schedule"
    assert svc.kv.migrations == 1
    assert len(results) == len(enq_log) > 0
    ref = verify_history(svc, cfg, store_kw, 4, 8, enq_log, results,
                         migrations, "oracle")
    svc.check_invariants()

    # final full-keyspace readback against the folded dict model
    st, rv = svc.kv.read(np.arange(n_keys, dtype=np.int32))
    st, rv = np.asarray(st), np.asarray(rv)
    for k in range(n_keys):
        if k in ref:
            assert st[k] == ST_OK and np.array_equal(rv[k], ref[k]), k
        else:
            assert st[k] == ST_NOT_FOUND, k


def check_session_interleaving(seed, S=2, W=4, N=3, C=6, n_events=80,
                               n_keys=150, migrate_at=None):
    """The property behind the oracle, sized for many seeded instances."""
    svc, cfg, store_kw = make_service(S=S, W=W, N=N, C=C, trigger=0.6)
    sessions = [svc.open_session() for _ in range(N)]
    rng = np.random.default_rng(seed)
    enq_log, results, migrations = {}, {}, []
    drive(svc, sessions, rng, n_events, n_keys, enq_log, results)
    if migrate_at is not None:
        nm = rng.integers(0, S, svc.kv.n_buckets).astype(np.int32)
        migrations.append((len(svc.schedule), nm.copy()))
        svc.kv.migrate(nm)
        drive(svc, sessions, rng, n_events // 2, n_keys, enq_log, results)
    finish(svc, sessions, results)
    verify_history(svc, cfg, store_kw, S, W, enq_log, results, migrations,
                   ("interleave", seed))
    svc.check_invariants()


def test_session_interleaving_seeded():
    check_session_interleaving(11)
    check_session_interleaving(22, S=4, W=2, C=4)
    check_session_interleaving(33, migrate_at=True)
    check_session_interleaving(44, N=1, C=12)


# ---------------------------------------------------------------------------
# poll/drain against a per-ticket reference (the host mirror of the pool)
# ---------------------------------------------------------------------------

def drive_logged(svc, sessions, rng, n_events, n_keys, enq_log, log):
    """Random enqueue/step/poll/drain interleaving whose polls mix a
    session's outstanding tickets with unknown, rejected (-1), other
    sessions' and already-collected ones (sometimes listed twice).  Every
    poll and drain is logged with the tickets issued and the rounds run
    before it; the invariants are checked after every event."""
    collected = []
    for _ in range(n_events):
        act = rng.choice(["enq", "enq", "step", "poll", "poll", "drain"])
        s = sessions[int(rng.integers(0, len(sessions)))]
        issued = max(enq_log, default=-1) + 1
        if act == "enq":
            keys, ops, vals = mixed_enqueue(rng, n_keys,
                                            int(rng.integers(1, 7)))
            tk = s.enqueue(keys, ops, vals)
            for i, t in enumerate(tk):
                if t >= 0:
                    enq_log[int(t)] = (s.sid, int(keys[i]), int(ops[i]),
                                       vals[i].copy())
            # a fresh slot reads PENDING until a round runs, also where it
            # held a DONE op collected since the last round
            issued = max(enq_log, default=-1) + 1
            got = s.poll(tk)
            assert not got[0].any()
            log.append(("poll", issued, len(svc.schedule), s.sid, tk, got))
        elif act == "step":
            svc.step()
        elif act == "poll":
            out = s.outstanding_tickets()
            pick = list(rng.choice(out, size=min(len(out), 5),
                                   replace=False)) if len(out) else []
            junk = [-1, issued + 1000]
            others = [x for x in sessions if x is not s and x.outstanding]
            if others:
                junk.append(int(others[0].outstanding_tickets()[0]))
            if collected:
                junk.append(int(rng.choice(collected)))
            if pick and rng.random() < 0.3:
                junk.append(int(pick[0]))       # listed twice
            tickets = np.asarray(pick + junk, np.int64)
            rng.shuffle(tickets)
            got = s.poll(tickets)
            collected += [int(t) for t in tickets[got[0]]]
            log.append(("poll", issued, len(svc.schedule), s.sid, tickets,
                        got))
        elif act == "drain":
            got = s.drain()
            collected += [int(t) for t in got[0]]
            log.append(("drain", issued, len(svc.schedule), s.sid, None,
                        got))
        svc.check_invariants()


def check_log_against_rounds(svc, enq_log, log):
    """The per-ticket reference for every logged call: a poll answers
    exactly the tickets of its session that ran in an earlier round and
    were not collected before (each at its first listing), with that
    round's status and values, and ST_NONE/zeros elsewhere; a drain
    returns every uncollected ticket of its session in FIFO order."""
    ran = {}                            # ticket -> (round, status, vals)
    for r, (sess, valid, _, _, _, status, rvals,
            tkt) in enumerate(jax.device_get(svc.schedule)):
        for i in np.flatnonzero(np.asarray(valid)):
            ran[int(tkt[i])] = (r, int(status[i]), np.asarray(rvals[i]))
    collected = set()
    for kind, issued, rounds, sid, tickets, (a, st, v) in log:
        if kind == "poll":
            for i, t in enumerate(int(t) for t in tickets):
                ready = (0 <= t < issued and enq_log[t][0] == sid
                         and t not in collected and t in ran
                         and ran[t][0] < rounds)
                assert a[i] == ready, (kind, rounds, t)
                if ready:
                    collected.add(t)
                    assert st[i] == ran[t][1], (rounds, t)
                    assert np.array_equal(v[i], ran[t][2]), (rounds, t)
                else:
                    assert st[i] == 0 and not v[i].any(), (rounds, t)
        else:
            want = [t for t in range(issued)
                    if enq_log[t][0] == sid and t not in collected]
            assert list(a) == want, (kind, rounds, sid)
            for i, t in enumerate(want):
                assert ran[t][0] < rounds, (rounds, t)
                assert st[i] == ran[t][1], (rounds, t)
                assert np.array_equal(v[i], ran[t][2]), (rounds, t)
            collected.update(want)
    return {t: (ran[t][1], ran[t][2]) for t in collected}


@pytest.mark.parametrize("seed", [101, 202, 303])
def test_poll_drain_reference_with_ring_wrap(seed):
    """Depth-8 rings that wrap many times, slots reused between two
    steps, and polls of unknown, rejected, foreign, repeated and
    collected tickets: every poll and drain result matches the per-ticket
    reference, the twin replay holds, and so do the invariants (the host
    mirror against the pool) after every event."""
    C = 8
    svc, cfg, store_kw = make_service(S=2, W=4, N=3, C=C, trigger=0.6)
    sessions = [svc.open_session() for _ in range(3)]
    rng = np.random.default_rng(seed)
    enq_log, log = {}, []
    # the reuse case by construction: fill a ring, run and collect it,
    # refill the same slots before the next round, and poll them
    s = sessions[0]
    for _ in range(2):
        keys, ops, vals = mixed_enqueue(rng, 150, C)
        tk = s.enqueue(keys, ops, vals)
        for i, t in enumerate(tk):
            enq_log[int(t)] = (s.sid, int(keys[i]), int(ops[i]), vals[i])
        issued = max(enq_log) + 1
        log.append(("poll", issued, len(svc.schedule), s.sid, tk,
                    s.poll(tk)))
        svc.run_until_idle()
        log.append(("poll", issued, len(svc.schedule), s.sid, tk,
                    s.poll(tk)))
        assert log[-1][-1][0].all() and log[-2][-1][0].sum() == 0
        svc.check_invariants()
    drive_logged(svc, sessions, rng, 150, 150, enq_log, log)
    for s in sessions:
        got = s.drain()
        log.append(("drain", max(enq_log) + 1, len(svc.schedule), s.sid,
                    None, got))
    svc.check_invariants()
    assert sum(1 for e in log if e[0] == "drain") > len(sessions)
    assert max(enq_log) + 1 > 6 * C             # the rings wrapped
    results = check_log_against_rounds(svc, enq_log, log)
    assert len(results) == len(enq_log)         # each collected once
    verify_history(svc, cfg, store_kw, 2, 4, enq_log, results, [],
                   ("reference", seed))


@pytest.mark.parametrize("n_sessions", [1, 8])
def test_pool_fetched_once_per_round(n_sessions):
    """Polls of every session after one step() fetch the pool once; polls
    with no step in between, and polls before any step, fetch nothing."""
    svc, _, _ = make_service(S=2, W=2, N=n_sessions, C=16)
    sessions = [svc.open_session() for _ in range(n_sessions)]
    rng = np.random.default_rng(n_sessions)
    # more ops than three rounds of 2 x 2 lanes run
    tks = [s.enqueue(*mixed_enqueue(rng, 64, 14)) for s in sessions]

    def poll_all():
        for s, tk in zip(sessions, tks):
            s.poll(tk)
        return svc.stats()["sessions"]["pool_fetches"]

    assert poll_all() == 0
    svc.step()
    assert poll_all() == 1
    assert poll_all() == 1
    svc.step()
    svc.step()
    assert poll_all() == 2
    assert svc.stats()["sessions"]["polls"] == 4 * n_sessions
    for s in sessions:
        s.drain()
    svc.check_invariants()


def test_pool_fetches_bounded_by_rounds_in_serving_loop():
    """The serving loop's shape (chunks offered round-robin to 8 sessions
    with room, one step, a poll of every session's outstanding tickets):
    one pool fetch a round, and at most rounds plus drains overall."""
    N, C, chunk = 8, 32, 8
    svc, _, _ = make_service(S=2, W=16, N=N, C=C)
    svc.trace_schedule = False
    sessions = [svc.open_session() for _ in range(N)]
    pend = [np.zeros(0, np.int64) for _ in sessions]
    rng = np.random.default_rng(9)
    rr = 0
    for _ in range(40):
        for _ in range(int(rng.integers(1, 4))):
            room = [i for i in range(N)
                    if sessions[(rr + i) % N].capacity
                    - sessions[(rr + i) % N].in_use >= chunk]
            if not room:
                break
            i = (rr + room[0]) % N
            rr = (i + 1) % N
            tk = sessions[i].enqueue(*mixed_enqueue(rng, 200, chunk))
            pend[i] = np.concatenate([pend[i], tk.astype(np.int64)])
        svc.step()
        for i, s in enumerate(sessions):
            if len(pend[i]):
                done, _, _ = s.poll(pend[i])
                pend[i] = pend[i][~done]
    st = svc.stats()["sessions"]
    assert st["pool_fetches"] == st["pack_rounds"] == 40
    for s in sessions:
        s.drain()
    st = svc.stats()["sessions"]
    assert st["pool_fetches"] <= st["pack_rounds"] + N
    assert st["outstanding"] == 0
    svc.check_invariants()


def test_session_over_replicated_store():
    """The session layer runs unchanged over `ReplicatedKV`: packed
    cross-session rounds fan in to every alive replica (replicas stay
    byte-identical), the primary's statuses/values match a flat
    `ShardedKV` twin replaying the recorded schedule, and replica 0 is
    leaf-for-leaf equal to that twin — the acceptance bar's
    replica-0-state form of the interleaving oracle."""
    from repro.core.replication import (ReplicatedKV,
                                        replicas_byte_identical)
    cfg = tiny_cfg()
    store_kw = dict(trigger=0.6, compact_frac=0.3, compact_batch=64,
                    donate=False, lanes=4)
    svc = KVSessionService(ReplicatedKV(cfg, 2, n_replicas=2, **store_kw),
                           max_sessions=2, session_depth=8)
    svc.trace_schedule = True
    sessions = [svc.open_session() for _ in range(2)]
    rng = np.random.default_rng(5)
    enq_log, results = {}, {}
    drive(svc, sessions, rng, 80, 150, enq_log, results)
    finish(svc, sessions, results)
    assert len(results) == len(enq_log) > 0
    assert replicas_byte_identical(svc.kv)

    twin = ShardedKV(cfg, 2, **store_kw)
    for r, (sess, valid, bkeys, bops, bvals, status, rvals,
            tkt) in enumerate(jax.device_get(svc.schedule)):
        st_t, rv_t, _, deferred = twin.apply_round(
            np.asarray(bkeys), np.asarray(bops), np.asarray(bvals))
        assert not np.asarray(deferred).any(), r
        assert np.array_equal(np.asarray(st_t), np.asarray(status)), r
        assert np.array_equal(np.asarray(rv_t), np.asarray(rvals)), r
    rep0 = jax.tree_util.tree_map(lambda x: x[0], svc.kv.state)
    for la, lb in zip(jax.tree_util.tree_leaves(jax.device_get(rep0)),
                      jax.tree_util.tree_leaves(jax.device_get(twin.state))):
        assert np.array_equal(np.asarray(la), np.asarray(lb)), \
            "replica 0 diverged from the flat twin replay"
    assert np.array_equal(svc.kv.compactions[0], twin.compactions)
    svc.check_invariants()


# ---------------------------------------------------------------------------
# Fairness / liveness under a hot-shard flood (satellite)
# ---------------------------------------------------------------------------

def shard_keyset(S, shard, n, n_keys=1 << 14):
    """Keys that all route to `shard` under the default bucket map."""
    cand = np.arange(n_keys, dtype=np.int32)
    sid = np.asarray(shard_router.shard_of(jnp.asarray(cand), S))
    ks = cand[sid == shard]
    assert len(ks) >= n, (S, shard, len(ks))
    return ks[:n]


def oldest_pending_slot(svc):
    """(ticket, (row, col)) of the globally-oldest PENDING op, or None.
    The slot position matters: `pool.ticket` keeps stale values in FREE
    slots, so the ticket value alone does not identify the op."""
    state, tkt = jax.device_get((svc.pool.slot_state, svc.pool.ticket))
    state, tkt = np.asarray(state), np.asarray(tkt)
    pend = state == SLOT_PENDING
    if not pend.any():
        return None
    masked = np.where(pend, tkt, np.iinfo(np.int32).max)
    pos = np.unravel_index(np.argmin(masked), masked.shape)
    return int(tkt[pos]), pos


def check_liveness(seed, S=2, W=4, N=3, C=8, rounds=30):
    """The liveness invariant, step by step: whatever the backlog, the
    globally-oldest PENDING ticket is executed by the very next round
    (global-FIFO arbitration wins its shard's capacity, and its session
    prefix is already done), so completion is bounded for every op."""
    svc, _, _ = make_service(S=S, W=W, N=N, C=C, trigger=0.9)
    sessions = [svc.open_session() for _ in range(N)]
    rng = np.random.default_rng(seed)
    hot = shard_keyset(S, 0, 64)
    for _ in range(rounds):
        for s in sessions:
            if rng.random() < 0.8 and s.in_use < C:
                B = int(rng.integers(1, C - s.in_use + 1))
                keys = hot[rng.integers(0, len(hot), B)].astype(np.int32)
                s.enqueue(keys, np.full(B, OP_RMW, np.int32),
                          np.ones((B, V), np.int32))
        oldest = oldest_pending_slot(svc)
        svc.step()
        if oldest is not None:
            t, pos = oldest
            state = np.asarray(jax.device_get(svc.pool.slot_state))
            assert state[pos] == SLOT_DONE, \
                (seed, t, "oldest pending ticket starved")
        for s in sessions:
            if rng.random() < 0.5 and s.outstanding:
                s.poll(list(s.outstanding_tickets()))
    for s in sessions:
        s.drain()
    svc.check_invariants()


def test_session_liveness_seeded():
    check_liveness(7)
    check_liveness(77, S=4, W=2, C=4)
    check_liveness(777, N=1)


def test_no_starvation_under_hot_shard_flood():
    """Session B's ops complete within the FIFO bound — the ops ahead of
    them divided by the lane width — even while session A continuously
    refloods the SAME shard with newer tickets every round."""
    S, W, C = 2, 4, 16
    svc, _, _ = make_service(S=S, W=W, N=2, C=C, trigger=0.9)
    a, b = svc.open_session(), svc.open_session()
    hot = shard_keyset(S, 0, 64)

    def flood(n):
        n = min(n, C - a.in_use)
        if n > 0:
            a.enqueue(hot[:n].astype(np.int32),
                      np.full(n, OP_RMW, np.int32), np.ones((n, V), np.int32))

    flood(C)                                    # A fills its ring first
    tb = b.enqueue(hot[:4].astype(np.int32), np.full(4, OP_RMW, np.int32),
                   np.ones((4, V), np.int32))
    ahead = C + len(tb)                         # all older + B's own ops
    bound = -(-ahead // W) + 1
    done_round = None
    for r in range(bound):
        svc.step()
        done, st, _ = b.poll(tb)
        # collect A's completions and immediately reflood with NEW tickets
        a.poll(list(a.outstanding_tickets()))
        flood(C)
        if done.all():
            done_round = r + 1
            break
        tb = tb[~done]
    assert done_round is not None and done_round <= bound, \
        (done_round, bound, "hot-shard flood starved session B")
    a.drain()
    b.drain()
    svc.check_invariants()


# ---------------------------------------------------------------------------
# Packer unit properties (pure pack_from_pool, no store)
# ---------------------------------------------------------------------------

def check_packer(seed, N=4, C=6, S=2, W=3, n_keys=64):
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, n_keys, (N, C)).astype(np.int32)
    ops = rng.choice([OP_READ, OP_UPSERT], (N, C)).astype(np.int32)
    vals = rng.integers(0, 9, (N, C, V)).astype(np.int32)
    pending = rng.random((N, C)) < 0.6
    # distinct global tickets, random placement across the rings
    tkt = rng.permutation(N * C).reshape(N, C).astype(np.int32)
    bmap = shard_router.default_bucket_map(S, 4 * S)
    bkeys, bops, bvals, sess, slot, valid, fill = jax.device_get(
        shard_router.pack_from_pool(
            jnp.asarray(keys), jnp.asarray(ops), jnp.asarray(vals),
            jnp.asarray(tkt), jnp.asarray(pending), S, W,
            jnp.asarray(bmap)))
    valid = np.asarray(valid)
    sid_of = lambda k: bmap[np.asarray(
        shard_router.bucket_of(jnp.asarray(k, jnp.int32), len(bmap)))]
    picked = set()
    last_t = -1
    for i in np.flatnonzero(valid):
        n, c = int(sess[i]), int(slot[i])
        assert pending[n, c], (seed, "packed a non-pending slot")
        assert (n, c) not in picked, (seed, "slot packed twice")
        picked.add((n, c))
        assert bkeys[i] == keys[n, c] and bops[i] == ops[n, c]
        assert np.array_equal(bvals[i], vals[n, c])
        assert tkt[n, c] > last_t, (seed, "emission not ticket-ascending")
        last_t = tkt[n, c]
    assert (np.asarray(bops)[~valid] == OP_NOOP).all()
    # per-shard cap + fill telemetry
    sids = [int(sid_of(keys[n, c])) for n, c in picked]
    counts = np.bincount(sids, minlength=S)
    assert (counts <= W).all(), (seed, "over slab width")
    assert np.array_equal(np.asarray(fill), counts), seed
    # global FIFO: the oldest pending ticket is always packed
    if pending.any():
        tmin = tkt[pending].min()
        assert any(tkt[n, c] == tmin for n, c in picked), \
            (seed, "oldest pending ticket not packed")
    # per-session prefix closure
    for n in range(N):
        for c in range(C):
            if (n, c) in picked:
                older = [(n, c2) for c2 in range(C)
                         if pending[n, c2] and tkt[n, c2] < tkt[n, c]]
                for nc in older:
                    assert nc in picked, (seed, n, c, "prefix broken")
    # per-shard selection is oldest-first: an unpacked pending op must be
    # explained by >= W older PENDING ops in its shard (it lost the
    # top-W-by-ticket cut; closure does not backfill) or by its own
    # session prefix not fitting
    for n in range(N):
        for c in range(C):
            if pending[n, c] and (n, c) not in picked:
                s = int(sid_of(keys[n, c]))
                older_same_shard = sum(
                    1 for n2 in range(N) for c2 in range(C)
                    if pending[n2, c2]
                    and int(sid_of(keys[n2, c2])) == s
                    and tkt[n2, c2] < tkt[n, c])
                blocked_prefix = any(
                    pending[n, c2] and tkt[n, c2] < tkt[n, c]
                    and (n, c2) not in picked for c2 in range(C))
                assert older_same_shard >= W or blocked_prefix, \
                    (seed, n, c, "op skipped without cause")


def test_packer_seeded():
    for seed in (3, 33, 333, 3333, 33333):
        check_packer(seed)
    check_packer(1, S=4, W=1)
    check_packer(2, N=1, C=12, S=2, W=8)
    check_packer(4, N=8, C=2)


def test_packer_empty_pool():
    bmap = shard_router.default_bucket_map(2, 8)
    out = shard_router.pack_from_pool(
        jnp.zeros((3, 4), jnp.int32), jnp.zeros((3, 4), jnp.int32),
        jnp.zeros((3, 4, V), jnp.int32), jnp.zeros((3, 4), jnp.int32),
        jnp.zeros((3, 4), bool), 2, 4, jnp.asarray(bmap))
    bkeys, bops, bvals, sess, slot, valid, fill = jax.device_get(out)
    assert not np.asarray(valid).any()
    assert (np.asarray(bops) == OP_NOOP).all()
    assert (np.asarray(fill) == 0).all()


# ---------------------------------------------------------------------------
# Ring / handle edge cases
# ---------------------------------------------------------------------------

def test_ring_capacity_rejection_and_reuse():
    """Over-capacity enqueues reject with ticket -1; collection frees
    slots for reuse; host cursor mirrors stay coherent throughout."""
    svc, _, _ = make_service(S=2, W=8, N=2, C=4, trigger=0.9)
    s = svc.open_session()
    t1 = s.enqueue(np.arange(6, dtype=np.int32),
                   np.full(6, OP_UPSERT, np.int32), np.ones((6, V), np.int32))
    assert list(t1[4:]) == [-1, -1] and s.in_use == 4
    done, st, _ = s.poll(t1)                    # nothing executed yet
    assert not done.any() and (np.asarray(st) == 0).all()
    svc.step()
    done, st, _ = s.poll(t1)
    assert list(done) == [True] * 4 + [False, False]
    assert s.in_use == 0                        # collection freed the ring
    t2 = s.enqueue(np.arange(4, dtype=np.int32),
                   np.full(4, OP_READ, np.int32))
    assert (t2 >= 0).all()
    tk, st, rv = s.drain()
    assert (np.asarray(st) == ST_OK).all()
    svc.check_invariants()


def test_out_of_order_free_holds_capacity():
    """Ring semantics: collecting a NEWER ticket while an older one is
    still uncollected does not free capacity (head cannot advance past
    the older slot); collecting the older one releases both at once."""
    svc, _, _ = make_service(S=2, W=1, N=1, C=4, trigger=0.9)
    s = svc.open_session()
    hot = shard_keyset(2, 0, 4)
    tk = s.enqueue(hot.astype(np.int32), np.full(4, OP_RMW, np.int32),
                   np.ones((4, V), np.int32))
    svc.step()                      # W=1: only the oldest ticket executes
    svc.step()                      # ... and then the next-oldest
    done, _, _ = s.poll(tk[2:])     # newest two are still pending
    assert not done.any()
    done, _, _ = s.poll(tk[1:2])    # collect ticket 1 BEFORE ticket 0
    assert done.all() and s.in_use == 4     # hole: no capacity released
    done, _, _ = s.poll(tk[:1])     # collecting ticket 0 releases both
    assert done.all() and s.in_use == 2
    s.drain()
    assert s.in_use == 0
    svc.check_invariants()


def test_noop_enqueue_rejected():
    svc, _, _ = make_service(S=2, W=4, N=1, C=4)
    s = svc.open_session()
    with pytest.raises(AssertionError):
        s.enqueue(np.zeros(2, np.int32), np.full(2, OP_NOOP, np.int32))


def test_session_lifecycle():
    """close_session frees the sid for reuse; a closed handle refuses
    work; the pool has a hard session cap."""
    svc, _, _ = make_service(S=2, W=4, N=2, C=4)
    a, b = svc.open_session(), svc.open_session()
    with pytest.raises(RuntimeError):
        svc.open_session()
    a.enqueue(np.arange(2, dtype=np.int32), np.full(2, OP_UPSERT, np.int32),
              np.ones((2, V), np.int32))
    a.drain()
    a.close()
    with pytest.raises(AssertionError):
        a.enqueue(np.zeros(1, np.int32), np.full(1, OP_READ, np.int32))
    c = svc.open_session()          # reuses sid 0, cursors carry over
    assert c.sid == a.sid
    tk = c.enqueue(np.arange(2, dtype=np.int32),
                   np.full(2, OP_READ, np.int32))
    tk, st, rv = c.drain()
    assert (np.asarray(st) == ST_OK).all()
    b.close()
    svc.check_invariants()


# ---------------------------------------------------------------------------
# ticket latency clock: phase oracle + disabled path bit-exactness
# ---------------------------------------------------------------------------

def _drive_for_clock(svc, seed):
    """A fixed interleaving of enqueues / steps / polls / drains; returns
    the per-poll and per-drain outputs for bit-exact comparison."""
    rng = np.random.default_rng(seed)
    a, b = svc.open_session(), svc.open_session()
    outs = []
    for _ in range(4):
        tks = {}
        for s in (a, b):
            keys, ops, vals = mixed_enqueue(rng, 64, 8)
            tks[s.sid] = s.enqueue(keys, ops, vals)
        svc.step()
        svc.step()
        for s in (a, b):
            done, st, rv = s.poll(tks[s.sid])
            outs.append((np.asarray(done), np.asarray(st), np.asarray(rv)))
    svc.run_until_idle()
    for s in (a, b):
        tk, st, rv = s.drain()
        outs.append((np.asarray(tk), np.asarray(st), np.asarray(rv)))
    return outs


def test_ticket_latency_oracle_and_disabled_bit_exact():
    """With obs enabled, a fully-drained run's phase histograms satisfy
    the lifecycle oracle: every collected ticket has exactly one queue,
    apply and e2e observation, all durations are positive, and the e2e
    total dominates queue+apply (e2e spans both, minus no overlap).  The
    disabled twin — identical op stream — returns bit-exact client
    results and records nothing."""
    from repro import obs
    from repro.obs import latency
    obs.configure(enabled=False, reset=True)
    try:
        svc_off, _, _ = make_service(S=2, W=8, N=2, C=16)
        outs_off = _drive_for_clock(svc_off, seed=5)
        assert latency.summary() == {}      # disabled: nothing recorded

        obs.configure(enabled=True, reset=True)
        svc_on, _, _ = make_service(S=2, W=8, N=2, C=16)
        outs_on = _drive_for_clock(svc_on, seed=5)

        for (xa, ya, za), (xb, yb, zb) in zip(outs_off, outs_on):
            np.testing.assert_array_equal(xa, xb)
            np.testing.assert_array_equal(ya, yb)
            np.testing.assert_array_equal(za, zb)

        assert svc_on._clock.outstanding == 0   # fully drained
        s = latency.summary()
        n = svc_on.collected
        assert n > 0
        assert s["queue"]["count"] == n
        assert s["apply"]["count"] == n
        assert s["e2e"]["count"] == n
        assert s["pack"]["count"] == svc_on.pack_rounds
        for phase in ("queue", "apply", "e2e", "pack"):
            assert s[phase]["mean"] > 0.0, phase
            assert s[phase]["p50"] > 0.0, phase
        e2e_sum = s["e2e"]["mean"] * n
        part = (s["queue"]["mean"] + s["apply"]["mean"]) * n
        assert e2e_sum >= part * (1 - 1e-9)
    finally:
        obs.configure(enabled=False, reset=True)


# ---------------------------------------------------------------------------
# Hypothesis properties (seeded fallbacks above always run)
# ---------------------------------------------------------------------------

try:
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st
    _HAVE_HYPOTHESIS = True
except ImportError:
    _HAVE_HYPOTHESIS = False

if _HAVE_HYPOTHESIS:
    @settings(max_examples=6, deadline=None,
              suppress_health_check=[HealthCheck.too_slow])
    @given(st.integers(0, 2**31 - 1), st.booleans())
    def test_session_interleaving_property(seed, migrate):
        check_session_interleaving(seed, n_events=50,
                                   migrate_at=True if migrate else None)

    @settings(max_examples=6, deadline=None,
              suppress_health_check=[HealthCheck.too_slow])
    @given(st.integers(0, 2**31 - 1))
    def test_session_liveness_property(seed):
        check_liveness(seed, rounds=15)

    @settings(max_examples=25, deadline=None)
    @given(st.integers(0, 2**31 - 1), st.integers(1, 4), st.integers(1, 6))
    def test_packer_property(seed, s_exp, w):
        check_packer(seed, S=1 << (s_exp - 1), W=w)
else:
    _skip = pytest.mark.skip(
        reason="hypothesis not installed (pip install '.[test]')")

    @_skip
    def test_session_interleaving_property():
        pass

    @_skip
    def test_session_liveness_property():
        pass

    @_skip
    def test_packer_property():
        pass
