"""ReplicatedKV: a replica axis next to the shard axis (the follow-on the
sharding subsystem unlocks, ROADMAP) — fan-out reads, fan-in writes, and
live replica resync.

The read cache exists because read-hot records deserve cheap extra copies
(paper S7.2); at cluster scale the same idea is *replication*: R copies of
every shard serve read-hot traffic in parallel, while writes keep all
copies convergent.  `ReplicatedF2State` is structurally a `ShardedF2State`
whose every leaf carries a second leading axis:

        leaf [R, S, ...]   —  R replicas  x  S shards  x  per-store state

stacked by `jax.vmap` of `sharded.create` and executed by *nested* vmap
(or `shard_map` over a 2-D `(replica, shard)` device mesh; a single-device
mesh runs the same path, so CPU CI exercises the multi-device program).

Write fan-in
------------
Upserts/RMWs/Deletes route ONCE (`shard_router.route` is replica-
independent — one shared bucket map) and every alive replica applies the
identical per-shard slabs.  Replicas start as bit-identical copies and
every fan-in state transition is a pure function of (state, slabs), so
alive replicas stay **bit-identical by construction** — the parity suite
(tests/test_replication.py) holds replica 0 leaf-for-leaf equal to an
unreplicated ShardedKV over the same op stream, through masked
compactions, rebalances and a drop→resync cycle.  Mixed `apply` batches
fan in whole (read lanes included, with read-cache admission), exactly
like ShardedKV — so the replicated write path is the sharded write path
under one extra vmap.

Read fan-out
------------
The dedicated read path (`read`) sends each lane to exactly ONE replica:
a deterministic per-batch selector (`shard_router.assign_replicas`;
round-robin, or least-loaded from the per-replica traffic EWMA) assigns
lanes, each replica probes only its masked sub-batch, and per-lane
results gather back by assignment.  A hot shard's read demand therefore
splits R ways — with per-shard slab width `lanes`, deferral rounds drop
by up to R (the cluster reading of the paper's read-cache story).
Fan-out reads are **pure**: they never admit to the read cache and never
write back state (the probe I/O is accounted host-side per replica), so
serving reads from different replicas cannot desync them.

Replica lifecycle
-----------------
`drop_replica(r)` removes a replica from serving: the selector skips it,
and fan-in passes mask it out (`_rep_select`), so its state freezes while
the survivors advance — a deliberate desync, the tensorized stand-in for
a crashed node.  `resync(r)` rebuilds it live from a healthy replica via
the PR-4 drain→replay machinery: reset r to a fresh store, drain the
source's hot+cold logs with the compaction-style liveness walk (a *pure*
non-donating pass — healthy replicas stay byte-identical through it),
then replay the live records as routed writes masked to r only (cold
values first, hot records after, live hot tombstones as Deletes), with
the pressure scheduler restricted to r so mid-replay compactions touch
nobody else.  The resynced replica is logically convergent (bit-exact
statuses/values — the oracle) though its log *layout* is compacted
relative to never-dropped replicas, which remain byte-identical to each
other.

Rebalancing under replication flips the ONE shared bucket map — all
replicas' routing changes atomically; drain/purge/replay run masked over
the alive replicas, dead replicas are rebuilt under the new map at
resync time.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from . import rebalance, shard_router, sharded, store
from .sharded import DISPATCHES, SHARD_AXIS, ShardedKV, bucket_counts
from repro import obs
from repro.testing import faults
from .types import (BLOCK_BYTES, OP_DELETE, OP_NOOP, OP_READ, OP_UPSERT,
                    F2Config, IoStats)

REPLICA_AXIS = "replicas"


def create(cfg: F2Config, n_replicas: int, n_shards: int) -> store.F2State:
    """ReplicatedF2State: R bit-identical ShardedF2States stacked on a new
    leading replica axis."""
    return jax.vmap(lambda _: sharded.create(cfg, n_shards))(
        jnp.arange(n_replicas))


def resolve_mesh_2d(dispatch: str, n_replicas: int,
                    n_shards: int) -> Optional[Mesh]:
    """None -> nested vmap on one device; else a 2-D (replica, shard) Mesh
    using the most devices that factor as (divisor of R) x (divisor of S).
    A (1, 1) mesh is valid, so `dispatch="shard_map"` runs on CPU CI."""
    assert dispatch in DISPATCHES, f"unknown dispatch {dispatch!r}"
    devs = jax.devices()
    if dispatch == "vmap" or (dispatch == "auto" and len(devs) == 1):
        return None
    best, best_n = (1, 1), 0
    for rd in range(1, min(len(devs), n_replicas) + 1):
        if n_replicas % rd:
            continue
        sd = max(d for d in range(1, min(len(devs) // rd, n_shards) + 1)
                 if n_shards % d == 0)
        if rd * sd > best_n:
            best, best_n = (rd, sd), rd * sd
    return Mesh(np.asarray(devs[:best_n]).reshape(best),
                (REPLICA_AXIS, SHARD_AXIS))


def _rep_select(rep_do: jax.Array, new, old):
    """Per-replica masked state update: keep `new` where rep_do[r], else
    `old` — the replica-axis analogue of the scheduler's `_select`."""
    def sel(a, b):
        cond = rep_do.reshape(rep_do.shape + (1,) * (a.ndim - 1))
        return jnp.where(cond, a, b)
    return jax.tree_util.tree_map(sel, new, old)


# -- pure (state-discarding) drain kernels for resync ------------------------

def _pure_drain_hot(cfg, B, nb, state, start, until, move, do):
    _, k, v, tomb, take = rebalance.drain_hot_step(
        cfg, B, nb, state, start, until, move, do)
    return k, v, tomb, take


def _pure_drain_cold(cfg, B, nb, state, start, until, move, do):
    _, k, v, take = rebalance.drain_cold_step(
        cfg, B, nb, state, start, until, move, do)
    return k, v, take


def replicas_byte_identical(kv: "ReplicatedKV",
                            replicas=None) -> bool:
    """True iff the given replicas (default: all alive) are byte-identical
    on every state leaf — the invariant fan-in maintains by construction."""
    reps = (list(np.flatnonzero(kv.alive)) if replicas is None
            else [int(r) for r in replicas])
    if len(reps) < 2:
        return True
    state = jax.device_get(kv.state)
    for leaf in jax.tree_util.tree_leaves(state):
        a = np.asarray(leaf)
        for r in reps[1:]:
            if not np.array_equal(a[reps[0]], a[r]):
                return False
    return True


class ReplicatedKV(ShardedKV):
    """API-compatible with `api.KV`/`ShardedKV`, holding R replica copies
    of S hash-partitioned shards.  Writes fan in (every alive replica
    applies the identical routed slabs), dedicated reads fan out (each
    lane served by exactly one replica, chosen by a deterministic
    selector), and replicas can be dropped and live-resynced."""

    _obs_facade = "replicated"

    def __init__(
        self,
        cfg: F2Config,
        n_shards: int,
        n_replicas: int = 2,
        read_selector: str = "round_robin",
        replica_decay: float = 0.8,
        **kw,
    ):
        assert n_replicas >= 1
        assert read_selector in shard_router.REPLICA_POLICIES, read_selector
        assert not cfg.host_tier, \
            "host_tier is not supported under replication (the host chunk " \
            "stores would need a replica axis and resync integration)"
        # hooks used inside super().__init__ need these first
        self.R = int(n_replicas)
        self.read_selector = read_selector
        self.alive = np.ones(self.R, bool)
        self._resync_only: Optional[int] = None
        super().__init__(cfg, n_shards, **kw)
        self.drops = 0
        self.resyncs = 0
        self.resynced_records = 0
        self._read_batches = 0          # selector rotation counter
        self._replica_decay = float(replica_decay)
        self._replica_load = np.zeros(self.R, np.float64)
        self._pending_read = []         # unfolded fan-out round telemetry
        self._read_io = {f: np.zeros((self.R, self.S), np.int64)
                         for f in IoStats._fields}
        self._read_exhausted = np.zeros((self.R, self.S), bool)
        self._fresh = None              # lazily-built blank replica (resync)

        R = self.R

        def reset_replica(state, fresh, onehot):
            return jax.tree_util.tree_map(
                lambda f, s: jnp.where(
                    onehot.reshape((R,) + (1,) * (s.ndim - 1)), f[None], s),
                fresh, state)

        self._reset_step = jax.jit(reset_replica)
        # pure resync drains: non-donating (self.state stays live) and
        # state-discarding (healthy replicas byte-identical through them)
        self._pure_drain_hot = jax.jit(self._lift(functools.partial(
            _pure_drain_hot, self.cfg, self._mig_batch, self.n_buckets),
            n_in=5))
        self._pure_drain_cold = jax.jit(self._lift(functools.partial(
            _pure_drain_cold, self.cfg, self._mig_batch, self.n_buckets),
            n_in=5))

    # -- axis hooks (consumed by the generalized ShardedKV internals) --------
    @property
    def _lead_shape(self) -> tuple:
        return (self.R, self.S)

    def _resolve_mesh(self, dispatch: str) -> Optional[Mesh]:
        return resolve_mesh_2d(dispatch, self.R, self.S)

    def _create_state(self) -> store.F2State:
        return self._create_on_mesh(functools.partial(create, self.cfg,
                                                      self.R, self.S))

    def _lift(self, fn, n_in: int):
        """Nested vmap over (replica, shard); under shard_map the two
        leading axes partition across the 2-D device mesh (replicas never
        communicate either — the program stays embarrassingly parallel)."""
        vf = jax.vmap(jax.vmap(fn))
        if self.mesh is None:
            return vf
        spec = P(REPLICA_AXIS, SHARD_AXIS)
        return jax.shard_map(vf, mesh=self.mesh, in_specs=(spec,) * n_in,
                             out_specs=spec, check_vma=False)

    def _sched_mask(self, shards: np.ndarray) -> np.ndarray:
        """Scheduler passes touch only alive replicas — or, mid-resync,
        only the replica being rebuilt (so replay-pressure compactions
        cannot perturb healthy replicas)."""
        if self._resync_only is not None:
            rep_ok = np.arange(self.R) == self._resync_only
        else:
            rep_ok = self.alive
        return shards & rep_ok[:, None]

    def _rep_shard(self, m: np.ndarray) -> np.ndarray:
        return self.alive[:, None] & m[None, :]

    def _rep_move(self, move: np.ndarray) -> jax.Array:
        return jnp.asarray(np.broadcast_to(move, (self.R,) + move.shape))

    def _host_view(self, x) -> np.ndarray:
        return np.asarray(x)[self._primary(self.alive)]

    @staticmethod
    def _primary(rep_do: np.ndarray) -> int:
        """Lowest-indexed selected replica: where fan-in results (and
        migrate-drain collections) are taken from."""
        return int(np.flatnonzero(rep_do)[0])

    # -- jitted steps ---------------------------------------------------------
    def _build_router_steps(self, dn: dict, admit: bool):
        cfg, S, R, nb = self.cfg, self.S, self.R, self.n_buckets

        apply_lifted = self._lift(
            functools.partial(store.apply, cfg, admit_rc=admit), n_in=4)

        def fan_in_step(state, keys, ops, vals, bmap, rep_do):
            """Route ONCE, broadcast the slabs over the replica axis, apply
            on every selected replica (dead replicas tree-select their old
            state).  Returns per-replica statuses/values [R, B] — all
            selected rows are identical when replicas are in sync."""
            W = self.lanes or keys.shape[0]
            skeys, sops, svals, rt = shard_router.route(
                keys, ops, vals, S, W, bucket_map=bmap)
            rep = lambda x: jnp.broadcast_to(x[None], (R,) + x.shape)  # noqa: E731
            new_state, sstatus, srvals = apply_lifted(
                state, rep(skeys), rep(sops), rep(svals))
            state = _rep_select(rep_do, new_state, state)
            status, rvals = jax.vmap(shard_router.unroute,
                                     in_axes=(None, 0, 0))(rt, sstatus,
                                                           srvals)
            return (state, status, rvals, rt.placed, rt.deferred,
                    rt.occupancy, bucket_counts(rt, nb))

        self._step = jax.jit(fan_in_step, **dn)

        # fan-out read: pure (admit_rc=False, state discarded) — serving a
        # lane from replica r cannot desync r from its peers
        read_lifted = self._lift(
            functools.partial(store.read_batch, cfg, admit_rc=False),
            n_in=3)

        def fan_out_read(state, keys, rep, active, bmap):
            """Each replica routes + probes its assigned lanes; per-lane
            results gather back by assignment.  Returns merged results
            plus per-replica telemetry (I/O delta, load, exhaustion) —
            and no state: fan-out reads never write back."""
            B = keys.shape[0]
            W = self.lanes or B
            rids = jnp.arange(R, dtype=jnp.int32)
            ops_rb = jnp.where((rep[None, :] == rids[:, None])
                               & active[None, :], OP_READ, OP_NOOP)
            vals0 = jnp.zeros((B, cfg.value_width), jnp.int32)
            skeys, sops, _sv, rt = jax.vmap(
                lambda o: shard_router.route(keys, o, vals0, S, W,
                                             bucket_map=bmap))(ops_rb)
            new_state, sstatus, srvals = read_lifted(state, skeys,
                                                     sops == OP_READ)
            status_r, vals_r = jax.vmap(shard_router.unroute)(rt, sstatus,
                                                              srvals)
            lane = jnp.arange(B)
            io_delta = jax.tree_util.tree_map(lambda a, b: a - b,
                                              new_state.stats, state.stats)
            return (status_r[rep, lane], vals_r[rep, lane],
                    rt.placed[rep, lane], rt.deferred[rep, lane],
                    rt.occupancy.sum(axis=0),                 # [S] client
                    jax.vmap(lambda r: bucket_counts(r, nb))(rt).sum(0),
                    io_delta, new_state.walk_exhausted,       # [R, S] each
                    rt.occupancy.sum(axis=1))                 # [R] load

        self._read_step = jax.jit(fan_out_read)

    # -- batched operations ---------------------------------------------------
    def apply_round(self, keys, ops, vals=None, _rep_do=None):
        """One fan-in routed round: every selected replica (default: all
        alive) applies the identical routed slabs, results come from the
        primary replica.  Same contract as `ShardedKV.apply_round` — the
        session scheduler drives this entry under replication."""
        keys, ops, vals = self._coerce(keys, ops, vals)
        with obs.span("replicated.apply_round", cat="serve",
                      B=int(keys.shape[0]), round=self.rounds):
            if (self.wal is not None and not self._migrating
                    and not self._wal_defer and _rep_do is None):
                # write-ahead, same rule as ShardedKV: client rounds only —
                # masked resync/rebuild replay reconstructs already-logged
                # data, and `apply` logs its whole batch itself
                self.wal.log_slab(keys, ops, vals, self.map_version)
            rep_do = np.asarray(self.alive if _rep_do is None else _rep_do,
                                bool)
            h = self._primary(rep_do)
            (self.state, st_r, rv_r, placed, deferred,
             occ, bc) = self._step(self.state, keys, ops, vals,
                                   self._bucket_map_dev, jnp.asarray(rep_do))
            self._note_round(occ, bc)
            self.maybe_compact()
        return st_r[h], rv_r[h], placed, deferred

    def apply(self, keys, ops, vals=None, _rep_do=None):
        """Fan-in: every selected replica (default: all alive) applies the
        identical routed batch; results come from the primary replica.
        Deferral, the pressure scheduler and the rebalance check work
        exactly like ShardedKV."""
        keys, ops, vals = self._coerce(keys, ops, vals)
        B = keys.shape[0]
        if self.lanes is None or self.lanes >= B:
            status, rvals, _placed, _deferred = self.apply_round(
                keys, ops, vals, _rep_do=_rep_do)
            self.maybe_rebalance()
            return status, rvals
        # write-ahead ONCE for the whole batch (see ShardedKV.apply)
        if (self.wal is not None and not self._migrating
                and _rep_do is None):
            self.wal.log_slab(keys, ops, vals, self.map_version)
        status = np.zeros(B, np.int32)
        rvals = np.zeros((B, self.cfg.value_width), np.int32)
        cur_ops = ops
        self._wal_defer = True
        try:
            for _ in range(B + 1):
                st_r, rv_r, placed, deferred = self.apply_round(
                    keys, cur_ops, vals, _rep_do=_rep_do)
                placed_np = np.asarray(placed)
                status = np.where(placed_np, np.asarray(st_r), status)
                rvals = np.where(placed_np[:, None], np.asarray(rv_r),
                                 rvals)
                deferred_np = np.asarray(deferred)
                if not deferred_np.any():
                    break
                cur_ops = jnp.where(jnp.asarray(deferred_np), ops,
                                    jnp.int32(OP_NOOP))
        finally:
            self._wal_defer = False
        self.maybe_rebalance()
        return jnp.asarray(status), jnp.asarray(rvals)

    def read(self, keys, replica: Optional[int] = None):
        """Fan-out read: every lane served by exactly one alive replica
        (deterministic selector; `replica=` pins the whole batch — the
        operator's read-one-replica probe).  Pure: no replica state
        changes, so serving cannot desync replicas."""
        keys = jnp.asarray(keys, jnp.int32)
        B = keys.shape[0]
        if replica is None:
            self._fold_read()       # least_loaded reads the folded EWMA
            rep = shard_router.assign_replicas(
                B, self.alive, counter=self._read_batches,
                policy=self.read_selector, loads=self._replica_load)
        else:
            assert self.alive[replica], f"replica {replica} is not alive"
            rep = np.full(B, int(replica), np.int32)
        self._read_batches += 1
        rep_dev = jnp.asarray(rep)
        bmap = self._bucket_map_dev
        active = np.ones(B, bool)
        if self.lanes is None or self.lanes >= B:
            with obs.span("replicated.read", cat="serve", B=B):
                (status, rvals, _placed, _deferred, occ, bc, io_d, exh,
                 rl) = self._read_step(self.state, keys, rep_dev,
                                       jnp.asarray(active), bmap)
                self._note_read_round(occ, bc, io_d, exh, rl)
            obs.observe("f2_deferral_rounds", 1, buckets=obs.COUNT_BUCKETS,
                        help="routed rounds needed per client batch",
                        facade=self._obs_facade, path="read")
            return status, rvals
        status = np.zeros(B, np.int32)
        rvals = np.zeros((B, self.cfg.value_width), np.int32)
        n_rounds = 0
        for _ in range(B + 1):
            with obs.span("replicated.read", cat="serve", B=B):
                (st_b, rv_b, placed, deferred, occ, bc, io_d, exh,
                 rl) = self._read_step(self.state, keys, rep_dev,
                                       jnp.asarray(active), bmap)
                self._note_read_round(occ, bc, io_d, exh, rl)
            n_rounds += 1
            placed_np = np.asarray(placed)
            status = np.where(placed_np, np.asarray(st_b), status)
            rvals = np.where(placed_np[:, None], np.asarray(rv_b), rvals)
            deferred_np = np.asarray(deferred)
            if not deferred_np.any():
                break
            active = deferred_np
        obs.observe("f2_deferral_rounds", n_rounds,
                    buckets=obs.COUNT_BUCKETS,
                    help="routed rounds needed per client batch",
                    facade=self._obs_facade, path="read")
        return jnp.asarray(status), jnp.asarray(rvals)

    # -- fan-out read telemetry (host-side: replica states never change) -----
    def _note_read_round(self, occ, bc, io_delta, exhausted, rep_lanes):
        self._note_round(occ, bc)
        self._pending_read.append((io_delta, exhausted, rep_lanes))
        if len(self._pending_read) >= 128:
            self._fold_read()

    def _fold_read(self):
        if not self._pending_read:
            return
        pending, self._pending_read = jax.device_get(self._pending_read), []
        for io_d, exh, rl in pending:
            for f in IoStats._fields:
                self._read_io[f] += np.asarray(
                    getattr(io_d, f)).astype(np.int64)
            self._read_exhausted |= np.asarray(exh)
            self._replica_load = (self._replica_decay * self._replica_load
                                  + np.asarray(rl).astype(np.float64))
        if obs.enabled():       # mirror the folded fan-out read signal
            obs.gauge_set("f2_replica_load", self._replica_load.tolist(),
                          help="per-replica fan-out read-load EWMA",
                          facade=self._obs_facade)
            obs.count_total("f2_fanout_read_ops_total",
                            int(self._read_io["read_ops"].sum()),
                            help="reads served via replica fan-out",
                            facade=self._obs_facade)
            obs.count_total("f2_fanout_mem_hits_total",
                            int(self._read_io["mem_hits"].sum()),
                            help="fan-out reads served from memory",
                            facade=self._obs_facade)

    @property
    def replica_load(self) -> np.ndarray:
        self._fold_read()
        return self._replica_load.copy()

    # -- replica lifecycle ----------------------------------------------------
    def drop_replica(self, r: int):
        """Remove replica r from serving: reads route around it, fan-in
        masks it out, its state freezes (a deliberate desync — the stand-in
        for a crashed node)."""
        r = int(r)
        assert self.alive[r], f"replica {r} already dropped"
        assert self.alive.sum() >= 2, "cannot drop the last alive replica"
        assert not self._migrating
        self.alive[r] = False
        self.drops += 1
        obs.journal.emit("replica.dropped", facade=self._obs_facade,
                         replica=r)
        obs.count("f2_replica_drops_total", facade=self._obs_facade)

    def resync(self, r: int) -> int:
        """Rebuild dropped replica r live from a healthy replica: reset ->
        pure liveness drain of the source's hot+cold logs -> replay masked
        to r (cold values first, live hot tombstones as Deletes), with the
        pressure scheduler restricted to r.  Healthy replicas stay
        byte-identical throughout.  Returns records replayed."""
        r = int(r)
        assert not self.alive[r], f"replica {r} is alive; drop it first"
        assert not self._migrating
        rs_span = obs.span("replica.resync", cat="replication", replica=r)
        rs_span.__enter__()
        h = self._primary(self.alive)
        Bm = self._mig_batch
        V = self.cfg.value_width
        onehot = np.arange(self.R) == r
        # --- reset r to a blank store ------------------------------------
        if self._fresh is None:
            self._fresh = sharded.create(self.cfg, self.S)
        self.state = self._reset_step(self.state, self._fresh,
                                      jnp.asarray(onehot))
        self.compactions[r] = 0
        self.temp_table_peak_bytes[r] = 0
        self._fold_read()
        for f in IoStats._fields:
            self._read_io[f][r] = 0
        self._read_exhausted[r] = False
        # --- pure drain of the source replica (cold tier, then hot) ------
        move_dev = self._rep_move(np.ones((self.S, self.n_buckets), bool))
        do = np.zeros((self.R, self.S), bool)
        do[h] = True
        hb, ht, cb, ct, *_ = self._bounds()
        parts = []
        for tier, begins, tails in (("cold", cb, ct), ("hot", hb, ht)):
            n = np.where(do, tails - begins, 0)
            until = jnp.asarray(tails, jnp.int32)
            n_steps = int(-(-int(n.max()) // Bm)) if n.max() > 0 else 0
            for i in range(n_steps):
                starts = begins + i * Bm
                sdo = jnp.asarray(do & (starts < begins + n))
                sj = jnp.asarray(starts, jnp.int32)
                if tier == "cold":
                    k, v, take = self._pure_drain_cold(self.state, sj,
                                                       until, move_dev, sdo)
                    tomb = None
                else:
                    k, v, tomb, take = self._pure_drain_hot(
                        self.state, sj, until, move_dev, sdo)
                take_np = np.asarray(take)[h]
                if not take_np.any():
                    continue
                k_np = np.asarray(k)[h][take_np]
                v_np = np.asarray(v)[h][take_np]
                if tomb is None:
                    ops_np = np.full(len(k_np), OP_UPSERT, np.int32)
                else:
                    ops_np = np.where(np.asarray(tomb)[h][take_np],
                                      OP_DELETE, OP_UPSERT).astype(np.int32)
                parts.append((k_np, v_np, ops_np))
        # --- replay into r only, scheduler restricted to r ----------------
        if parts:
            keys_all = np.concatenate([p[0] for p in parts])
            vals_all = np.concatenate([p[1] for p in parts])
            ops_all = np.concatenate([p[2] for p in parts])
        else:
            keys_all = np.zeros(0, np.int32)
            vals_all = np.zeros((0, V), np.int32)
            ops_all = np.zeros(0, np.int32)
        n_moved = len(keys_all)
        self.alive[r] = True
        self._migrating = True          # replay lanes are not client traffic
        self._resync_only = r
        try:
            for off in range(0, n_moved, Bm):
                ks = keys_all[off:off + Bm]
                pad = Bm - len(ks)
                ks = np.pad(ks, (0, pad))
                os_ = np.pad(ops_all[off:off + Bm], (0, pad),
                             constant_values=OP_NOOP)
                vs = np.pad(vals_all[off:off + Bm], ((0, pad), (0, 0)))
                self.apply(ks, os_, vs, _rep_do=onehot)
                faults.maybe_crash("resync.mid_replay")
        finally:
            self._resync_only = None
            self._migrating = False
            rs_span.__exit__(None, None, None)
        self.resyncs += 1
        self.resynced_records += n_moved
        obs.journal.emit("replica.resynced", facade=self._obs_facade,
                         replica=r, records=n_moved)
        obs.count("f2_replica_resyncs_total", facade=self._obs_facade)
        return n_moved

    # -- reporting ------------------------------------------------------------
    def io_stats(self) -> dict:
        """Cluster totals: fan-in I/O is charged on every alive replica
        (replication's real write amplification), fan-out read I/O is the
        host-side per-replica accounting."""
        out = super().io_stats()
        self._fold_read()
        out["read_bytes"] += int(self._read_io["read_blocks"].sum()) \
            * BLOCK_BYTES
        out["read_ops"] += int(self._read_io["read_ops"].sum())
        out["mem_hits"] += int(self._read_io["mem_hits"].sum())
        return out

    def replica_stats(self) -> dict:
        """Per-replica serving telemetry: liveness, read-load EWMA, served
        read I/O, and the lifecycle counters."""
        self._fold_read()
        return dict(
            n_replicas=self.R,
            alive=self.alive.tolist(),
            read_selector=self.read_selector,
            replica_load=np.round(self._replica_load, 2).tolist(),
            read_ops=self._read_io["read_ops"].sum(axis=1).tolist(),
            mem_hits=self._read_io["mem_hits"].sum(axis=1).tolist(),
            drops=self.drops,
            resyncs=self.resyncs,
            resynced_records=self.resynced_records,
        )

    def _stats_tree(self) -> dict:
        """The nested KVProtocol telemetry tree, with the per-replica
        sub-dict added (liveness, load EWMA, lifecycle counters); the
        inherited `stats()` folds it under the `replicated` facade."""
        out = super()._stats_tree()
        out["replicas"] = self.replica_stats()
        return out

    # shard_stats is inherited: the base assembles it through `_host_view`,
    # which picks the primary alive replica's rows here — fills/records at
    # client level, traffic already counted once per client lane.

    def memory_model_bytes(self) -> dict:
        return {k: v * self.R for k, v in super().memory_model_bytes().items()}

    def check_invariants(self):
        """Every ShardedKV invariant, per (replica, shard); fan-out read
        chain-walk exhaustion (accounted host-side) is checked too."""
        st = self.state
        (h_of, c_of, i_of, wex, hb, ht, cb, ct) = jax.device_get(
            (st.hot.overflowed, st.cold.overflowed, st.cold_idx.overflowed,
             st.walk_exhausted, st.hot.begin, st.hot.tail, st.cold.begin,
             st.cold.tail))
        self._fold_read()
        wex = np.asarray(wex) | self._read_exhausted
        for r in range(self.R):
            for s in range(self.S):
                at = f"replica {r} shard {s}"
                assert not bool(h_of[r, s]), f"{at}: hot log ring overflow"
                assert not bool(c_of[r, s]), f"{at}: cold log ring overflow"
                assert not bool(i_of[r, s]), \
                    f"{at}: chunk log overwrote live chunk"
                assert not bool(wex[r, s]), \
                    f"{at}: hash chain exceeded chain_max"
                assert int(hb[r, s]) <= int(ht[r, s]), \
                    f"{at}: hot begin > tail"
                assert int(cb[r, s]) <= int(ct[r, s]), \
                    f"{at}: cold begin > tail"
