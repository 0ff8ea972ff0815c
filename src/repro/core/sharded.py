"""ShardedKV: S independent F2 stores driven by one program (horizontal
partitioning — the tensorized analogue of "more cores" in the paper's
scaling story, ROADMAP north star).

State model
-----------
`ShardedF2State` is structurally an `F2State` whose every leaf carries a
leading shard axis: per-shard states stacked with `jax.vmap` of
`store.create`.  Because `F2State` is a pure int32 pytree and every store
entry point is pure jnp, lifting with `jax.vmap` is *bit-exact* with
running S independent stores — the parity suite (tests/test_sharded.py)
enforces exactly that.

Batch flow
----------
`apply` routes one B-lane batch through `shard_router` into S fixed-width
slabs, executes `vmap(store.apply)` over the stacked state, and inverse-
gathers statuses/values back to original lane order.  With the default
`lanes=None` every batch routes in one round (slab width = B) and the
semantics are exactly one `store.apply` per shard.  A smaller `lanes`
caps per-shard slab width: over-capacity lanes are deferred to follow-up
rounds (rounds execute in order; per-key order is preserved because equal
keys share a shard and routing is stable).

Compaction scheduler
--------------------
The scalar trigger loop of `api.KV.maybe_compact` becomes a *vectorized
pressure scheduler*: each tier's per-shard tail-occupancy fills are
computed in a single device_get (re-read between tiers so compaction
cascades fire in-pass, like KV), and hot->cold / cold->cold / chunk-GC
steps run **masked** —
one vmapped call advances every over-threshold shard while under-threshold
shards pass through untouched (a per-shard `do` flag selects old vs new
state, so an idle shard's counters, stats and truncation markers are
byte-identical to never having compacted).

Dispatch
--------
`dispatch="vmap"` (default on one device) runs the stacked state on a
single device.  `dispatch="shard_map"` partitions the shard axis across a
1-D device mesh via `jax.shard_map` (each device vmaps its local shards;
there is no cross-shard communication, so the program is embarrassingly
parallel); the state is created already split over the mesh
(`NamedSharding` on the shard axis), so each device holds only its own
shards.  `dispatch="auto"` picks shard_map when more
than one device is visible and S divides across them, else vmap.  The
shard_map path also runs on a single-device mesh, so CPU CI exercises the
same code multi-device deployments use.

Live rebalancing
----------------
Keys route through a bucket -> shard indirection table
(`shard_router.bucket_of` + `self.bucket_map`; the default map is
byte-identical to hash-top-bits routing).  The routed step accumulates
per-bucket traffic device-side; `maybe_rebalance()` — run next to the
pressure scheduler — folds it into an EWMA, and when the max/mean shard
imbalance crosses the configured threshold, plans bucket moves and
migrates them live: drain the source shard with the compaction-style
liveness walk, purge the moved bucket's source records (META_INVALID),
flip the indirection entry, and replay the drained records as ordinary
routed writes.  All of it is masked vmapped steps, so shards not
involved in a migration stay byte-identical (`core.rebalance`).
"""
from __future__ import annotations

import functools
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from . import (compaction, host_tier, probe_engine, rebalance, shard_router,
               store)
from . import cold_index as _cold_index
from .rebalance import RebalanceConfig
from repro import obs
from repro.testing import faults
from .types import (BLOCK_BYTES, OP_DELETE, OP_NOOP, OP_READ, OP_RMW,
                    OP_UPSERT, F2Config)

DISPATCHES = ("auto", "vmap", "shard_map")
SHARD_AXIS = "shards"


def create(cfg: F2Config, n_shards: int) -> store.F2State:
    """ShardedF2State: per-shard F2States stacked on a leading axis."""
    return jax.vmap(lambda _: store.create(cfg))(jnp.arange(n_shards))


_select = rebalance._select      # per-shard masked state update (one def)


# -- single-shard masked kernels (vmapped by ShardedKV) ----------------------

def _masked_hc_step(cfg, B, state, start, until, do):
    s2, n = compaction.hot_cold_step(cfg, state, start, until, B)
    return _select(do, s2, state), jnp.where(do, n, 0)


def _masked_cc_step(cfg, B, state, start, until, do):
    s2, n = compaction.cold_cold_step(cfg, state, start, until, B)
    return _select(do, s2, state), jnp.where(do, n, 0)


def _masked_sl_step(cfg, B, charge_walk_io, state, start, until, do):
    s2, n = compaction.single_log_lookup_step(
        cfg, state, start, until, B, charge_walk_io=charge_walk_io)
    return _select(do, s2, state), jnp.where(do, n, 0)


def _masked_hot_trunc(cfg, state, until, do):
    return _select(do, compaction.hot_truncate(cfg, state, until), state)


def _masked_cold_trunc(cfg, state, until, do):
    return _select(do, compaction.cold_truncate(cfg, state, until), state)


def _masked_full_scan(cfg, state, do):
    return _select(do, compaction.charge_full_scan(cfg, state), state)


# masked resumable cold-cold kernels (host tier; see compaction.cc_commit):
# unselected shards keep a clean all(-1) demand and pass state through
# untouched, so idle shards stay byte-identical

def _masked_cc_fplan(cfg, B, state, start, until, do):
    miss = compaction.plan_cc_frontier(cfg, state, start, until, B)
    return jnp.where(do, miss, jnp.int32(-1))


def _masked_cc_walk(cfg, B, state, start, until, carry, do):
    s2, c2 = compaction.cc_walk_round(cfg, state, start, until, carry, B)
    c2 = c2._replace(missed=jnp.where(do, c2.missed, jnp.int32(-1)))
    return _select(do, s2, state), c2


def _masked_cc_commit(cfg, B, state, start, until, carry, do):
    s2, n = compaction.cc_commit(cfg, state, start, until, carry, B)
    return _select(do, s2, state), jnp.where(do, n, 0)


def _masked_chunk_gc(cfg, state, do):
    ci, stats = _cold_index.compact_chunklog(state.cold_idx, cfg, state.stats)
    return _select(do, state._replace(cold_idx=ci, stats=stats), state)


def bucket_counts(rt: shard_router.Route, n_buckets: int) -> jax.Array:
    """Per-bucket placed-lane counts: the device-side half of the
    rebalancer's traffic stats (shared by the write + read steps)."""
    bidx = jnp.where(rt.placed, rt.bucket, jnp.int32(n_buckets))
    return jnp.zeros((n_buckets,), jnp.int32).at[bidx].add(1, mode="drop")


def resolve_mesh(dispatch: str, n_shards: int) -> Optional[Mesh]:
    """None -> plain vmap; a 1-D Mesh -> shard_map over the shard axis."""
    assert dispatch in DISPATCHES, f"unknown dispatch {dispatch!r}"
    devs = jax.devices()
    if dispatch == "vmap" or (dispatch == "auto" and len(devs) == 1):
        return None
    # largest device count that divides S evenly (1 is always valid)
    ndev = max(d for d in range(1, min(len(devs), n_shards) + 1)
               if n_shards % d == 0)
    return Mesh(np.asarray(devs[:ndev]), (SHARD_AXIS,))


class ShardedKV:
    """API-compatible with `api.KV` (apply/upsert/read/rmw/delete,
    check_invariants, io_stats, memory_model_bytes, compact_*), holding S
    hash-partitioned shards behind one deterministic batch router."""

    _obs_facade = "sharded"

    def __init__(
        self,
        cfg: F2Config,
        n_shards: int,
        mode: str = "f2",
        trigger: float = 0.8,
        compact_frac: float = 0.1,
        compact_batch: int = 2048,
        faster_compaction: str = "scan",
        donate: bool = True,
        dispatch: str = "auto",
        lanes: Optional[int] = None,
        n_buckets: Optional[int] = None,
        rebalance_cfg: Optional[RebalanceConfig] = None,
    ):
        assert mode in ("f2", "faster")
        assert n_shards >= 1 and (n_shards & (n_shards - 1)) == 0, \
            f"n_shards={n_shards} not a power of 2"
        if mode == "faster":
            assert cfg.rc_capacity >= 1
        self.cfg = cfg
        self.S = n_shards
        self.mode = mode
        self.trigger = trigger
        self.compact_frac = compact_frac
        self.compact_batch = compact_batch
        self.faster_compaction = faster_compaction
        self.lanes = lanes
        self.write_lanes = 0     # widest per-shard mutate slab run
        self.mesh = self._resolve_mesh(dispatch)
        self.dispatch = "vmap" if self.mesh is None else "shard_map"
        self.state = self._create_state()
        self.compactions = np.zeros(self._lead_shape, np.int64)
        self.temp_table_peak_bytes = np.zeros(self._lead_shape, np.int64)
        self.frontier_bytes = compact_batch * cfg.record_bytes
        self.rounds = 0                 # routed rounds executed (telemetry)
        self.last_occupancy = np.zeros(n_shards, np.int64)  # last round's

        # -- rebalancer state (the indirection table always exists; with
        #    the default map routing is byte-identical to hash top bits) --
        self.rb = rebalance_cfg
        bps = (rebalance_cfg.buckets_per_shard if rebalance_cfg is not None
               else 8)
        self.n_buckets = n_buckets or n_shards * bps
        nb = self.n_buckets
        assert nb >= n_shards and (nb & (nb - 1)) == 0, \
            f"n_buckets={nb} not a power of 2 >= n_shards"
        self.bucket_map = shard_router.default_bucket_map(n_shards, nb)
        self._bucket_map_dev = jnp.asarray(self.bucket_map)  # flip-cached
        self._traffic_ewma = np.zeros(nb, np.float64)
        self._routed_lanes = np.zeros(n_shards, np.int64)   # cumulative
        self._pending = []              # unfolded (occ, bcounts) rounds
        self.migrations = 0             # migrate() passes that moved >= 1
        self.migrated_buckets = 0
        self.migrated_records = 0
        self._migrating = False
        self._last_rb_round = 0
        # -- durability hook: `core.durability.DurableKV` installs a WAL
        #    writer here; every client batch logs its full input slab ONCE
        #    (write-ahead: `apply` before its deferral loop, `apply_round`
        #    when driven directly) and migrate() logs a self-contained MAP
        #    record.  The bucket map cannot change mid-batch (the rebalance
        #    check runs after the deferral loop), so the round sequence is
        #    a pure function of (batch, map, lanes) and replay re-derives
        #    it — deferral rounds are never re-logged.  map_version counts
        #    bucket-map flips; WAL headers carry it so recovery can assert
        #    replay stays in lockstep with the log. --
        self.wal = None
        self._wal_defer = False     # True inside apply(): rounds are covered
        self.map_version = 0
        self._decay = rebalance_cfg.decay if rebalance_cfg else 0.9
        mig_batch = (rebalance_cfg.migrate_batch if rebalance_cfg
                     else min(compact_batch, 256))

        dn = dict(donate_argnums=0) if donate else {}
        admit = (mode == "f2") and cfg.rc_capacity > 1
        self._build_router_steps(dn, admit)
        self._drain_hot = jax.jit(self._lift(functools.partial(
            rebalance.drain_hot_step, cfg, mig_batch, nb), n_in=5), **dn)
        self._drain_cold = jax.jit(self._lift(functools.partial(
            rebalance.drain_cold_step, cfg, mig_batch, nb), n_in=5), **dn)
        self._purge = jax.jit(self._lift(functools.partial(
            rebalance.purge_step, cfg, nb), n_in=3), **dn)
        self._mig_batch = mig_batch
        self._hc_step = jax.jit(self._lift(functools.partial(
            _masked_hc_step, cfg, compact_batch), n_in=4), **dn)
        self._cc_step = jax.jit(self._lift(functools.partial(
            _masked_cc_step, cfg, compact_batch), n_in=4), **dn)
        self._sl_step = jax.jit(self._lift(functools.partial(
            _masked_sl_step, cfg, compact_batch,
            faster_compaction == "lookup"), n_in=4), **dn)
        self._hot_trunc = jax.jit(self._lift(functools.partial(
            _masked_hot_trunc, cfg), n_in=3), **dn)
        self._cold_trunc = jax.jit(self._lift(functools.partial(
            _masked_cold_trunc, cfg), n_in=3), **dn)
        self._full_scan = jax.jit(self._lift(functools.partial(
            _masked_full_scan, cfg), n_in=2), **dn)
        self._chunk_gc = jax.jit(self._lift(functools.partial(
            _masked_chunk_gc, cfg), n_in=2), **dn)

        # -- host tier: lifted movement kernels + per-shard chunk stores ----
        self._ht = None
        if cfg.host_tier:
            assert mode == "f2", "host_tier requires mode='f2'"
            assert rebalance_cfg is None, \
                "host_tier is incompatible with live rebalancing (bucket " \
                "migration would have to move host-resident chunks)"
            assert (cfg.host_cache_chunks * cfg.host_chunk_records
                    >= compact_batch + 4 * cfg.host_chunk_records), (
                "host_cache_chunks * host_chunk_records must cover "
                "compact_batch plus chain headroom (>= compact_batch + "
                "4 * host_chunk_records)")
            self._cc_fplan = jax.jit(self._lift(functools.partial(
                _masked_cc_fplan, cfg, compact_batch), n_in=4))
            self._cc_winit = jax.jit(self._lift(functools.partial(
                compaction.cc_walk_init, cfg, B=compact_batch), n_in=3))
            self._cc_walk = jax.jit(self._lift(functools.partial(
                _masked_cc_walk, cfg, compact_batch), n_in=5), **dn)
            self._cc_commit = jax.jit(self._lift(functools.partial(
                _masked_cc_commit, cfg, compact_batch), n_in=5), **dn)
            slab = 8
            self._ht = host_tier.HostTier(
                cfg, n_shards=n_shards,
                install=jax.jit(self._lift(host_tier.install_chunks,
                                           n_in=8), **dn),
                extract=jax.jit(self._lift(functools.partial(
                    host_tier.extract_chunks, cfg, slab), n_in=2)),
                commit=jax.jit(self._lift(host_tier.demote_commit,
                                          n_in=2), **dn),
                drop=jax.jit(self._lift(functools.partial(
                    host_tier.drop_dead_rows, cfg), n_in=1), **dn),
                extract_slab_chunks=slab,
                obs_facade=self._obs_facade)

    # -- subclass hooks (the replica axis lives in core.replication) ----------
    @property
    def _lead_shape(self) -> tuple:
        """Leading axes of the stacked state / per-store host counters:
        (S,) here, (R, S) for the replicated subclass."""
        return (self.S,)

    def _resolve_mesh(self, dispatch: str) -> Optional[Mesh]:
        return resolve_mesh(dispatch, self.S)

    def _create_state(self) -> store.F2State:
        return self._create_on_mesh(functools.partial(create, self.cfg,
                                                      self.S))

    def _create_on_mesh(self, make) -> store.F2State:
        """Build the stacked state; under shard_map, directly in its
        partitioned layout (no full copy on one device first)."""
        if self.mesh is None:
            return make()
        sharding = NamedSharding(self.mesh, P(*self.mesh.axis_names))
        return jax.jit(make, out_shardings=sharding)()

    def _sched_mask(self, shards: np.ndarray) -> np.ndarray:
        """Restrict scheduler/compaction passes (replication masks dead or
        resyncing replicas here); identity for the plain sharded store."""
        return shards

    def _rep_shard(self, m: np.ndarray) -> np.ndarray:
        """Broadcast a client-level per-shard mask/array to the lifted
        leading shape (replication prepends the replica axis)."""
        return m

    def _rep_move(self, move: np.ndarray) -> jax.Array:
        """Lift a [S, n_buckets] bucket-move mask to device, shaped for the
        lifted migration kernels."""
        return jnp.asarray(move)

    def _host_view(self, x) -> np.ndarray:
        """Collapse a lifted per-store output to client level (replication
        returns the primary replica's rows)."""
        return np.asarray(x)

    def _build_router_steps(self, dn: dict, admit: bool):
        """Build the jitted routed write/read steps (`self._step`,
        `self._read_step`).  The replicated subclass overrides this with
        fan-in/fan-out variants over the replica axis."""
        cfg, nb = self.cfg, self.n_buckets
        apply_lifted = self._lift(
            functools.partial(store.apply, cfg, admit_rc=admit), n_in=4)

        def routed_step(state, keys, ops, vals, bmap):
            W = self.lanes or keys.shape[0]
            skeys, sops, svals, rt = shard_router.route(
                keys, ops, vals, self.S, W, bucket_map=bmap)
            state, sstatus, srvals = apply_lifted(state, skeys, sops, svals)
            status, rvals = shard_router.unroute(rt, sstatus, srvals)
            return (state, status, rvals, rt.placed, rt.deferred,
                    rt.occupancy, bucket_counts(rt, nb))

        self._step = jax.jit(routed_step, **dn)

        # dedicated read path (like KV._read): no write engine, and the
        # caller does not run the compaction scheduler afterwards
        read_lifted = self._lift(
            functools.partial(store.read_batch, cfg, admit_rc=admit),
            n_in=3)

        def routed_read(state, keys, ops, bmap):
            W = self.lanes or keys.shape[0]
            vals = jnp.zeros((keys.shape[0], cfg.value_width), jnp.int32)
            skeys, sops, _, rt = shard_router.route(
                keys, ops, vals, self.S, W, bucket_map=bmap)
            state, sstatus, srvals = read_lifted(state, skeys,
                                                 sops == OP_READ)
            status, rvals = shard_router.unroute(rt, sstatus, srvals)
            return (state, status, rvals, rt.placed, rt.deferred,
                    rt.occupancy, bucket_counts(rt, nb))

        self._read_step = jax.jit(routed_read, **dn)

        if not cfg.host_tier:
            return

        # pure pre-fault planner for a routed write round: same router,
        # per-shard `store.plan_fetch`; never donates (plan then promote)
        plan_lifted = self._lift(
            functools.partial(store.plan_fetch, cfg), n_in=3)

        def routed_plan(state, keys, ops, vals, bmap):
            W = self.lanes or keys.shape[0]
            skeys, sops, _, _rt = shard_router.route(
                keys, ops, vals, self.S, W, bucket_map=bmap)
            return plan_lifted(state, skeys, sops)

        self._plan_routed = jax.jit(routed_plan)

        # deferring read path: per-shard missed slabs come back for the
        # promote loop, plus a lane-level view to pick the served lanes
        readh_lifted = self._lift(
            functools.partial(store.read_batch_host, cfg, admit_rc=admit),
            n_in=3)

        def routed_read_host(state, keys, ops, bmap):
            W = self.lanes or keys.shape[0]
            vals = jnp.zeros((keys.shape[0], cfg.value_width), jnp.int32)
            skeys, sops, _, rt = shard_router.route(
                keys, ops, vals, self.S, W, bucket_map=bmap)
            state, sstatus, srvals, smissed = readh_lifted(
                state, skeys, sops == OP_READ)
            status, rvals = shard_router.unroute(rt, sstatus, srvals)
            lane_miss, _ = shard_router.unroute(rt, smissed, srvals)
            lane_miss = jnp.where(rt.placed, lane_miss, jnp.int32(-1))
            return (state, status, rvals, smissed, lane_miss, rt.placed,
                    rt.deferred, rt.occupancy, bucket_counts(rt, nb))

        self._read_step_host = jax.jit(routed_read_host, **dn)

    def _lift(self, fn, n_in: int):
        """vmap over the shard axis; under shard_map additionally partition
        that axis across the device mesh (every in/out leaf is sharded on
        its leading axis; shards never communicate)."""
        vf = jax.vmap(fn)
        if self.mesh is None:
            return vf
        return jax.shard_map(vf, mesh=self.mesh,
                             in_specs=(P(SHARD_AXIS),) * n_in,
                             out_specs=P(SHARD_AXIS), check_vma=False)

    def _note_round(self, occ, bcounts):
        """Record one routed round's traffic (the scatter-add ran
        device-side inside the step).  The tiny count arrays are queued
        and folded into the host EWMA lazily (`_fold_traffic`) so the
        routed hot paths add no device->host sync.  Migration-replay
        rounds DO count as executed rounds (`self.rounds` — replay is
        real work and benchmarks must see its cost) but are excluded
        from the *traffic signal* (EWMA / routed_lanes), so internal
        replay lanes cannot tilt the planner or the measured client
        imbalance."""
        self.last_occupancy = occ
        self.rounds += 1
        if self._migrating:
            return
        self._pending.append((occ, bcounts))
        if len(self._pending) >= 128:   # bound queue growth when stats
            self._fold_traffic()        # are never read

    def _fold_traffic(self):
        """Drain queued rounds into the EWMA / lane totals (one host
        transfer for the whole queue, in round order — values identical
        to folding eagerly every round)."""
        if not self._pending:
            return
        with obs.span("sharded.fold", cat="serve",
                      rounds=len(self._pending)):
            pending, self._pending = jax.device_get(self._pending), []
            for occ_np, bc_np in pending:
                self._routed_lanes += np.asarray(occ_np).astype(np.int64)
                self._traffic_ewma = (self._decay * self._traffic_ewma
                                      + np.asarray(bc_np))
            if obs.enabled():       # mirror the folded traffic signal
                obs.gauge_set("f2_bucket_traffic_ewma",
                              self._traffic_ewma.tolist(),
                              help="per-bucket routed-traffic EWMA",
                              facade=self._obs_facade)
                obs.gauge_set("f2_routed_lanes",
                              self._routed_lanes.tolist(),
                              help="cumulative routed lanes per shard",
                              facade=self._obs_facade)
                obs.rules.maybe_evaluate()  # alert pass at the fold point

    @property
    def traffic_ewma(self) -> np.ndarray:
        self._fold_traffic()
        return self._traffic_ewma.copy()    # folding mutates the internal

    @property
    def routed_lanes(self) -> np.ndarray:
        self._fold_traffic()
        return self._routed_lanes.copy()    # folding mutates the internal

    # -- batched operations --------------------------------------------------
    def _coerce(self, keys, ops, vals):
        """Device arrays for a mutate batch; notes its per-shard slab
        width for `stats()["engines"]`."""
        keys = jnp.asarray(keys, jnp.int32)
        self.write_lanes = max(self.write_lanes,
                               self.lanes or int(keys.shape[0]))
        ops = jnp.asarray(ops, jnp.int32)
        if vals is None:
            vals = jnp.zeros((keys.shape[0], self.cfg.value_width), jnp.int32)
        else:
            vals = jnp.asarray(vals, jnp.int32)
        return keys, ops, vals

    def apply_round(self, keys, ops, vals=None):
        """Exactly ONE routed round — route, lifted apply, inverse-gather,
        then a pressure-scheduler pass.  Returns (status [B], vals [B, V],
        placed [B], deferred [B]) as device arrays with no host sync:
        lanes beyond a shard's slab width come back `deferred` and were
        NOT executed.  This is the entry the session scheduler drives (it
        packs <= `lanes` ops per shard, so its rounds never defer); `apply`
        is the synchronous loop over it.  The rebalance check is per
        *batch*, not per round — callers run `maybe_rebalance()` at their
        own batch boundary."""
        keys, ops, vals = self._coerce(keys, ops, vals)
        with obs.span("sharded.apply_round", cat="serve",
                      B=int(keys.shape[0]), round=self.rounds):
            if (self.wal is not None and not self._migrating
                    and not self._wal_defer):
                # write-ahead: the round's full input is durable before it
                # executes (internal migration/resync replay is NOT logged
                # — it reconstructs data the log already covers; `apply`
                # logs its whole batch itself and re-derives the deferral
                # rounds)
                self.wal.log_slab(keys, ops, vals, self.map_version)
            if self._ht is not None:
                # pre-fault every host chunk this round would touch (routed
                # writes cannot defer mid-step, exactly like KV.apply)
                self.state = self._ht.ensure(
                    self.state, lambda st: self._plan_routed(
                        st, keys, ops, vals, self._bucket_map_dev))
            (self.state, status, rvals, placed, deferred,
             occ, bc) = self._step(self.state, keys, ops, vals,
                                   self._bucket_map_dev)
            if self._ht is not None:
                self._ht.end_batch()
            self._note_round(occ, bc)
            self.maybe_compact()
        return status, rvals, placed, deferred

    def apply(self, keys, ops, vals=None):
        """Route, execute, inverse-gather.  With lanes=None this is one
        round (bit-exact with one store.apply per shard); with a narrower
        slab, over-capacity lanes defer to follow-up rounds, each followed
        by a scheduler pass, until every lane has executed."""
        keys, ops, vals = self._coerce(keys, ops, vals)
        B = keys.shape[0]
        if self.lanes is None or self.lanes >= B:
            # single-round fast path: deferral is impossible, so no host
            # round-trips of per-lane results (the serving hot path)
            status, rvals, _placed, _deferred = self.apply_round(keys, ops,
                                                                 vals)
            obs.observe("f2_deferral_rounds", 1, buckets=obs.COUNT_BUCKETS,
                        help="routed rounds needed per client batch",
                        facade=self._obs_facade, path="apply")
            self.maybe_rebalance()
            return status, rvals
        # write-ahead ONCE for the whole batch: the map is frozen until
        # the post-loop rebalance check, so the deferral rounds below are
        # a pure function of (batch, map, lanes) that replay re-derives
        if self.wal is not None and not self._migrating:
            self.wal.log_slab(keys, ops, vals, self.map_version)
        status = np.zeros(B, np.int32)
        rvals = np.zeros((B, self.cfg.value_width), np.int32)
        cur_ops = ops
        self._wal_defer = True
        n_rounds = 0
        t_defer = None          # set when round 1 leaves lanes deferred
        try:
            for _ in range(B + 1):      # each round places >= 1 lane
                st_r, rv_r, placed, deferred = self.apply_round(keys,
                                                                cur_ops,
                                                                vals)
                n_rounds += 1
                placed_np = np.asarray(placed)
                status = np.where(placed_np, np.asarray(st_r), status)
                rvals = np.where(placed_np[:, None], np.asarray(rv_r),
                                 rvals)
                deferred_np = np.asarray(deferred)
                if not deferred_np.any():
                    break
                if t_defer is None and obs.enabled():
                    t_defer = time.perf_counter()
                cur_ops = jnp.where(jnp.asarray(deferred_np), ops,
                                    jnp.int32(OP_NOOP))
        finally:
            self._wal_defer = False
        if t_defer is not None:
            obs.observe_phase("deferral", time.perf_counter() - t_defer)
        obs.observe("f2_deferral_rounds", n_rounds,
                    buckets=obs.COUNT_BUCKETS,
                    help="routed rounds needed per client batch",
                    facade=self._obs_facade, path="apply")
        # the rebalance check runs once per batch, after every routed
        # round has executed (a mid-batch map flip would re-route lanes
        # that were already deferred under the old map — harmless, but
        # one check per batch keeps migrations at batch boundaries; it is
        # also what makes the once-per-batch WAL record sound)
        self.maybe_rebalance()
        return jnp.asarray(status), jnp.asarray(rvals)

    def upsert(self, keys, vals):
        ops = jnp.full((len(keys),), OP_UPSERT, jnp.int32)
        return self.apply(keys, ops, vals)

    def read(self, keys):
        """Routed read-only batch on the read hot path: lifts
        `store.read_batch` per shard (no write-engine pass, no scheduler
        run — state still advances through read-cache admission, exactly
        like KV.read)."""
        keys = jnp.asarray(keys, jnp.int32)
        B = keys.shape[0]
        bmap = self._bucket_map_dev     # re-uploaded only at a map flip
        cur_ops = jnp.full((B,), OP_READ, jnp.int32)
        if self._ht is not None:
            return self._read_host_loop(keys, cur_ops, bmap)
        if self.lanes is None or self.lanes >= B:
            with obs.span("sharded.read", cat="serve", B=B):
                (self.state, status, rvals, _placed, _deferred,
                 occ, bc) = self._read_step(self.state, keys, cur_ops, bmap)
                self._note_round(occ, bc)
            obs.observe("f2_deferral_rounds", 1, buckets=obs.COUNT_BUCKETS,
                        help="routed rounds needed per client batch",
                        facade=self._obs_facade, path="read")
            return status, rvals
        status = np.zeros(B, np.int32)
        rvals = np.zeros((B, self.cfg.value_width), np.int32)
        n_rounds = 0
        t_defer = None
        for _ in range(B + 1):
            with obs.span("sharded.read", cat="serve", B=B):
                (self.state, st_r, rv_r, placed, deferred,
                 occ, bc) = self._read_step(self.state, keys, cur_ops, bmap)
                self._note_round(occ, bc)
            n_rounds += 1
            placed_np = np.asarray(placed)
            status = np.where(placed_np, np.asarray(st_r), status)
            rvals = np.where(placed_np[:, None], np.asarray(rv_r), rvals)
            deferred_np = np.asarray(deferred)
            if not deferred_np.any():
                break
            if t_defer is None and obs.enabled():
                t_defer = time.perf_counter()
            cur_ops = jnp.where(jnp.asarray(deferred_np),
                                jnp.int32(OP_READ), jnp.int32(OP_NOOP))
        if t_defer is not None:
            obs.observe_phase("deferral", time.perf_counter() - t_defer)
        obs.observe("f2_deferral_rounds", n_rounds,
                    buckets=obs.COUNT_BUCKETS,
                    help="routed rounds needed per client batch",
                    facade=self._obs_facade, path="read")
        return jnp.asarray(status), jnp.asarray(rvals)

    def _read_host_loop(self, keys, cur_ops, bmap):
        """Routed reads under the host tier: router deferral and host-chunk
        miss-with-deferral share one retry loop.  A placed lane whose cold
        walk parked on an absent chunk comes back unserved (`lane_miss` >=
        0); the parked chunks are promoted (partial, pinned) and only the
        unserved lanes re-run.  A batch whose combined pinned paths exceed
        `host_cache_chunks` splits into two retried slices instead of
        failing (`f2_cache_contract_splits_total`); the thrash error is
        reserved for a single lane whose own path exceeds the cache."""
        B = keys.shape[0]
        n_active = int((np.asarray(cur_ops) == OP_READ).sum())
        status = np.zeros(B, np.int32)
        rvals = np.zeros((B, self.cfg.value_width), np.int32)
        n_rounds = 0
        t_defer = None
        for _ in range(B + self._ht.max_rounds + 8):
            with obs.span("sharded.read", cat="serve", B=B):
                (self.state, st_r, rv_r, smissed, lane_miss, placed,
                 deferred, occ, bc) = self._read_step_host(
                    self.state, keys, cur_ops, bmap)
                self._note_round(occ, bc)
            n_rounds += 1
            placed_np = np.asarray(placed)
            hmiss = placed_np & (np.asarray(lane_miss) >= 0)
            served = placed_np & ~hmiss
            status = np.where(served, np.asarray(st_r), status)
            rvals = np.where(served[:, None], np.asarray(rv_r), rvals)
            redo = np.asarray(deferred) | hmiss
            if not redo.any():
                break
            if t_defer is None and obs.enabled():
                t_defer = time.perf_counter()
            needs = self._ht.collect(smissed)
            if self._ht.any_missing(needs):
                try:
                    self.state = self._ht.promote(self.state, needs,
                                                  partial=True)
                except host_tier.CacheThrash:
                    # graceful degradation: the batch's combined pinned
                    # walk paths outgrew the chunk cache.  Drop this
                    # batch's pins and serve the unserved lanes in
                    # cache-sized slices — only a SINGLE-lane batch whose
                    # own path exceeds the cache is a real contract breach
                    # (even one unserved lane may be blocked by pins that
                    # belong to its batchmates, so it retries alone with
                    # the whole cache before the error is final).
                    unserved = np.flatnonzero(redo)
                    if n_active <= 1:
                        raise
                    self._ht.end_batch()
                    self._ht.note_contract_split()
                    parts = (np.array_split(unserved, 2)
                             if len(unserved) > 1 else [unserved])
                    for half in parts:
                        hmask = np.zeros(B, np.bool_)
                        hmask[half] = True
                        h_ops = jnp.where(jnp.asarray(hmask),
                                          jnp.int32(OP_READ),
                                          jnp.int32(OP_NOOP))
                        st_h, rv_h = self._read_host_loop(keys, h_ops, bmap)
                        status = np.where(hmask, np.asarray(st_h), status)
                        rvals = np.where(hmask[:, None], np.asarray(rv_h),
                                         rvals)
                    if t_defer is not None:
                        obs.observe_phase("deferral",
                                          time.perf_counter() - t_defer)
                    obs.observe("f2_deferral_rounds", n_rounds,
                                buckets=obs.COUNT_BUCKETS,
                                help="routed rounds needed per client batch",
                                facade=self._obs_facade, path="read")
                    return jnp.asarray(status), jnp.asarray(rvals)
            cur_ops = jnp.where(jnp.asarray(redo), jnp.int32(OP_READ),
                                jnp.int32(OP_NOOP))
        else:
            raise RuntimeError(
                "host tier: sharded read deferral did not converge")
        self._ht.end_batch()
        if t_defer is not None:
            obs.observe_phase("deferral", time.perf_counter() - t_defer)
        obs.observe("f2_deferral_rounds", n_rounds, buckets=obs.COUNT_BUCKETS,
                    help="routed rounds needed per client batch",
                    facade=self._obs_facade, path="read")
        return jnp.asarray(status), jnp.asarray(rvals)

    def rmw(self, keys, deltas):
        ops = jnp.full((len(keys),), OP_RMW, jnp.int32)
        return self.apply(keys, ops, deltas)

    def delete(self, keys):
        ops = jnp.full((len(keys),), OP_DELETE, jnp.int32)
        return self.apply(keys, ops)

    # -- vectorized pressure scheduler ---------------------------------------
    def _bounds(self):
        s = self.state
        with obs.span("compact.bounds", cat="compaction"):
            return [np.asarray(x).astype(np.int64) for x in jax.device_get(
                (s.hot.begin, s.hot.tail, s.cold.begin, s.cold.tail,
                 s.cold_idx.begin, s.cold_idx.tail))]

    def hot_fills(self) -> np.ndarray:
        hb, ht, *_ = self._bounds()
        return (ht - hb) / self.cfg.hot_capacity

    def cold_fills(self) -> np.ndarray:
        _, _, cb, ct, *_ = self._bounds()
        return (ct - cb) / self.cfg.cold_capacity

    def chunklog_fills(self) -> np.ndarray:
        *_, ib, it = self._bounds()
        return (it - ib) / self.cfg.chunklog_capacity

    def hot_fill(self) -> float:        # KV-facade scalar: the hottest shard
        return float(self.hot_fills().max())

    def cold_fill(self) -> float:
        return float(self.cold_fills().max())

    def chunklog_fill(self) -> float:
        return float(self.chunklog_fills().max())

    def maybe_compact(self):
        """Vectorized pressure check: every shard's occupancy on all three
        tiers in ONE device_get (the steady-state no-compaction path costs
        a single host sync), then masked compaction passes over exactly the
        shards above threshold.  Bounds are re-read only after a pass that
        actually ran (like KV.maybe_compact, which reads fresh state per
        tier) so a cascade — hot->cold pushing a cold log or the chunk log
        over its own trigger — compacts in the same scheduler invocation."""
        hb, ht, cb, ct, ib, it = self._bounds()
        hot_over = (ht - hb) / self.cfg.hot_capacity > self.trigger
        if self.mode == "faster":
            if hot_over.any():
                self.compact_single_log(shards=hot_over)
            return
        if hot_over.any():
            self.compact_hot_cold(shards=hot_over)
            # hot->cold appends cold records AND chunk-index versions
            _, _, cb, ct, ib, it = self._bounds()
        # mirror KV.maybe_compact: under the host tier cold-cold GC fires
        # on total span vs the host log budget, not device-ring occupancy
        # (demotion handles ring pressure)
        cold_budget = self.cfg.cold_capacity * (
            self.cfg.host_log_factor if self._ht is not None else 1.0)
        cold_over = (ct - cb) / cold_budget > self.trigger
        if cold_over.any():
            self.compact_cold_cold(shards=cold_over)
            *_, ib, it = self._bounds()
        chunk_over = self._sched_mask(
            (it - ib) / self.cfg.chunklog_capacity > self.trigger)
        if chunk_over.any():
            n_sh = int(chunk_over.sum())
            with obs.span("compact.chunk_gc", cat="compaction", shards=n_sh):
                self.state = self._chunk_gc(self.state,
                                            jnp.asarray(chunk_over))
            obs.journal.emit("compaction.chunk_gc",
                             facade=self._obs_facade, shards=n_sh)
            obs.count("f2_compactions_total", facade=self._obs_facade,
                      kind="chunk_gc")

    def _regions(self, begins, tails, n_records, shards):
        """Per-shard compaction region sizes, mirroring KV._region exactly
        (zero for unselected shards)."""
        avail = np.maximum(tails - begins, 0)
        if n_records is None:
            n = np.maximum(np.minimum(
                (avail * self.compact_frac).astype(np.int64), avail),
                self.compact_batch)
        else:
            n = np.full(begins.shape, int(n_records), np.int64)
        return np.where(shards, np.minimum(n, avail), 0)

    def _masked_steps(self, step, begins, n, shards):
        """Run ceil(max n / compact_batch) masked step calls (the copying
        phase); shard j is live in call i iff begins[j] + i*cb is inside
        its region.  Returns (until [S], per-shard live totals)."""
        until = jnp.asarray(begins + n, jnp.int32)
        cb = self.compact_batch
        n_steps = int(-(-int(n.max()) // cb)) if n.max() > 0 else 0
        live_total = np.zeros(shards.shape, np.int64)
        for i in range(n_steps):
            starts = begins + i * cb
            do = shards & (starts < begins + n)
            if self._ht is not None:
                # each step appends <= compact_batch cold records per shard;
                # keep that much ring headroom by demoting first
                self.state = self._ht.demote_if_needed(
                    self.state, cb + self.cfg.host_chunk_records)
            self.state, n_live = step(self.state,
                                      jnp.asarray(starts, jnp.int32), until,
                                      jnp.asarray(do))
            live_total += np.asarray(n_live).astype(np.int64)
        return until, live_total

    def compact_hot_cold(self, n_records: Optional[int] = None,
                         shards: Optional[np.ndarray] = None):
        hb, ht, *_ = self._bounds()
        shards = np.ones(hb.shape, bool) if shards is None else shards
        shards = self._sched_mask(np.asarray(shards, bool))
        n_sh = int(shards.sum())
        n = self._regions(hb, ht, n_records, shards)
        with obs.span("compact.hot_cold", cat="compaction", shards=n_sh):
            until, _ = self._masked_steps(self._hc_step, hb, n, shards)
            self.state = self._hot_trunc(self.state, until,
                                         jnp.asarray(shards))
        self.compactions += shards.astype(np.int64)
        obs.journal.emit("compaction.hot_cold", facade=self._obs_facade,
                         shards=n_sh)
        obs.count("f2_compactions_total", facade=self._obs_facade,
                  kind="hot_cold")

    def compact_cold_cold(self, n_records: Optional[int] = None,
                          shards: Optional[np.ndarray] = None):
        _, _, cb, ct, *_ = self._bounds()
        shards = np.ones(cb.shape, bool) if shards is None else shards
        shards = self._sched_mask(np.asarray(shards, bool))
        n_sh = int(shards.sum())
        n = self._regions(cb, ct, n_records, shards)
        with obs.span("compact.cold_cold", cat="compaction", shards=n_sh):
            if self._ht is None:
                until, _ = self._masked_steps(self._cc_step, cb, n, shards)
            else:
                until = self._cc_steps_host(cb, n, shards)
            self.state = self._cold_trunc(self.state, until,
                                          jnp.asarray(shards))
            if self._ht is not None:
                self._ht.end_batch()
                self.state = self._ht.gc(self.state)
        self.compactions += shards.astype(np.int64)
        obs.journal.emit("compaction.cold_cold", facade=self._obs_facade,
                         shards=n_sh)
        obs.count("f2_compactions_total", facade=self._obs_facade,
                  kind="cold_cold")

    def _cc_steps_host(self, begins, n, shards):
        """Masked cold-cold copying phase under the host tier: per masked
        step, demote for headroom, pin + ensure each live shard's frontier
        chunks, drain the resumable liveness walk (parked chunks promote
        partial/pin-free between rounds), then commit — the vectorized
        twin of `api.KV._ccstep_host`."""
        until = jnp.asarray(begins + n, jnp.int32)
        until_np = begins + n
        cb = self.compact_batch
        n_steps = int(-(-int(n.max()) // cb)) if n.max() > 0 else 0
        shift = self.cfg.host_chunk_records.bit_length() - 1
        for i in range(n_steps):
            starts = begins + i * cb
            do = shards & (starts < until_np)
            do_dev = jnp.asarray(do)
            sj = jnp.asarray(starts, jnp.int32)
            self._ht.end_batch()
            self.state = self._ht.demote_if_needed(
                self.state, cb + self.cfg.host_chunk_records)
            # pin each live shard's below-floor frontier chunks: `ensure`
            # only pins what it installs, but the commit re-reads the
            # frontier after pin-free walk promotes
            cbg, ctl, cfl = (np.asarray(x).astype(np.int64)
                             for x in jax.device_get(
                                 (self.state.cold.begin,
                                  self.state.cold.tail,
                                  self.state.cold.floor)))
            pins = []
            for s in range(self.S):
                lo = max(int(starts[s]), int(cbg[s]))
                hi = min(int(until_np[s]), int(ctl[s]),
                         int(starts[s]) + cb, int(cfl[s]))
                pins.append(set(range(lo >> shift, ((hi - 1) >> shift) + 1))
                            if do[s] and lo < hi else set())
            self._ht.pin_chunks(pins)
            self.state = self._ht.ensure(
                self.state, lambda st: self._cc_fplan(st, sj, until, do_dev))
            carry = self._cc_winit(self.state, sj, until)
            self.state, carry = self._cc_walk(self.state, sj, until, carry,
                                              do_dev)
            for _ in range(cb * self.cfg.chain_max + 8):
                needs = self._ht.collect(carry.missed)
                if not self._ht.any_missing(needs):
                    break
                self.state = self._ht.promote(self.state, needs,
                                              partial=True, pin=False)
                self.state, carry = self._cc_walk(self.state, sj, until,
                                                  carry, do_dev)
            else:
                raise RuntimeError(
                    "host tier: cold-cold walk did not converge")
            self.state, _ = self._cc_commit(self.state, sj, until, carry,
                                            do_dev)
        return until

    def compact_single_log(self, n_records: Optional[int] = None,
                           shards: Optional[np.ndarray] = None):
        hb, ht, *_ = self._bounds()
        shards = np.ones(hb.shape, bool) if shards is None else shards
        shards = self._sched_mask(np.asarray(shards, bool))
        n_sh = int(shards.sum())
        n = self._regions(hb, ht, n_records, shards)
        with obs.span("compact.single_log", cat="compaction", shards=n_sh):
            until, live_total = self._masked_steps(self._sl_step, hb, n,
                                                   shards)
            if self.faster_compaction == "scan":
                self.state = self._full_scan(self.state, jnp.asarray(shards))
                self.temp_table_peak_bytes = np.maximum(
                    self.temp_table_peak_bytes,
                    np.where(shards,
                             live_total * (self.cfg.record_bytes + 16), 0))
            self.state = self._hot_trunc(self.state, until,
                                         jnp.asarray(shards))
        self.compactions += shards.astype(np.int64)
        obs.journal.emit("compaction.single_log", facade=self._obs_facade,
                         shards=n_sh)
        obs.count("f2_compactions_total", facade=self._obs_facade,
                  kind="single_log")

    # -- live rebalancing (core.rebalance) -----------------------------------
    def shard_stats(self) -> rebalance.ShardStats:
        """The one occupancy/traffic struct: per-shard fills and record
        counts, per-bucket traffic EWMA, and the max/mean imbalance under
        the current bucket map.  `maybe_rebalance` plans from it and the
        benchmarks report from it.  Fills/records go through `_host_view`
        so the struct stays client-level ([S]) under replication."""
        hb, ht, cb, ct, ib, it = self._bounds()
        load = rebalance.shard_loads(self.traffic_ewma, self.bucket_map,
                                     self.S)
        return rebalance.ShardStats(
            hot_fill=self._host_view((ht - hb) / self.cfg.hot_capacity),
            cold_fill=self._host_view((ct - cb) / self.cfg.cold_capacity),
            chunklog_fill=self._host_view(
                (it - ib) / self.cfg.chunklog_capacity),
            records=self._host_view((ht - hb) + (ct - cb)),
            occupancy=np.asarray(self.last_occupancy).astype(np.int64),
            routed_lanes=self.routed_lanes,      # properties return copies
            traffic_ewma=self.traffic_ewma,
            shard_traffic=load,
            imbalance=rebalance.imbalance_of(load),
            bucket_map=self.bucket_map.copy(),
        )

    def _stats_tree(self) -> dict:
        """The raw nested telemetry tree; `stats()` folds it through the
        metrics registry (identity when observability is disabled)."""
        t = dict(
            io=self.io_stats(),
            shards=dict(
                n_shards=self.S,
                rounds=self.rounds,
                **self.shard_stats().to_dict(),
                compactions=self.compactions.tolist(),
                migrations=self.migrations,
                migrated_buckets=self.migrated_buckets,
                migrated_records=self.migrated_records,
            ),
            engines=probe_engine.engine_choices(self.cfg, self.write_lanes),
        )
        if self._ht is not None:
            t["host"] = self._ht.stats()
        return t

    def stats(self) -> dict:
        """The ONE nested telemetry shape every facade speaks (KVProtocol):
        an `io` sub-dict (KV.io_stats totals) plus, per facade, `shards`
        (this class), `replicas` (ReplicatedKV) and `sessions`
        (serve.sessions.KVSessionService) sub-dicts — what an operator
        dashboard polls, what `serve_step.kv_service_stats` returns, and
        what the benches report from.  With observability enabled, every
        leaf is mirrored into `f2_stats_*` gauges labeled by facade."""
        return obs.fold_stats(self._obs_facade, self._stats_tree())

    def maybe_rebalance(self) -> bool:
        """Occupancy-driven trigger, run next to the pressure scheduler:
        every `check_every` routed rounds, plan bucket moves from the
        traffic EWMA and migrate them if the imbalance crossed the
        threshold.  A balanced store plans no moves and is left
        byte-identical (the idempotence half of the migration oracle)."""
        rb = self.rb
        if (rb is None or not rb.enabled or self._migrating
                or self.S == 1):
            return False
        if self.rounds - self._last_rb_round < rb.check_every:
            return False
        self._last_rb_round = self.rounds
        with obs.span("rebalance.check", cat="rebalance"):
            new_map = rebalance.plan_moves(
                self.traffic_ewma, self.bucket_map, self.S,
                threshold=rb.threshold, max_moves=rb.max_moves,
                min_traffic=rb.min_traffic,
                fill=self._fill_signal() if rb.fill_weight > 0 else None,
                fill_weight=rb.fill_weight)
            if new_map is None:
                return False
            self.migrate(new_map)
            return True

    def _fill_signal(self) -> np.ndarray:
        """Per-shard live-region record counts [S] — the occupancy half of
        the fill-aware planner's blended load signal (weight 0 by default,
        in which case this is never computed)."""
        hb, ht, cb, ct, *_ = self._bounds()
        return self._host_view((ht - hb) + (ct - cb)).astype(np.float64)

    def rebalance(self, new_map: Optional[np.ndarray] = None,
                  threshold: Optional[float] = None) -> int:
        """Operator-driven rebalance: migrate to an explicit map, or plan
        one from the current traffic stats.  Returns records moved (0 when
        already balanced — and then the store is byte-identical)."""
        if new_map is None:
            rb = self.rb
            fw = rb.fill_weight if rb else 0.0
            new_map = rebalance.plan_moves(
                self.traffic_ewma, self.bucket_map, self.S,
                threshold=(threshold if threshold is not None
                           else rb.threshold if rb else 1.25),
                max_moves=rb.max_moves if rb else 0,
                min_traffic=rb.min_traffic if rb else 0.0,
                fill=self._fill_signal() if fw > 0 else None,
                fill_weight=fw)
            if new_map is None:
                return 0
        return self.migrate(new_map)

    def migrate(self, new_map: np.ndarray) -> int:
        """Live bucket migration: drain -> (scheduler pass) -> purge ->
        flip -> replay.  See `core.rebalance` for the protocol; shards
        with no moving bucket stay byte-identical through every step.
        Returns the number of records replayed into their new shards."""
        assert self._ht is None, \
            "host_tier does not support live bucket migration"
        new_map = np.asarray(new_map, np.int32)
        assert new_map.shape == (self.n_buckets,), new_map.shape
        assert ((new_map >= 0) & (new_map < self.S)).all(), new_map
        changed = np.flatnonzero(new_map != self.bucket_map)
        if changed.size == 0:
            return 0
        move = shard_router.bucket_moves(self.bucket_map, new_map, self.S)
        do = self._rep_shard(move.any(axis=1))
        move_dev = self._rep_move(move)
        Bm = self._mig_batch
        V = self.cfg.value_width
        self._migrating = True
        try:
            # --- drain: compaction-style liveness frontiers over the
            #     source shards' cold then hot logs (cold first so the
            #     replay linearizes hot versions over cold ones) ----------
            mig_span = obs.span("rebalance.migrate", cat="rebalance",
                                buckets=int(changed.size))
            mig_span.__enter__()
            hb, ht, cb, ct, *_ = self._bounds()
            parts = []              # (keys, vals, ops) np fragments
            for tier, begins, tails in (("cold", cb, ct), ("hot", hb, ht)):
                n = np.where(do, tails - begins, 0)
                until = jnp.asarray(tails, jnp.int32)
                n_steps = int(-(-int(n.max()) // Bm)) if n.max() > 0 else 0
                for i in range(n_steps):
                    starts = begins + i * Bm
                    sdo = jnp.asarray(do & (starts < begins + n))
                    sj = jnp.asarray(starts, jnp.int32)
                    if tier == "cold":
                        (self.state, k, v,
                         take) = self._drain_cold(self.state, sj, until,
                                                  move_dev, sdo)
                        tomb = None
                    else:
                        (self.state, k, v, tomb,
                         take) = self._drain_hot(self.state, sj, until,
                                                 move_dev, sdo)
                    take_np = self._host_view(take)
                    if not take_np.any():
                        continue
                    k_np = self._host_view(k)[take_np]
                    v_np = self._host_view(v)[take_np]
                    if tomb is None:
                        ops_np = np.full(len(k_np), OP_UPSERT, np.int32)
                    else:
                        ops_np = np.where(self._host_view(tomb)[take_np],
                                          OP_DELETE, OP_UPSERT
                                          ).astype(np.int32)
                    parts.append((k_np, v_np, ops_np))
            # --- let a pending pressure pass interleave (the "racing"
            #     compaction of the oracle): the drained snapshot stays
            #     valid — compaction only copies live records and
            #     truncates — and the purge below is by bucket over the
            #     whole arrays, so records that moved hot->cold meanwhile
            #     are still caught ----------------------------------------
            self.maybe_compact()
            if parts:
                keys_all = np.concatenate([p[0] for p in parts])
                vals_all = np.concatenate([p[1] for p in parts])
                ops_all = np.concatenate([p[2] for p in parts])
            else:
                keys_all = np.zeros(0, np.int32)
                vals_all = np.zeros((0, V), np.int32)
                ops_all = np.zeros(0, np.int32)
            n_moved = len(keys_all)
            # --- durability: one self-contained MAP record (new map +
            #     drained payload under a single CRC) goes to the WAL
            #     *before* the destructive purge — recovery either replays
            #     the whole migration or, on a torn record, none of it ----
            if self.wal is not None:
                self.wal.log_map(new_map, self.map_version + 1,
                                 keys_all, ops_all, vals_all)
            # --- purge source copies, then flip the indirection ----------
            self.state = self._purge(self.state, move_dev, jnp.asarray(do))
            self.bucket_map = new_map.copy()
            self._bucket_map_dev = jnp.asarray(self.bucket_map)
            self.map_version += 1
            faults.maybe_crash("migrate.after_flip")
            # --- replay as ordinary routed writes (now land on dst) ------
            for off in range(0, n_moved, Bm):
                ks = keys_all[off:off + Bm]
                pad = Bm - len(ks)
                ks = np.pad(ks, (0, pad))
                os_ = np.pad(ops_all[off:off + Bm], (0, pad),
                             constant_values=OP_NOOP)
                vs = np.pad(vals_all[off:off + Bm], ((0, pad), (0, 0)))
                self.apply(ks, os_, vs)
        finally:
            self._migrating = False
            mig_span.__exit__(None, None, None)
        self.migrations += 1
        self.migrated_buckets += int(changed.size)
        self.migrated_records += n_moved
        obs.journal.emit("rebalance.migrated", facade=self._obs_facade,
                         buckets=int(changed.size), records=n_moved,
                         map_version=self.map_version)
        obs.count("f2_migrations_total", facade=self._obs_facade)
        obs.count("f2_migrated_records_total", n_moved,
                  facade=self._obs_facade)
        return n_moved

    # -- reporting ------------------------------------------------------------
    def io_stats(self) -> dict:
        """KV-compatible totals over all shards."""
        s = self.state.stats
        rb, wb, ro, mh = jax.device_get(
            (s.read_blocks, s.write_blocks, s.read_ops, s.mem_hits))
        return dict(
            read_bytes=int(np.sum(rb)) * BLOCK_BYTES,
            write_bytes=int(np.sum(wb)) * BLOCK_BYTES,
            read_ops=int(np.sum(ro)),
            mem_hits=int(np.sum(mh)),
        )

    def io_stats_per_shard(self) -> dict:
        s = self.state.stats
        rb, wb, ro, mh = jax.device_get(
            (s.read_blocks, s.write_blocks, s.read_ops, s.mem_hits))
        return dict(
            read_bytes=(np.asarray(rb) * BLOCK_BYTES).tolist(),
            write_bytes=(np.asarray(wb) * BLOCK_BYTES).tolist(),
            read_ops=np.asarray(ro).tolist(),
            mem_hits=np.asarray(mh).tolist(),
        )

    def memory_model_bytes(self) -> dict:
        c = self.cfg
        per = dict(
            hot_index=c.hot_index_size * 8,
            hot_log_mem=c.hot_mem * c.record_bytes,
            read_cache=(c.rc_capacity if self.mode == "f2" else 0)
            * c.record_bytes,
            cold_log_mem=(c.cold_mem if self.mode == "f2" else 0)
            * c.record_bytes,
            chunk_index=(c.n_chunks if self.mode == "f2" else 0) * 8,
            chunklog_mem=(c.chunklog_mem if self.mode == "f2" else 0)
            * c.chunk_bytes,
        )
        if self.cfg.host_tier:
            per["host_chunk_cache"] = (
                c.host_cache_chunks * c.host_chunk_records
                * 4 * (3 + c.value_width))
        out = {k: v * self.S for k, v in per.items()}
        out["total"] = sum(out.values())
        if self._ht is not None:
            # the host store is NOT device memory; reported, not totaled
            out["host_store_bytes"] = self._ht.host_bytes()
        return out

    def check_invariants(self):
        """Every invariant of api.KV.check_invariants, per shard."""
        st = self.state
        (h_of, c_of, i_of, wex, hb, ht, cb, ct) = jax.device_get(
            (st.hot.overflowed, st.cold.overflowed, st.cold_idx.overflowed,
             st.walk_exhausted, st.hot.begin, st.hot.tail, st.cold.begin,
             st.cold.tail))
        for s in range(self.S):
            assert not bool(h_of[s]), f"shard {s}: hot log ring overflow"
            assert not bool(c_of[s]), f"shard {s}: cold log ring overflow"
            assert not bool(i_of[s]), \
                f"shard {s}: chunk log overwrote live chunk"
            assert not bool(wex[s]), \
                f"shard {s}: hash chain exceeded chain_max"
            assert int(hb[s]) <= int(ht[s]), f"shard {s}: hot begin > tail"
            assert int(cb[s]) <= int(ct[s]), f"shard {s}: cold begin > tail"
        if self.cfg.host_tier:
            mis, fl = jax.device_get((st.host.missed_in_step, st.cold.floor))
            c = self.cfg.host_chunk_records
            for s in range(self.S):
                assert not bool(np.ravel(mis)[s]), \
                    f"shard {s}: host chunk miss on a committed path " \
                    f"(pre-fault bug)"
                f = int(np.ravel(fl)[s])
                assert f % c == 0, f"shard {s}: floor {f} not chunk-aligned"
                assert 0 <= f <= int(np.ravel(ct)[s]), \
                    f"shard {s}: floor {f} outside [0, tail]"
