"""CPR-style durability for the sharded/replicated store: fuzzy
snapshots + a write-ahead slab log + crash recovery.

`DurableKV` wraps a `ShardedKV` or `ReplicatedKV` and makes it durable
with two on-disk artifacts under one directory:

    <dir>/snap/step_<E>/...        async F2State snapshots (Checkpointer)
    <dir>/wal_<E>.log              one WAL segment per snapshot epoch

**Snapshots** are CPR-style fuzzy checkpoints: the full per-shard
`F2State` pytree plus routing/replication metadata (`bucket_map`,
`map_version`, epoch, next WAL seq, the replica `alive` mask), captured
between rounds and written through the async `Checkpointer` off the step
path.  Taking snapshot E first rotates the WAL to segment E, so segment E
holds exactly the rounds after snapshot E's capture point.

**The WAL** is slab-shaped, not record-shaped: each SLAB record is one
client batch's full input (keys/ops/vals), logged ONCE *before* any of
its routed rounds execute.  Because `shard_router.route` is a pure
function of (batch, bucket_map) and the bucket map is frozen for the
duration of a batch (the rebalance check runs after the deferral loop),
the whole multi-round deferral sequence is a pure function of (batch,
map, lanes) — replay re-derives it round by round, so every lane
executes exactly once across replay (no RMW double-apply) and internal
deferral rounds are never re-logged.  Batches with no write op are
skipped.  Migrations
append one self-contained MAP record — the new bucket map plus the
drained payload under a single CRC, logged after the drain and *before*
the destructive purge — so recovery re-enacts a migration atomically:
a torn MAP record replays as "migration never happened", a complete one
as purge -> flip -> replay, never half of each.

**Recovery** (`recover(dir, make_kv)`) = restore the latest complete
snapshot -> replay the WAL suffix (epochs >= snapshot epoch, seq order,
flipping/purging at MAP records) -> `check_invariants()`.  The result is
*logically* equivalent to the crashed store (read-cache contents and
compaction layout may differ — reads are not logged — but statuses and
values of every subsequent op are bit-exact, the same convergence
contract `resync()` already proves).  Replica semantics: replay fans in
to the replicas alive at the snapshot; replicas that were dead at the
snapshot are revived afterwards by copying the recovered primary's rows
(they are bit-identical by construction).

**Graceful degradation** (`rebuild_replica(r)`): a dropped replica
rebuilds from snapshot + WAL suffix instead of a live `resync()` drain —
the healthy replicas serve zero drain reads; replay is masked to r with
the scheduler restricted to r, exactly resync's discipline.  Segment
reads retry with bounded backoff on I/O errors; a truncated tail record
(length/CRC mismatch) is dropped, not crashed on.
"""
from __future__ import annotations

import os
import struct
import time
import zlib
from dataclasses import dataclass
from typing import Any, Callable, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.checkpoint.checkpointer import Checkpointer
from repro.core import shard_router
from repro.core.types import OP_DELETE, OP_NOOP, OP_RMW, OP_UPSERT
from repro.testing import faults

SEG_MAGIC = b"F2WL"
SEG_VERSION = 2
REC_MAGIC = 0xF25AB10C
REC_SLAB = 1
REC_MAP = 2
_SEG_HDR = struct.Struct("<4sII")          # magic, version, epoch
_REC_HDR = struct.Struct("<IIIIIII")       # magic, type, epoch, seq,
#                                            map_version, payload_len, crc
_PAY_HDR = struct.Struct("<III")           # n_map, batch, value_width


@dataclass
class DurabilityConfig:
    """Deployment shape of the durability layer.

    fsync: "batch" (default) is group commit — appends are buffered
    across a client batch's internal deferral rounds and fsync'd once
    before the batch's statuses are returned, so every *acked* op is
    crash-durable (CPR's commit-point discipline); "always" additionally
    syncs after every routed round; "rotate" syncs only at segment
    rotation / close (a crash may lose the OS-buffered tail — still
    torn-tail safe).  snapshot_every_rounds=0 means manual `snapshot()`
    calls only."""

    dir: str
    snapshot_every_rounds: int = 0
    fsync: str = "batch"               # "batch" | "always" | "rotate"
    keep: int = 3                      # snapshots retained
    segment_retries: int = 3           # bounded retry on torn-segment reads
    retry_backoff: float = 0.01        # seconds, doubled per retry
    revive_dead_replicas: bool = True  # recover(): byte-copy primary rows
    blocking_snapshots: bool = False   # True: snapshot() waits for disk

    def __post_init__(self):
        assert self.fsync in ("batch", "always", "rotate"), self.fsync


class WalRecord(NamedTuple):
    rtype: int            # REC_SLAB | REC_MAP
    epoch: int
    seq: int
    map_version: int      # SLAB: map in effect; MAP: version after flip
    keys: np.ndarray      # int32 [B]
    ops: np.ndarray       # int32 [B]
    vals: np.ndarray      # int32 [B, V]
    new_map: Optional[np.ndarray]   # MAP only: int32 [n_buckets]


def _segment_path(directory: str, epoch: int) -> str:
    return os.path.join(directory, f"wal_{epoch:08d}.log")


def wal_epochs(directory: str) -> List[int]:
    out = []
    for f in os.listdir(directory):
        if f.startswith("wal_") and f.endswith(".log"):
            out.append(int(f[4:-4]))
    return sorted(out)


class WalWriter:
    """Appends slab/map records to the current epoch's segment file."""

    def __init__(self, directory: str, epoch: int = 0, seq: int = 0,
                 fsync: str = "batch"):
        os.makedirs(directory, exist_ok=True)
        self.dir = directory
        self.epoch = int(epoch)
        self.seq = int(seq)          # next record's global sequence number
        self.fsync = fsync
        self._unsynced = 0           # bytes appended, not yet fsync'd
        self._f = open(_segment_path(directory, self.epoch), "ab")
        if self._f.tell() == 0:
            self._f.write(_SEG_HDR.pack(SEG_MAGIC, SEG_VERSION, self.epoch))
            self._f.flush()

    # -- record encoding -------------------------------------------------------
    @staticmethod
    def _encode(keys, ops, vals, new_map=None) -> bytes:
        """Raw little-endian framing: `_PAY_HDR` (n_map, B, V) then the
        int32 arrays back to back.  np.savez's zip container costs ~0.3ms
        per round — two orders of magnitude more than the bytes."""
        keys = np.ascontiguousarray(keys, np.int32)
        ops = np.ascontiguousarray(ops, np.int32)
        vals = np.ascontiguousarray(vals, np.int32)
        nm = (b"" if new_map is None
              else np.ascontiguousarray(new_map, np.int32).tobytes())
        return (_PAY_HDR.pack(len(nm) // 4, len(keys), vals.shape[1])
                + nm + keys.tobytes() + ops.tobytes() + vals.tobytes())

    def _append(self, rtype: int, map_version: int, payload: bytes):
        hdr = _REC_HDR.pack(REC_MAGIC, rtype, self.epoch, self.seq,
                            map_version, len(payload),
                            zlib.crc32(payload) & 0xFFFFFFFF)
        try:
            faults.maybe_crash("wal.mid_append")
        except faults.InjectedCrash:
            # model a torn append: half the record reaches the disk, then
            # the process dies — recovery must drop this tail record
            torn = (hdr + payload)[: _REC_HDR.size + max(1, len(payload) // 2)]
            self._f.write(torn)
            self._f.flush()
            os.fsync(self._f.fileno())
            raise
        self._f.write(hdr)
        self._f.write(payload)
        self._unsynced += len(hdr) + len(payload)
        if self.fsync == "always":
            self._f.flush()
            os.fsync(self._f.fileno())
            self._unsynced = 0
        # otherwise flushed + fsync'd at sync()/close()
        self.seq += 1
        kind = "slab" if rtype == REC_SLAB else "map"
        obs.count("f2_wal_records_total", help="WAL records appended",
                  kind=kind)
        obs.count("f2_wal_bytes_total", _REC_HDR.size + len(payload),
                  help="WAL bytes appended", kind=kind)

    # -- the two record types --------------------------------------------------
    def log_slab(self, keys, ops, vals, map_version: int):
        """One client batch's full input.  Write-free batches (reads/noops
        only) are skipped: they cannot change logical content, and replay
        re-derives any internal deferral rounds from the batch itself.
        The span's `bytes` is the record this batch appends if it holds a
        write."""
        B, V = np.shape(vals)
        with obs.span("wal.log_slab", cat="durability",
                      bytes=_REC_HDR.size + _PAY_HDR.size + 4 * B * (2 + V)):
            ops_np = np.asarray(ops, np.int32)
            writes = ((ops_np == OP_UPSERT) | (ops_np == OP_RMW)
                      | (ops_np == OP_DELETE))
            if not writes.any():
                return
            payload = self._encode(keys, ops_np, vals)
            self._append(REC_SLAB, map_version, payload)

    def log_map(self, new_map, map_version: int, keys, ops, vals):
        """One migration: the post-flip bucket map plus the drained
        payload, atomic under a single CRC.  MAP records are a durable
        barrier in every fsync mode — the destructive purge that follows
        is only safe once the record that re-enacts it is on disk."""
        payload = self._encode(keys, ops, vals, new_map=new_map)
        self._append(REC_MAP, map_version, payload)
        self.sync()

    # -- lifecycle -------------------------------------------------------------
    def sync(self):
        """Group-commit barrier: fsync any buffered appends.  `DurableKV`
        calls this after every client-visible batch, before the statuses
        are returned — an op is acked only once its record is durable.
        No-op when nothing is buffered (e.g. fsync="always").  The span's
        `bytes` counts what was appended since the last sync."""
        if self._unsynced and self._f is not None and not self._f.closed:
            with obs.span("wal.sync", cat="durability",
                          bytes=self._unsynced):
                if obs.enabled():
                    t0 = time.perf_counter()
                    self._f.flush()
                    os.fsync(self._f.fileno())
                    obs.observe("f2_wal_fsync_seconds",
                                time.perf_counter() - t0,
                                help="group-commit fsync latency")
                else:
                    self._f.flush()
                    os.fsync(self._f.fileno())
            self._unsynced = 0

    def rotate(self, new_epoch: int):
        """Start segment `new_epoch`; called at the snapshot capture point
        so segment E holds exactly the rounds after snapshot E."""
        self.close()
        self.epoch = int(new_epoch)
        self._f = open(_segment_path(self.dir, self.epoch), "ab")
        if self._f.tell() == 0:
            self._f.write(_SEG_HDR.pack(SEG_MAGIC, SEG_VERSION, self.epoch))
            self._f.flush()
        obs.journal.emit("wal.segment_rotated", epoch=self.epoch)

    def close(self):
        if self._f is not None and not self._f.closed:
            self._f.flush()
            if self._unsynced:          # clean segments are already durable
                os.fsync(self._f.fileno())
                self._unsynced = 0
            self._f.close()


def _read_file_with_retry(path: str, retries: int, backoff: float) -> bytes:
    """Bounded retry/backoff around segment reads: transient I/O errors
    (e.g. a flaky device) are retried; the last error propagates."""
    delay = backoff
    for attempt in range(max(1, retries)):
        try:
            with open(path, "rb") as f:
                return f.read()
        except OSError:
            if attempt == max(1, retries) - 1:
                raise
            time.sleep(delay)
            delay *= 2


def read_segment(path: str, retries: int = 3, backoff: float = 0.01,
                 ) -> List[WalRecord]:
    """Decode one segment, dropping a torn tail (short header, short
    payload, or CRC mismatch) instead of crashing.  Anything *after* a
    torn record is unreachable by construction (records are appended and
    fsync'd in order), so decoding stops there."""
    raw = _read_file_with_retry(path, retries, backoff)
    out: List[WalRecord] = []
    if len(raw) < _SEG_HDR.size:
        return out                      # torn before the segment header
    magic, version, seg_epoch = _SEG_HDR.unpack_from(raw, 0)
    if magic != SEG_MAGIC or version != SEG_VERSION:
        return out
    off = _SEG_HDR.size
    while off + _REC_HDR.size <= len(raw):
        (rmagic, rtype, epoch, seq, map_version,
         plen, crc) = _REC_HDR.unpack_from(raw, off)
        if rmagic != REC_MAGIC:
            break                       # torn/garbled tail
        body = raw[off + _REC_HDR.size: off + _REC_HDR.size + plen]
        if len(body) < plen or (zlib.crc32(body) & 0xFFFFFFFF) != crc:
            break                       # torn tail record: drop it
        n_map, b, v = _PAY_HDR.unpack_from(body, 0)
        if plen != _PAY_HDR.size + 4 * (n_map + 2 * b + b * v):
            break                       # framing mismatch: treat as torn
        p = _PAY_HDR.size
        new_map = None
        if n_map:
            new_map = np.frombuffer(body, np.int32, n_map, p).copy()
            p += 4 * n_map
        keys = np.frombuffer(body, np.int32, b, p).copy()
        p += 4 * b
        ops = np.frombuffer(body, np.int32, b, p).copy()
        p += 4 * b
        vals = np.frombuffer(body, np.int32, b * v, p).reshape(b, v).copy()
        out.append(WalRecord(
            rtype=rtype, epoch=epoch, seq=seq, map_version=map_version,
            keys=keys, ops=ops, vals=vals, new_map=new_map))
        off += _REC_HDR.size + plen
    return out


def read_wal(directory: str, from_epoch: int = 0, retries: int = 3,
             backoff: float = 0.01) -> List[WalRecord]:
    """All decodable records with epoch >= from_epoch, in seq order."""
    recs: List[WalRecord] = []
    for e in wal_epochs(directory):
        if e < from_epoch:
            continue
        recs.extend(read_segment(_segment_path(directory, e),
                                 retries, backoff))
    recs.sort(key=lambda r: r.seq)
    return recs


# ---------------------------------------------------------------------------
# DurableKV
# ---------------------------------------------------------------------------

class DurableKV:
    """Durability wrapper: installs the WAL hook on the inner store,
    snapshots it through the async `Checkpointer`, and recovers either a
    whole store (`recover`) or a single dropped replica
    (`rebuild_replica`) from snapshot + WAL suffix.

    Conforms to `KVProtocol`; every other attribute (stats, bucket_map,
    shard_stats, drop_replica, ...) transparently delegates to the
    wrapped store."""

    _obs_facade = "durable"

    def __init__(self, kv, cfg: DurabilityConfig):
        assert getattr(kv, "wal", "missing") is None, \
            "store already has a WAL installed (double-wrapped?)"
        self.kv = kv
        self.dcfg = cfg
        os.makedirs(cfg.dir, exist_ok=True)
        self.ckpt = Checkpointer(os.path.join(cfg.dir, "snap"), keep=cfg.keep)
        self.epoch = 0
        self.snapshots = 0
        self._last_snap_rounds = kv.rounds
        self._wal = WalWriter(cfg.dir, epoch=self.epoch, fsync=cfg.fsync)
        kv.wal = self._wal

    # -- protocol surface (delegation + snapshot cadence) ----------------------
    def _commit(self):
        """Group-commit barrier ("batch" mode): fsync the rounds this
        batch buffered before its statuses reach the caller."""
        if self.dcfg.fsync == "batch":
            if obs.enabled():   # fsync-to-ack: the durability ack stall
                t0 = time.perf_counter()
                self._wal.sync()
                obs.observe_phase("fsync", time.perf_counter() - t0)
            else:
                self._wal.sync()

    def apply(self, keys, ops, vals=None):
        out = self.kv.apply(keys, ops, vals)
        self._commit()
        self.maybe_snapshot()
        return out

    def apply_round(self, keys, ops, vals=None):
        out = self.kv.apply_round(keys, ops, vals)
        self._commit()
        return out

    def read(self, keys):
        return self.kv.read(keys)

    def upsert(self, keys, vals):
        out = self.kv.upsert(keys, vals)
        self._commit()
        self.maybe_snapshot()
        return out

    def rmw(self, keys, deltas):
        out = self.kv.rmw(keys, deltas)
        self._commit()
        self.maybe_snapshot()
        return out

    def delete(self, keys):
        out = self.kv.delete(keys)
        self._commit()
        self.maybe_snapshot()
        return out

    def _stats_tree(self) -> dict:
        out = self.kv._stats_tree()
        out["durability"] = {
            "epoch": self.epoch,
            "snapshots": self.snapshots,
            "wal_seq": self._wal.seq,
            "wal_segments": len(wal_epochs(self.dcfg.dir)),
        }
        return out

    def stats(self) -> dict:
        return obs.fold_stats(self._obs_facade, self._stats_tree())

    def check_invariants(self):
        self.kv.check_invariants()

    def __getattr__(self, name):
        if name == "kv":                    # not yet bound (mid-construction)
            raise AttributeError(name)
        return getattr(self.kv, name)       # stats fields, bucket_map, ...

    # -- snapshots -------------------------------------------------------------
    def _meta(self) -> dict:
        meta = {
            "bucket_map": self.kv.bucket_map.copy(),
            "map_version": np.int64(self.kv.map_version),
            "epoch": np.int64(self.epoch),
            "seq": np.int64(self._wal.seq),
        }
        if hasattr(self.kv, "alive"):
            meta["alive"] = self.kv.alive.copy()
        ht = getattr(self.kv, "_ht", None)
        if ht is not None:
            # host-resident cold chunks travel in the snapshot meta: the
            # floor is a state leaf, so a restore without the host store
            # would leave below-floor addresses unreadable
            meta.update(ht.export_snapshot())
        return meta

    def snapshot(self, blocking: Optional[bool] = None) -> int:
        """Take fuzzy snapshot epoch E+1: rotate the WAL (the capture
        point), then hand the state pytree to the async Checkpointer.
        Off the step path unless `blocking`.  Returns the new epoch."""
        self.ckpt.wait()                # surface a prior save's error here
        with obs.span("durability.snapshot", cat="durability"):
            self.epoch += 1
            self._wal.rotate(self.epoch)
            payload = {"state": self.kv.state, "meta": self._meta()}
            blocking = (self.dcfg.blocking_snapshots if blocking is None
                        else blocking)
            epoch, t0 = self.epoch, time.perf_counter()

            def _on_commit():
                # runs on the Checkpointer worker thread; registry and
                # journal are lock-protected
                dt = time.perf_counter() - t0
                obs.observe("f2_checkpoint_save_seconds", dt,
                            help="snapshot capture-to-durable latency",
                            facade=self._obs_facade)
                obs.journal.emit("snapshot.committed", epoch=epoch,
                                 seconds=round(dt, 6))
                self._gc_segments()

            # segment GC rides the save worker: it is only correct once the
            # snapshot is durable, and listdir+unlink have no business on
            # the step path
            self.ckpt.save(self.epoch, payload, blocking=blocking,
                           on_commit=_on_commit)
            self.snapshots += 1
            self._last_snap_rounds = self.kv.rounds
        obs.journal.emit("snapshot.taken", epoch=self.epoch,
                         blocking=bool(blocking))
        obs.count("f2_snapshots_total", facade=self._obs_facade)
        return self.epoch

    def maybe_snapshot(self) -> bool:
        """Cadence hook: callers invoke at batch / packed-round
        boundaries; snapshots fire every `snapshot_every_rounds` routed
        rounds."""
        every = self.dcfg.snapshot_every_rounds
        if every <= 0 or self.kv.rounds - self._last_snap_rounds < every:
            return False
        self.snapshot()
        return True

    def _gc_segments(self):
        """Drop WAL segments older than the newest *complete* snapshot —
        recovery never reads below the snapshot epoch."""
        latest = self.ckpt.latest_step()
        if latest is None:
            return
        for e in wal_epochs(self.dcfg.dir):
            if e < latest:
                os.remove(_segment_path(self.dcfg.dir, e))

    def wait(self):
        """Block until the in-flight snapshot (if any) is durable."""
        self.ckpt.wait()

    def close(self):
        self.ckpt.wait()
        self._wal.close()

    # -- replica rebuild from disk (graceful degradation) ----------------------
    def rebuild_replica(self, r: int) -> int:
        """Rebuild dropped replica r from snapshot + WAL suffix instead of
        `resync()`'s live drain: healthy replicas serve ZERO drain reads.
        Replay is masked to r (`_rep_do` onehot, scheduler restricted to
        r) under the historical bucket maps from the log; MAP records
        purge/flip for r exactly as the live store did.  Returns records
        replayed into r."""
        kv = self.kv
        assert hasattr(kv, "alive"), "rebuild_replica needs a ReplicatedKV"
        r = int(r)
        assert not kv.alive[r], f"replica {r} is alive; drop it first"
        assert not kv._migrating
        self._wal.sync()                # the replay below reads the log
        self.ckpt.wait()
        snap_epoch = self.ckpt.latest_step()
        onehot = np.arange(kv.R) == r

        if snap_epoch is None:
            # no snapshot yet: reset r to blank and replay the whole log
            from repro.core import sharded as _sharded
            if kv._fresh is None:
                kv._fresh = _sharded.create(kv.cfg, kv.S)
            kv.state = kv._reset_step(kv.state, kv._fresh,
                                      jnp.asarray(onehot))
            start_map = shard_router.default_bucket_map(kv.S, kv.n_buckets)
            start_version = 0
            from_epoch = 0
        else:
            like = {"state": kv.state, "meta": self._meta()}
            payload, _ = self.ckpt.restore(like, step=snap_epoch)
            snap_state, meta = payload["state"], payload["meta"]
            snap_alive = np.asarray(meta["alive"], bool)
            # r's rows as of the snapshot if it was alive then, else the
            # snapshot primary's (bit-identical among alive replicas)
            src = r if snap_alive[r] else int(np.flatnonzero(snap_alive)[0])
            kv.state = jax.tree.map(
                lambda live, snap: jnp.asarray(
                    np.concatenate([np.asarray(live)[:r],
                                    np.asarray(snap)[src:src + 1],
                                    np.asarray(live)[r + 1:]])),
                kv.state, snap_state)
            start_map = np.asarray(meta["bucket_map"], np.int32)
            start_version = int(meta["map_version"])
            from_epoch = int(meta["epoch"])

        # fresh-replica telemetry, exactly like resync()'s reset
        kv.compactions[r] = 0
        kv.temp_table_peak_bytes[r] = 0
        kv._fold_read()
        from repro.core.types import IoStats as _IoStats
        for f in _IoStats._fields:
            kv._read_io[f][r] = 0
        kv._read_exhausted[r] = False

        recs = read_wal(self.dcfg.dir, from_epoch=from_epoch,
                        retries=self.dcfg.segment_retries,
                        backoff=self.dcfg.retry_backoff)
        kv.alive[r] = True
        with obs.span("durability.rebuild_replica", cat="durability",
                      replica=r):
            n, end_map, _ = _replay(kv, recs, start_map, start_version,
                                    rep_mask=onehot, resync_only=r)
        # replay must land on the live map — every migrate logged a MAP
        assert (end_map == kv.bucket_map).all(), \
            "WAL replay ended on a different bucket map than the live store"
        kv.resyncs += 1                 # telemetry parity with resync()
        obs.journal.emit("replica.rebuilt", facade=self._obs_facade,
                         replica=r, records=n)
        return n


def _replay(kv, recs: List[WalRecord], start_map: np.ndarray,
            start_version: int = 0,
            rep_mask: Optional[np.ndarray] = None,
            resync_only: Optional[int] = None):
    """Replay WAL records onto `kv`, starting from bucket map `start_map`.

    Full recovery: `rep_mask=None` — rounds fan in to `kv.alive` (the
    snapshot's alive set) exactly like the original rounds did.  Masked
    rebuild: `rep_mask` onehot of the replica under reconstruction; only
    its rows change and only its shards see scheduler passes.

    SLAB records replay through the same deferral loop as `apply` — one
    client batch each, same map + lanes => the identical round sequence
    with the identical placement/deferral.  MAP records purge
    the moved buckets' source copies (`shard_router.bucket_moves` of the
    tracked current map vs the record's new map), flip the map, then
    replay the drained payload — the live `migrate()` protocol minus the
    drain, which the record already carries.  `_migrating` is held True
    throughout so replay is never re-logged and never triggers a
    spontaneous rebalance mid-replay (which would fork history from the
    log).  Returns (records replayed, map after the last record, map
    version after the last record) — callers assert the end map matches
    what they expect (rebuild: the live map; recover: becomes the map)."""
    cur_map = np.asarray(start_map, np.int32).copy()
    cur_ver = int(start_version)
    live_map, live_dev = kv.bucket_map, kv._bucket_map_dev
    kv._bucket_map_dev = jnp.asarray(cur_map)
    rep_kw = {} if rep_mask is None else {"_rep_do": rep_mask}
    Bm = kv._mig_batch
    replayed = 0
    kv._migrating = True
    if resync_only is not None:
        kv._resync_only = resync_only
    try:
        last_seq = None
        for rec in recs:
            if last_seq is not None and rec.seq <= last_seq:
                continue                # duplicate (overlapping segments)
            last_seq = rec.seq
            if rec.rtype == REC_SLAB:
                # header check: the logged batch must replay under the
                # same map it was routed with
                assert rec.map_version == cur_ver, (rec.map_version, cur_ver)
                # one record per client batch: re-derive the deferral
                # rounds exactly as the original `apply` loop did (the
                # round sequence is a pure function of batch, map, lanes
                # — the map is pinned for the whole record)
                cur_ops = rec.ops
                for _ in range(len(rec.keys) + 1):
                    _st, _rv, _placed, deferred = kv.apply_round(
                        rec.keys, cur_ops, rec.vals, **rep_kw)
                    deferred_np = np.asarray(deferred)
                    if not deferred_np.any():
                        break
                    cur_ops = np.where(deferred_np, rec.ops,
                                       OP_NOOP).astype(np.int32)
                replayed += int(((rec.ops == OP_UPSERT) | (rec.ops == OP_RMW)
                                 | (rec.ops == OP_DELETE)).sum())
            else:                       # REC_MAP: purge -> flip -> replay
                assert rec.map_version == cur_ver + 1, \
                    (rec.map_version, cur_ver)
                new_map = np.asarray(rec.new_map, np.int32)
                move = shard_router.bucket_moves(cur_map, new_map, kv.S)
                if move.any():
                    mshard = move.any(axis=1)
                    if rep_mask is None:
                        do = kv._rep_shard(mshard)
                    else:
                        do = np.asarray(rep_mask, bool)[:, None] \
                            & mshard[None, :]
                    kv.state = kv._purge(kv.state, kv._rep_move(move),
                                         jnp.asarray(do))
                cur_map = new_map.copy()
                cur_ver = int(rec.map_version)
                kv._bucket_map_dev = jnp.asarray(cur_map)
                n_moved = len(rec.keys)
                for off in range(0, n_moved, Bm):
                    ks = rec.keys[off:off + Bm]
                    pad = Bm - len(ks)
                    ks = np.pad(ks, (0, pad))
                    os_ = np.pad(rec.ops[off:off + Bm], (0, pad),
                                 constant_values=OP_NOOP)
                    vs = np.pad(rec.vals[off:off + Bm], ((0, pad), (0, 0)))
                    kv.apply(ks, os_, vs, **rep_kw)
                replayed += n_moved
    finally:
        if resync_only is not None:
            kv._resync_only = None
        kv._migrating = False
        if rep_mask is None:
            # full recovery: the tracked map IS the store's map now
            kv.bucket_map = cur_map.copy()
            kv._bucket_map_dev = jnp.asarray(cur_map)
            kv.map_version = cur_ver
        else:
            # masked rebuild on a live store: restore the live map (the
            # caller asserts replay ended on it)
            kv.bucket_map, kv._bucket_map_dev = live_map, live_dev
    return replayed, cur_map, cur_ver


def recover(directory: str, make_kv: Callable[[], Any],
            cfg: Optional[DurabilityConfig] = None) -> "DurableKV":
    """Bring a crashed durable store back: restore the latest complete
    snapshot into a fresh store from `make_kv` (same deployment shape as
    the crashed one), replay the WAL suffix, re-check invariants, and
    return a live `DurableKV` whose WAL continues in a fresh epoch.

    With no complete snapshot, replay starts from a blank store and epoch
    0 — the WAL alone carries the whole history."""
    cfg = cfg if cfg is not None else DurabilityConfig(dir=directory)
    kv = make_kv()
    assert getattr(kv, "wal", None) is None
    ckpt = Checkpointer(os.path.join(directory, "snap"), keep=cfg.keep)
    snap_epoch = ckpt.latest_step()
    if snap_epoch is None:
        start_map = kv.bucket_map.copy()
        from_epoch, next_seq, epoch = 0, 0, 0
    else:
        meta_like = {
            "bucket_map": kv.bucket_map.copy(),
            "map_version": np.int64(0),
            "epoch": np.int64(0),
            "seq": np.int64(0),
        }
        if hasattr(kv, "alive"):
            meta_like["alive"] = kv.alive.copy()
        ht = getattr(kv, "_ht", None)
        if ht is not None:
            # placeholders only fix the treedef; restore takes shapes
            # (i.e. the demoted-chunk count) from the manifest
            for k, a in ht.export_snapshot().items():
                meta_like[k] = a[:0]
        payload, _ = ckpt.restore({"state": kv.state, "meta": meta_like},
                                  step=snap_epoch)
        kv.state = jax.tree.map(jnp.asarray, payload["state"])
        meta = payload["meta"]
        if ht is not None:
            ht.import_snapshot(meta)
        start_map = np.asarray(meta["bucket_map"], np.int32)
        kv.bucket_map = start_map.copy()
        kv._bucket_map_dev = jnp.asarray(start_map)
        kv.map_version = int(meta["map_version"])
        if hasattr(kv, "alive"):
            kv.alive = np.asarray(meta["alive"], bool).copy()
        from_epoch = int(meta["epoch"])
        next_seq = int(meta["seq"])
        epoch = snap_epoch

    recs = read_wal(directory, from_epoch=from_epoch,
                    retries=cfg.segment_retries, backoff=cfg.retry_backoff)
    with obs.span("durability.recover", cat="durability"):
        n_replayed, _, _ = _replay(kv, recs, start_map,
                                   start_version=kv.map_version)
    obs.journal.emit("recovery.completed", records=n_replayed,
                     snapshot_epoch=snap_epoch)
    if recs:
        next_seq = max(next_seq, recs[-1].seq + 1)

    if (hasattr(kv, "alive") and cfg.revive_dead_replicas
            and not kv.alive.all()):
        # dead-at-snapshot replicas: revive by copying the recovered
        # primary's rows — alive replicas are bit-identical, so this is
        # exactly what a completed resync would have produced
        h = int(np.flatnonzero(kv.alive)[0])
        dead = np.flatnonzero(~kv.alive)
        def _revive(leaf):
            a = np.asarray(leaf).copy()
            for d in dead:
                a[d] = a[h]
            return jnp.asarray(a)
        kv.state = jax.tree.map(_revive, kv.state)
        kv.alive[:] = True
    kv.check_invariants()

    dk = DurableKV.__new__(DurableKV)
    dk.kv = kv
    dk.dcfg = cfg
    dk.ckpt = ckpt
    # fresh, never-used epoch: appending to the segment that fed this
    # recovery could bury new records behind its torn tail
    dk.epoch = max(wal_epochs(directory) + [epoch]) + 1
    dk.snapshots = 0
    dk._last_snap_rounds = kv.rounds
    dk._wal = WalWriter(cfg.dir, epoch=dk.epoch, seq=next_seq,
                        fsync=cfg.fsync)
    kv.wal = dk._wal
    return dk
