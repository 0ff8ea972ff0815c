"""One run of one cell: build the deployment, load it, warm it, offer
its traffic for the window, then check every answer against the plain
reference.

Set-up, in order: the service is built through the normal path
(`make_session_service` -> `KVSessionService` -> (`DurableKV` ->)
`ShardedKV`); every key is loaded once with seeded values through the
store's own `apply` in full-width upsert batches; one step each of
hot->cold, cold->cold and chunk GC runs through the scheduler's public
`maybe_compact` (so their programs are compiled); then a fixed amount of
the cell's traffic is offered at its rate and every answer awaited, which
compiles and caches every program the window uses.  The window opens on
an idle service, so a warm-up that compiled (a checkout's first run)
leaves no backlog for the window to serve.

The window is an open loop at the rate the traffic file fixes: ops fall
due evenly spaced from the window's opening, go to the sessions in fixed
chunks once due, and each iteration runs one `step()` and polls every
outstanding ticket.  An op's latency runs from its due time to the poll
that sees it done, so a stall also delays every op that falls due behind
it.

After the window: every op due in it is answered (a minute at most),
every key written is read back through the same sessions,
`check_invariants()` runs, the durable cell's WAL is read back, and the
reference replays the recorded rounds and compares every acknowledged
answer.
"""
from __future__ import annotations

import contextlib
import gc
import os
import shutil
import time
from pathlib import Path
from types import SimpleNamespace
from typing import Dict, List, Optional

import numpy as np

from . import model as ref
from . import ycsb
from .ops import OP_READ, OP_UPSERT, WRITE_OPS
from .spec import Cell, load_reader

DRAIN_LIMIT_S = 60.0        # an answer may come late, not never
WARMUP_LIMIT_S = 300.0      # the warm-up compiles in a checkout's first run
READBACK_CAP = 1 << 18      # keys read back after the window, at most
WARMUP_S = 3.0              # seconds' worth of traffic before the window
SLOW_ITERATION_S = 0.25     # an iteration this long is reported as a stall
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# what else JAX times on the way to a program, counted inside the window
WINDOW_EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "jaxpr traces",
                 "/jax/core/compile/jaxpr_to_mlir_module_duration":
                     "lowerings",
                 "/jax/compilation_cache/cache_retrieval_time_sec":
                     "compile-cache loads"}


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# the deployment
# ---------------------------------------------------------------------------

def build_service(config: dict, traffic: dict, run_dir: Path):
    from repro.core import DurabilityConfig, F2Config
    from repro.serve.serve_step import ServiceConfig, make_session_service

    cfg = F2Config(**config["store"])
    dur = None
    if config.get("durability"):
        d = config["durability"]
        dur = DurabilityConfig(dir=str(run_dir / "wal"), fsync=d["fsync"],
                               snapshot_every_rounds=int(
                                   d["snapshot_every_rounds"]))
    svc_cfg = config["service"]
    if traffic["sessions"] > svc_cfg["max_sessions"]:
        raise ValueError("traffic opens more sessions than the service holds")
    sc = ServiceConfig(n_shards=int(svc_cfg["n_shards"]),
                       max_sessions=int(traffic["sessions"]),
                       session_depth=int(traffic["depth"]),
                       pack_lanes=int(svc_cfg["pack_lanes"]),
                       durability=dur)
    return make_session_service(cfg, sc)


def inner_store(svc):
    """The routed store under the session service (under `DurableKV`
    when the deployment is durable)."""
    kv = svc.kv
    return getattr(kv, "kv", kv)


def warm_compaction(svc) -> None:
    """One pass of every scheduler step (hot->cold, cold->cold, chunk
    GC) through `maybe_compact`, with the pressure trigger at 0 so that
    each one fires and the region fraction at 0 so that each compacts
    one `compact_batch` (a larger cold->cold region in one call overruns
    the chunk log, ROADMAP R8), then both put back."""
    kv = inner_store(svc)
    trigger, frac = kv.trigger, kv.compact_frac
    kv.trigger, kv.compact_frac = 0.0, 0.0
    try:
        kv.maybe_compact()
    finally:
        kv.trigger, kv.compact_frac = trigger, frac


# ---------------------------------------------------------------------------
# the open loop
# ---------------------------------------------------------------------------

class FsyncWatch:
    """Records how many bytes of the WAL each fsync made durable, so that
    every acknowledgement can be checked against the bytes fsync'd before
    it, and how long each WAL fsync took (host clock).  It only observes:
    each call goes on to the real `os.fsync`."""

    def __init__(self, wal_dir: Path):
        self.dir = os.path.realpath(wal_dir)
        self.durable: Dict[str, int] = {}
        self.seconds: List[float] = []      # each WAL fsync, in order
        self._orig = os.fsync

    def _fsync(self, fd):
        t = time.perf_counter()
        self._orig(fd)
        dt = time.perf_counter() - t
        path = os.readlink(f"/proc/self/fd/{fd}")
        if path.startswith(self.dir):
            self.durable[path] = max(self.durable.get(path, 0),
                                     os.fstat(fd).st_size)
            self.seconds.append(dt)

    def __enter__(self):
        os.fsync = self._fsync
        return self

    def __exit__(self, *exc):
        os.fsync = self._orig

    def undurable_bytes(self) -> int:
        """Bytes written to the WAL's segments and not yet fsync'd."""
        lag = 0
        with os.scandir(self.dir) as it:
            for e in it:
                if e.name.endswith(".log"):
                    size = e.stat().st_size
                    lag += max(0, size - self.durable.get(e.path, 0))
        return lag


class Loop:
    """Clients of the session service, offering a stream of ops.  Ops go
    to the service in chunks of `chunk` (so the enqueue program has one
    shape), each to the next session whose ring has room for it.  A
    stream with due times is offered at its rate: a chunk goes once its
    last op is due, and an op's latency runs from its due time to the poll
    that sees it done.  A stream without due times (the read-back) is
    offered as fast as the rings take it."""

    def __init__(self, svc, n_sessions: int, chunk: int, annotate: bool):
        self.svc = svc
        self.chunk = chunk
        self.sessions = [svc.open_session() for _ in range(n_sessions)]
        self.pend = [np.zeros(0, np.int64) for _ in self.sessions]
        self.enq: List[tuple] = []      # (tickets, stream id, first pos)
        self.due: List[tuple] = []      # (tickets, due times)
        self.acks: List[tuple] = []     # (tickets, status, vals, t_ack)
        self.streams: List[tuple] = []  # (keys, ops, vals, due or None)
        self.sid = -1
        self.pos = 0
        self.limit = 0
        self.rr = 0
        self.watch: Optional[FsyncWatch] = None
        self.unsynced: List[np.ndarray] = []   # tickets acked before fsync
        self.iterations = 0
        self._kv = inner_store(svc)
        # per iteration while recording: (start, offer, step, poll, wait
        # seconds, compaction passes run)
        self.timeline: Optional[List[tuple]] = None
        if annotate:
            import jax
            self._span = jax.profiler.TraceAnnotation
        else:
            self._span = lambda name: contextlib.nullcontext()

    def set_stream(self, keys, ops, vals, due=None) -> None:
        """Ops to offer from now on, padded to whole chunks with repeats
        of the first op (which the reference checks like any other)."""
        pad = -len(keys) % self.chunk
        ext = lambda a: np.concatenate([a, np.repeat(a[:1], pad, 0)])
        if due is not None:
            due = np.concatenate([due, np.full(pad, due[-1] if len(due)
                                                else 0.0)])
        self.streams.append((ext(keys), ext(ops), ext(vals), due))
        self.sid = len(self.streams) - 1
        self.pos = 0
        self.limit = len(keys) + pad

    def close_offer(self, t_close: float) -> None:
        """Offer nothing due after `t_close` (past the whole chunk that
        holds the last op due by then)."""
        due = self.streams[self.sid][3]
        n = int(np.searchsorted(due, t_close, side="right"))
        self.limit = min(self.limit, n + (-n % self.chunk))

    def offered_all(self) -> bool:
        return self.pos >= self.limit

    def lag(self, now: float) -> float:
        """How late the generator runs: the age of the oldest op due and
        not yet handed to a session."""
        due = self.streams[self.sid][3]
        if due is None or self.pos >= self.limit or due[self.pos] > now:
            return 0.0
        return now - float(due[self.pos])

    def next_due(self) -> Optional[float]:
        due = self.streams[self.sid][3]
        if due is None or self.pos >= self.limit:
            return None
        return float(due[self.pos + self.chunk - 1])

    def offer(self, now: float) -> None:
        K, O, V, due = self.streams[self.sid]
        C, n_s = self.chunk, len(self.sessions)
        with self._span("bench.offer"):
            while self.pos + C <= self.limit:
                p = self.pos
                if due is not None and due[p + C - 1] > now:
                    break
                for j in range(n_s):
                    i = (self.rr + j) % n_s
                    s = self.sessions[i]
                    if s.capacity - s.in_use >= C:
                        break
                else:
                    break           # every ring is full: ops wait here
                self.rr = (i + 1) % n_s
                t = time.perf_counter()
                tk = s.enqueue(K[p:p + C], O[p:p + C], V[p:p + C])
                acc = tk.astype(np.int64)
                self.enq.append((acc, self.sid, p))
                self.due.append((acc, due[p:p + C] if due is not None
                                 else np.full(C, t)))
                self.pend[i] = np.concatenate([self.pend[i], acc])
                self.pos = p + C

    def step(self) -> None:
        with self._span("bench.step"):
            self.svc.step()

    def poll(self) -> None:
        lag = self.watch.undurable_bytes() if self.watch else 0
        with self._span("bench.poll"):
            for i, s in enumerate(self.sessions):
                if not len(self.pend[i]):
                    continue
                done, st, v = s.poll(self.pend[i])
                t = time.perf_counter()
                if done.any():
                    self.acks.append((self.pend[i][done], st[done],
                                      v[done], t))
                    if lag:
                        self.unsynced.append(self.pend[i][done])
                    self.pend[i] = self.pend[i][~done]

    def iterate(self) -> None:
        """Offer what is due, then one round and a poll; with nothing
        outstanding, wait for the next chunk to fall due."""
        t0 = time.perf_counter()
        passes = int(self._kv.compactions.sum())
        self.offer(t0)
        t1 = t2 = t3 = time.perf_counter()
        if self.outstanding():
            self.step()
            t2 = time.perf_counter()
            self.poll()
            t3 = time.perf_counter()
        else:
            nxt = self.next_due()
            if nxt is not None:
                with self._span("bench.wait"):
                    time.sleep(max(0.0, nxt - time.perf_counter()))
        if self.timeline is not None:
            t4 = time.perf_counter()
            self.timeline.append((t0, t1 - t0, t2 - t1, t3 - t2, t4 - t3,
                                  int(self._kv.compactions.sum()) - passes))
        self.iterations += 1

    def outstanding(self) -> int:
        return sum(len(p) for p in self.pend)

    def run_out(self, limit_s: float) -> None:
        """Offer the rest of the stream and wait for every answer (for
        at most `limit_s`: an answer may come late, not never)."""
        t0 = time.perf_counter()
        while (self.outstanding() or not self.offered_all()) \
                and time.perf_counter() - t0 < limit_s:
            self.iterate()

    def close(self) -> None:
        for s in self.sessions:
            if s.outstanding == 0:
                s.close()


# ---------------------------------------------------------------------------
# the check
# ---------------------------------------------------------------------------

def check_against_reference(loop: Loop, schedule, model: ref.Model,
                            first_readback: int):
    """Replay the recorded rounds through the reference and compare every
    acknowledged answer.  A round's lanes must be the ops the clients
    enqueued, each ticket executed once, in ticket order.  Returns the
    counts compared and a function giving, for tickets, whether each
    failed (answered wrong or never answered)."""
    enq_t = np.concatenate([e[0] for e in loop.enq])
    t_lo, t_hi = int(enq_t.min()), int(enq_t.max()) + 1
    n = t_hi - t_lo
    V = model.vals.shape[1]
    src_k = np.zeros(n, np.int32)
    src_o = np.zeros(n, np.int32)
    src_v = np.zeros((n, V), np.int32)
    for tk, sid, p in loop.enq:
        K, O, Vv, _ = loop.streams[sid]
        j = tk - t_lo
        src_k[j] = K[p:p + len(tk)]
        src_o[j] = O[p:p + len(tk)]
        src_v[j] = Vv[p:p + len(tk)]
    enqueued = np.zeros(n, bool)
    enqueued[enq_t - t_lo] = True
    acked = np.zeros(n, bool)
    ack_st = np.zeros(n, np.int32)
    ack_v = np.zeros((n, V), np.int32)
    for tk, st, v, _ in loop.acks:
        j = tk - t_lo
        acked[j] = True
        ack_st[j] = st
        ack_v[j] = v
    executed = np.zeros(n, np.int32)
    wrong = np.zeros(n, bool)
    bad_lanes = 0
    for tkt, valid, bk, bo, bv in schedule:
        t = tkt[valid].astype(np.int64)
        if np.any(np.diff(t) <= 0) or np.any(t < t_lo) or np.any(t >= t_hi):
            bad_lanes += int(valid.sum())
            continue
        j = t - t_lo
        bad_lanes += int(np.sum(~enqueued[j]))
        bad_lanes += int(np.sum((bk[valid] != src_k[j])
                                | (bo[valid] != src_o[j])
                                | np.any(bv[valid] != src_v[j], axis=1)))
        executed[j] += 1
        want_st, want_v = model.batch(src_k[j], src_o[j], src_v[j])
        got = acked[j]
        wrong[j] = got & ref.mismatched(src_o[j], ack_st[j], ack_v[j],
                                        want_st, want_v)
    rb = np.arange(n) + t_lo >= first_readback
    counts = {
        "wrong_answers": int(np.sum(wrong & ~rb)),
        "readback_wrong": int(np.sum(wrong & rb)),
        "lost_ops": int(np.sum(enqueued & ~acked)),
        "unexecuted_acks": int(np.sum(acked & (executed == 0))),
        "bad_round_lanes": bad_lanes + int(np.sum(executed > 1)),
    }
    failed = wrong | ~acked | (executed == 0)
    return counts, lambda tickets: failed[np.asarray(tickets) - t_lo]


def check_wal(wal_dir: Path, schedule) -> int:
    """Rounds with a write whose batch is not, in order, among the WAL's
    last records exactly as the round ran."""
    from repro.core.durability import REC_SLAB, read_wal
    recs = [r for r in read_wal(str(wal_dir)) if r.rtype == REC_SLAB]
    rounds = [(bk, bo, bv) for _, _, bk, bo, bv in schedule
              if np.isin(bo, WRITE_OPS).any()]
    recs = recs[len(recs) - len(rounds):] if len(rounds) <= len(recs) \
        else [None] * (len(rounds) - len(recs)) + recs
    missing = 0
    for rec, (bk, bo, bv) in zip(recs, rounds):
        if rec is None or not (np.array_equal(rec.keys, bk)
                               and np.array_equal(rec.ops, bo)
                               and np.array_equal(rec.vals, bv)):
            missing += 1
    return missing


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def quantile_ms(lat_s: np.ndarray, q: float) -> float:
    return float(np.percentile(lat_s, q) * 1e3)


def device_info(jax, chips: int) -> dict:
    devs = jax.devices()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs[:chips])
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips, "memory_peak_bytes": int(peak)}


class Deployment(SimpleNamespace):
    """A built, loaded and warmed deployment with its reference."""


def set_up(cell: Cell, seed: int, run_dir: Path, fault=None) -> Deployment:
    """Build the service, load every key once with seeded values through
    the store's own full-width `apply`, and warm the compaction steps."""
    import jax

    config, traffic = dict(cell.config), cell.traffic
    if fault is not None:
        config = fault.configure(config)
    shutil.rmtree(run_dir, ignore_errors=True)
    run_dir.mkdir(parents=True)
    n_keys = int(config["n_keys"])
    V = int(config["store"]["value_width"])
    lanes = int(config["load_lanes"])
    durable = bool(config.get("durability"))
    rng = np.random.default_rng(seed)

    svc = build_service(config, traffic, run_dir)
    if fault is not None:
        fault.install(svc, "built")
    stack = contextlib.ExitStack()
    watch = stack.enter_context(FsyncWatch(run_dir / "wal")) \
        if durable else None
    t_load = time.perf_counter()
    order = rng.permutation(n_keys).astype(np.int32)
    load_vals = rng.integers(0, np.iinfo(np.int32).max, (n_keys, V),
                             dtype=np.int32)
    up = np.full(lanes, OP_UPSERT, np.int32)
    for s in range(0, n_keys, lanes):
        k = order[s:s + lanes]
        svc.kv.apply(k, up[:len(k)], load_vals[k])
    jax.block_until_ready(inner_store(svc).state)
    load_s = time.perf_counter() - t_load
    model = ref.Model(n_keys, V)
    model.load(np.arange(n_keys), load_vals)
    if fault is not None:
        fault.install(svc, "loaded")
    t_warm = time.perf_counter()
    warm_compaction(svc)
    jax.block_until_ready(inner_store(svc).state)
    svc.trace_schedule = True
    return Deployment(svc=svc, model=model, rng=rng, config=config,
                      traffic=traffic, n_keys=n_keys, V=V, durable=durable,
                      stack=stack, watch=watch, run_dir=run_dir,
                      load_s=load_s, warm_s=time.perf_counter() - t_warm)


def offered(dep: Deployment, rate: float, seconds: float):
    """The seeded op stream for `seconds` of arrivals at `rate`: keys,
    ops, values and due times evenly spaced from 0.  Every seed offers the
    same arrivals, with other keys, ops and values."""
    n = int(np.ceil(rate * seconds))
    keys, ops, vals = ycsb.make_stream(dep.rng, dep.traffic, dep.n_keys,
                                       dep.V, n)
    return keys, ops, vals, np.arange(n) / rate


class GcWatch:
    """Collections of Python's garbage collector and the seconds each
    paused the process, while armed."""

    def __init__(self):
        self.pauses: List[tuple] = []       # (generation, seconds)
        self.armed = False
        self._t = 0.0

    def __call__(self, phase, info):
        if not self.armed:
            return
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._t))

    def __enter__(self):
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self)


class Window(SimpleNamespace):
    """What one measured window saw: its bounds on the host clock, the
    generator's lateness, each iteration's phases, the collector's pauses,
    and the counters and rounds around it."""


def run_window(dep: Deployment, loop: Loop, rate: float, warm_s: float,
               seconds: float, on_open=None) -> Window:
    """Offer `warm_s` seconds' worth of the traffic at `rate` and await
    every answer, then offer the measured `seconds` on the idle service;
    stop offering ops due after the window and take the counters.
    `on_open()` runs just before the window opens."""
    warm = offered(dep, rate, warm_s)
    # arrivals run a little past the window so that it never waits on
    # the end of the stream
    *stream, due = offered(dep, rate, seconds + 1.0)
    loop.set_stream(*warm[:3], warm[3] + time.perf_counter())
    loop.run_out(WARMUP_LIMIT_S)
    if loop.outstanding() or not loop.offered_all():
        raise RuntimeError("the warm-up traffic was not answered within "
                           f"{WARMUP_LIMIT_S:.0f}s")
    before = counters(dep.svc)
    sched0, acks0 = len(dep.svc.schedule), len(loop.acks)
    if on_open is not None:
        on_open()
    lags = []
    loop.timeline = []
    with GcWatch() as gcw, loop._span("bench.window"):
        gcw.armed = True
        t0 = time.perf_counter()
        loop.set_stream(*stream, due + t0)
        while True:
            loop.iterate()
            t1 = time.perf_counter()
            lags.append(loop.lag(t1))
            if t1 - t0 >= seconds:
                break
        gcw.armed = False
    timeline, loop.timeline = loop.timeline, None
    loop.close_offer(t1)
    return Window(t0=t0, t1=t1, lags=np.asarray(lags), before=before,
                  sched0=sched0, sched1=len(dep.svc.schedule), acks0=acks0,
                  outstanding=loop.outstanding(), timeline=timeline,
                  gc_pauses=gcw.pauses)


def report_stalls(name: str, w: Window) -> None:
    """Earlier lines: the window's slowest iterations by phase, when each
    compaction pass ran, and the collector's pauses."""
    tl = w.timeline
    slow = sorted(tl, key=lambda r: -sum(r[1:5]))[:3]
    n_slow = sum(1 for r in tl if sum(r[1:5]) >= SLOW_ITERATION_S)
    log(f"[{name}] iterations in the window: {len(tl)}; "
        f"{n_slow} took {SLOW_ITERATION_S * 1e3:.0f} ms or more")
    for r in slow:
        log(f"[{name}] slow iteration at {r[0] - w.t0:.3f}s: offer "
            f"{r[1] * 1e3:.3f} ms, step {r[2] * 1e3:.3f} ms, poll "
            f"{r[3] * 1e3:.3f} ms, wait {r[4] * 1e3:.3f} ms, compaction "
            f"passes {r[5]}")
    at = [f"{r[0] - w.t0:.3f}s ({sum(r[1:5]) * 1e3:.1f} ms)"
          for r in tl if r[5]]
    log(f"[{name}] iterations that ran a compaction pass: {len(at)}: "
        f"{', '.join(at) or '-'}")
    by_gen = {g: [s for gg, s in w.gc_pauses if gg == g] for g in (0, 1, 2)}
    log(f"[{name}] garbage collections in the window: " + "; ".join(
        f"generation {g}: {len(v)}, longest {max(v, default=0) * 1e3:.3f} "
        f"ms, total {sum(v) * 1e3:.3f} ms" for g, v in by_gen.items()))


def window_ops(loop: Loop, w: Window):
    """(tickets due in the window, their latencies in s or NaN where
    unanswered, acks inside the window)."""
    tk = np.concatenate([d[0] for d in loop.due])
    due = np.concatenate([d[1] for d in loop.due])
    sel = (due >= w.t0) & (due <= w.t1)
    tk, due = tk[sel], due[sel]
    a_tk = np.concatenate([a[0] for a in loop.acks])
    a_t = np.concatenate([np.full(len(a[0]), a[3]) for a in loop.acks])
    order = np.argsort(a_tk)
    a_tk, a_t = a_tk[order], a_t[order]
    i = np.minimum(np.searchsorted(a_tk, tk), len(a_tk) - 1)
    t_ack = np.where(a_tk[i] == tk, a_t[i], np.nan)
    n_in = sum(len(a[0]) for a in loop.acks[w.acks0:]
               if w.t0 < a[3] <= w.t1)
    return tk, t_ack - due, n_in


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, run_dir: Path, require_tpu: bool = True,
             fault=None) -> dict:
    """One run; returns the result object the benchmark prints.  `fault`
    (tests and control runs only) may change the configuration and
    wrap the store, so that the check's failure can be shown."""
    import jax

    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < cell.chips):
        raise NoChip(f"the cell needs {cell.chips} TPU chip(s); JAX sees "
                     f"{len(devs)} {devs[0].platform} device(s)")
    from .peaks import peaks_for
    peaks = peaks_for(devs[0].device_kind) if require_tpu else None

    compiles = {"window": 0, "all": 0}
    in_window_events: Dict[str, tuple] = {}
    in_window = [False]

    def on_event(event, secs, **kw):
        if event == COMPILE_EVENT:
            compiles["all"] += 1
            compiles["window"] += in_window[0]
        elif event in WINDOW_EVENTS and in_window[0]:
            n, t = in_window_events.get(event, (0, 0.0))
            in_window_events[event] = (n + 1, t + secs)

    jax.monitoring.register_event_duration_secs_listener(on_event)

    dep = set_up(cell, seed, run_dir, fault)
    svc, traffic = dep.svc, dep.traffic
    rate = float(traffic["rate_ops_per_s"])
    loop = Loop(svc, int(traffic["sessions"]), int(traffic["chunk"]),
                annotate=trace)
    loop.watch = dep.watch
    trace_dir = run_dir / "trace"
    timing = {}

    def on_open():
        timing["fsyncs"] = len(dep.watch.seconds) if dep.watch else 0
        if trace:
            # device ops, module spans and the benchmark's own annotations;
            # no Python tracer, whose per-call cost would slow the host
            # loop out of the regime that the untraced runs measure
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        in_window[0] = True
        timing["setup_s"] = time.perf_counter() - t_start

    with dep.stack:
        w = run_window(dep, loop, rate, WARMUP_S, seconds, on_open)
        in_window[0] = False
        if trace:
            jax.profiler.stop_trace()
        fsync_s = dep.watch.seconds[timing["fsyncs"]:] if dep.watch else []
        after = counters(svc)
        device = device_info(jax, cell.chips)
        # -- after the window: every op due in it answered, then read back
        loop.run_out(DRAIN_LIMIT_S)
        first_readback = int(max(np.max(e[0]) for e in loop.enq)) + 1
        written = written_keys(loop)
        if len(written) > READBACK_CAP:
            written = np.sort(dep.rng.choice(written, READBACK_CAP,
                                             replace=False))
        loop.set_stream(written.astype(np.int32),
                        np.full(len(written), OP_READ, np.int32),
                        np.zeros((len(written), dep.V), np.int32))
        loop.run_out(DRAIN_LIMIT_S)
    # -- the checks --------------------------------------------------------
    t_check = time.perf_counter()
    try:
        svc.check_invariants()
        invariant_failures = 0
    except AssertionError as e:
        log(f"check_invariants() failed: {e}")
        invariant_failures = 1
    schedule = fetch_schedule(jax, svc.schedule)
    io_total = svc.io_stats()
    loop.close()
    close = getattr(svc.kv, "close", None)
    if close is not None:
        close()
    svc.schedule = []
    del svc, dep.svc
    checks, failed_of = check_against_reference(loop, schedule, dep.model,
                                                first_readback)
    checks["invariant_failures"] = invariant_failures
    if dep.durable:
        checks["wal_missing_rounds"] = check_wal(run_dir / "wal", schedule)
        checks["unsynced_acks"] = int(sum(len(u) for u in loop.unsynced))
    check_s = time.perf_counter() - t_check

    # -- the window's numbers ----------------------------------------------
    due_tk, lat, n_acked = window_ops(loop, w)
    bad = failed_of(due_tk)
    if loop.unsynced:
        bad |= np.isin(due_tk, np.concatenate(loop.unsynced))
    attempted, failed = len(due_tk), int(np.sum(bad | np.isnan(lat)))
    lat = lat[~np.isnan(lat)]
    window_s = w.t1 - w.t0
    window_rounds = schedule[w.sched0:w.sched1]
    codes, counts = np.unique(np.concatenate(
        [bo[v] for _, v, _, bo, _ in window_rounds] or [np.zeros(0, int)]),
        return_counts=True)
    ops_executed = {int(c): int(n) for c, n in zip(codes, counts)}

    log(f"[{cell.name}] seed {seed}: {attempted} ops offered at "
        f"{rate:.0f}/s in {window_s:.3f}s, {n_acked} acknowledged in the "
        f"window over {len(window_rounds)} rounds; latency samples "
        f"{len(lat)}")
    if len(lat):
        log(f"[{cell.name}] acknowledged {n_acked / window_s:.3f} ops/s; "
            f"latency p50 {quantile_ms(lat, 50):.3f} ms, p99 "
            f"{quantile_ms(lat, 99):.3f} ms")
    log(f"[{cell.name}] generator lateness: max {w.lags.max() * 1e3:.3f} ms"
        f", mean {w.lags.mean() * 1e3:.3f} ms; outstanding at the close "
        f"{w.outstanding}")
    log(f"[{cell.name}] compiles inside the window: {compiles['window']} "
        f"(of {compiles['all']} in the run); inside the window: " + ", ".join(
            f"{what} {in_window_events.get(ev, (0, 0.0))[0]} "
            f"({in_window_events.get(ev, (0, 0.0))[1]:.3f}s)"
            for ev, what in WINDOW_EVENTS.items()))
    log(f"[{cell.name}] set-up {timing['setup_s']:.3f}s: load "
        f"{dep.load_s:.3f}s, compaction warm-up {dep.warm_s:.3f}s")
    log(f"[{cell.name}] compaction passes in the window: "
        f"{after['compactions'] - w.before['compactions']}; stable-tier "
        f"reads {after['io']['read_ops'] - w.before['io']['read_ops']}, "
        f"in-memory record touches "
        f"{after['io']['mem_hits'] - w.before['io']['mem_hits']}")
    report_stalls(cell.name, w)
    log(f"[{cell.name}] check {check_s:.3f}s; io at the end {io_total}")

    res = {"correct": all(v <= 0 for v in checks.values()),
           "attempted": int(attempted), "failed": int(failed)}
    ctx = SimpleNamespace(
        config=dep.config, traffic=traffic, peaks=peaks, window_s=window_s,
        ops=n_acked, rounds=len(window_rounds), ops_executed=ops_executed,
        before=w.before, after=after, fsync_s=fsync_s, trace=None)
    if trace:
        from .trace import Trace
        ctx.trace = Trace.from_dir(str(trace_dir))
        res["metrics"] = layer_metrics(cell, ctx)
        device["busy_s"] = ctx.trace.busy_s()
        device["window_s"] = ctx.trace.window_s()
        res["device"] = device
        res["breakdown"] = {"device_ops": ctx.trace.top_ops(10),
                            "idle_gaps": ctx.trace.idle_gaps(10)}
    else:
        res["metrics"] = end_to_end_metrics(cell, timing["setup_s"],
                                            window_s, n_acked, lat)
        res["device"] = device
    res["checks"] = {k: {"value": v, "limit": 0} for k, v in checks.items()}
    shutil.rmtree(run_dir, ignore_errors=True)
    return res


def counters(svc) -> dict:
    kv = inner_store(svc)
    st = svc.stats()["sessions"]
    return {"io": svc.io_stats(), "packed_lanes": st["packed_lanes"],
            "pack_rounds": st["pack_rounds"],
            "slab_lanes": kv.S * svc.W,
            "compactions": int(np.sum(kv.compactions))}


def fetch_schedule(jax, sched) -> list:
    """The recorded rounds as numpy: (tickets, valid, keys, ops, vals)."""
    out = []
    for i in range(0, len(sched), 256):
        part = jax.device_get([(r[7], r[1], r[2], r[3], r[4])
                               for r in sched[i:i + 256]])
        out.extend(tuple(np.asarray(x) for x in r) for r in part)
    return out


def written_keys(loop: Loop) -> np.ndarray:
    keys = []
    for tk, sid, p in loop.enq:
        K, O, _, _ = loop.streams[sid]
        k, o = K[p:p + len(tk)], O[p:p + len(tk)]
        keys.append(k[np.isin(o, WRITE_OPS)])
    return np.unique(np.concatenate(keys)) if keys else np.zeros(0, np.int32)


def end_to_end_metrics(cell: Cell, setup_s: float, window_s: float,
                       n_ops: int, lat_s: np.ndarray) -> dict:
    values = {
        "ops_per_s": n_ops / window_s,
        "p50_ms": quantile_ms(lat_s, 50) if len(lat_s) else None,
        "p99_ms": quantile_ms(lat_s, 99) if len(lat_s) else None,
        "setup_s": setup_s,
    }
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end if values[m["name"]] is not None}


def layer_metrics(cell: Cell, ctx) -> dict:
    out = {}
    for m in cell.per_layer:
        v = load_reader(m["name"]).read(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out
