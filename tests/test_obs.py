"""Observability layer (`repro.obs`): registry semantics, the
bit-compatible registry-backed `stats()` contract, Chrome-trace schema,
journal bounding, and the disabled-mode no-op fast path.

The load-bearing property is back-compat: with observability enabled,
every facade's `stats()` tree is rebuilt leaf-for-leaf from registry
gauges, and must be value- and type-identical to the disabled tree on an
identical op stream — while the store's outputs stay bit-exact.  The
disabled path must be free: shared no-op singletons, zero registry or
journal traffic, the identical tree object passed through."""
import json

import numpy as np
import pytest

from repro import obs
from repro.core import KV, F2Config
from repro.core.replication import ReplicatedKV
from repro.core.sharded import ShardedKV
from repro.core.types import OP_DELETE, OP_READ, OP_RMW, OP_UPSERT
from repro.obs import export
from repro.obs.journal import Journal
from repro.obs.metrics import (COUNT_BUCKETS, METRIC_NAME, MetricError,
                               MetricsRegistry, fold_stats)
from repro.obs.report import summarize
from repro.obs.trace import NOOP_SPAN, Tracer
from repro.serve.serve_step import ServiceConfig, make_session_service

V = 2
B = 64


def tiny_cfg(**kw):
    base = dict(hot_index_size=1 << 8, hot_capacity=1 << 9, hot_mem=1 << 6,
                cold_capacity=1 << 11, cold_mem=1 << 6, n_chunks=1 << 6,
                chunklog_capacity=1 << 9, chunklog_mem=1 << 5,
                rc_capacity=1 << 6, value_width=V, chain_max=48)
    base.update(kw)
    return F2Config(**base)


@pytest.fixture(autouse=True)
def _obs_clean():
    """Every test starts and ends disabled with empty registry/trace/
    journal — observability is process-global state."""
    obs.configure(enabled=False, reset=True)
    yield
    obs.configure(enabled=False, reset=True)


# ---------------------------------------------------------------------------
# metrics registry semantics
# ---------------------------------------------------------------------------

def test_counter_monotone_and_negative_raises():
    reg = MetricsRegistry()
    c = reg.counter("c_total", labels=("facade",)).labels(facade="kv")
    c.inc()
    c.inc(4)
    assert c.value == 5
    with pytest.raises(MetricError):
        c.inc(-1)
    c.set_total(100)            # absolute fold of a device-side running sum
    assert c.value == 100


def test_metric_kind_and_label_mismatch_raise():
    reg = MetricsRegistry()
    reg.counter("m", labels=("a",))
    with pytest.raises(MetricError):
        reg.gauge("m", labels=("a",))
    with pytest.raises(MetricError):
        reg.counter("m", labels=("b",))
    with pytest.raises(MetricError):
        reg.counter("m", labels=("a",)).labels(wrong=1)
    # idempotent get-or-create: the same declaration returns the family
    assert reg.counter("m", labels=("a",)) is reg.counter("m", labels=("a",))


def test_metric_name_must_be_prometheus_valid():
    reg = MetricsRegistry()
    for bad in ("f2_stats_engines_read.hot_backend", "1abc", "a-b", ""):
        with pytest.raises(MetricError):
            reg.gauge(bad)
    reg.gauge("f2_ok:sub_1")


def test_histogram_bucket_edges_validated():
    reg = MetricsRegistry()
    with pytest.raises(MetricError):
        reg.histogram("h_bad", buckets=(1.0, 1.0, 2.0))     # not strict
    with pytest.raises(MetricError):
        reg.histogram("h_bad2", buckets=(2.0, 1.0))         # decreasing
    reg.histogram("h", buckets=(1.0, 2.0, 4.0))
    with pytest.raises(MetricError):                        # redeclared
        reg.histogram("h", buckets=(1.0, 2.0))


def test_histogram_binning_at_edges():
    reg = MetricsRegistry()
    h = reg.histogram("h", buckets=(1.0, 2.0, 4.0)).labels()
    h.observe_many([0.5, 1.0, 1.5, 2.0, 3.0, 5.0])
    # v <= edge bins into that bucket; last slot catches > max edge
    assert h.counts == [2, 2, 1, 1]
    assert h.count == 6
    assert h.sum == pytest.approx(13.0)


def test_gauge_stores_raw_python_values():
    reg = MetricsRegistry()
    g = reg.gauge("g", labels=("k",))
    for raw in (3, 2.5, True, "least_loaded", [1, 2, 3], [0.5, 1.5]):
        g.labels(k="x").set(raw)
        got = g.labels(k="x").value
        assert got is raw               # no copy, no coercion
        assert type(got) is type(raw)


# ---------------------------------------------------------------------------
# disabled-mode fast path
# ---------------------------------------------------------------------------

def test_disabled_helpers_are_noops():
    assert not obs.enabled()
    obs.count("f2_x_total", 3, facade="kv")
    obs.count_total("f2_y_total", 10, facade="kv")
    obs.gauge_set("f2_z", 1.5, facade="kv")
    obs.observe("f2_h", 2.0, buckets=COUNT_BUCKETS, facade="kv")
    assert obs.get_registry().names() == []
    assert obs.journal.emit("compaction.hot_cold", facade="kv") is None
    assert len(obs.journal.JOURNAL) == 0


def test_disabled_span_is_shared_noop_singleton():
    s1 = obs.span("a", cat="serve", n=1)
    s2 = obs.span("b")
    assert s1 is s2 is NOOP_SPAN        # zero-allocation: one shared object
    with s1:
        pass
    assert len(obs.trace.TRACER) == 0


def test_disabled_fold_stats_is_identity():
    tree = {"io": {"read_ops": 7}, "shards": {"fill": [0.1, 0.2]}}
    assert obs.fold_stats("kv", tree) is tree
    assert obs.get_registry().names() == []


def test_enabled_fold_stats_rebuilds_tree_bit_compatibly():
    obs.configure(enabled=True)
    tree = {"io": {"read_ops": 7, "frac": 0.25},
            "shards": {"fill": [0.1, 0.2], "selector": "round_robin",
                       "alive": [True, False]}}
    out = fold_stats("sharded", tree)
    assert out == tree and out is not tree
    assert type(out["io"]["read_ops"]) is int
    assert type(out["io"]["frac"]) is float
    assert out["shards"]["fill"] is tree["shards"]["fill"]
    reg = obs.get_registry()
    assert "f2_stats_io_read_ops" in reg.names()
    g = reg.get("f2_stats_shards_selector")
    assert g.labels(facade="sharded").value == "round_robin"


# ---------------------------------------------------------------------------
# trace
# ---------------------------------------------------------------------------

CHROME_COMPLETE_KEYS = {"name", "cat", "ph", "ts", "dur", "pid", "tid",
                        "args"}


def _validate_chrome_trace(doc: dict):
    assert set(doc) == {"traceEvents", "displayTimeUnit", "otherData"}
    for ev in doc["traceEvents"]:
        assert ev["ph"] == "X", ev
        assert CHROME_COMPLETE_KEYS <= set(ev), ev
        assert ev["ts"] >= 0 and ev["dur"] >= 0
        assert isinstance(ev["pid"], int) and isinstance(ev["tid"], int)
        json.dumps(ev)                  # every event is JSON-able


def test_span_emits_chrome_complete_event(tmp_path):
    obs.configure(enabled=True)
    with obs.span("unit.work", cat="test", n=3):
        pass
    doc = obs.trace.TRACER.snapshot()
    _validate_chrome_trace(doc)
    ev = doc["traceEvents"][0]
    assert (ev["name"], ev["cat"], ev["args"]) == ("unit.work", "test",
                                                   {"n": 3})
    path = obs.trace.TRACER.save(str(tmp_path / "trace.json"))
    with open(path) as f:
        _validate_chrome_trace(json.load(f))


def test_tracer_capacity_bound():
    tr = Tracer(capacity=4)
    for i in range(6):
        with tr.span(f"s{i}"):
            pass
    assert len(tr) == 4 and tr.dropped == 2
    assert tr.snapshot()["otherData"]["dropped"] == 2


def test_store_run_produces_valid_trace():
    obs.configure(enabled=True)
    kv = ShardedKV(tiny_cfg(), 2, trigger=0.6, compact_batch=64,
                   donate=False)
    _drive(kv)
    doc = obs.trace.TRACER.snapshot()
    names = {ev["name"] for ev in doc["traceEvents"]}
    assert "sharded.apply_round" in names
    _validate_chrome_trace(doc)


# -- spans in the JAX profiler's trace ----------------------------------------

def _profiled_spans(trace_dir) -> list:
    """(name, args, start_ns, end_ns) of every `f2.*` event on the host
    plane of the one `.xplane.pb` under `trace_dir`."""
    import glob
    from jax.profiler import ProfileData
    paths = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    assert len(paths) == 1, paths
    out = []
    for plane in ProfileData.from_file(paths[0]).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            out.extend((e.name, dict(e.stats), int(e.start_ns),
                        int(e.end_ns)) for e in line.events
                       if e.name.startswith("f2."))
    return out


def _inside(child, parent) -> bool:
    return parent[2] <= child[2] and child[3] <= parent[3]


def test_spans_reach_the_profiler_trace_without_obs(tmp_path):
    """With obs off and a profiler recording, a routed round's spans land
    on the trace's host plane as `f2.<name>` with their args (the round's
    bounds read nested in it), and nothing reaches the Chrome buffer."""
    import jax
    kv = ShardedKV(tiny_cfg(), 2, trigger=0.6, compact_batch=64,
                   donate=False)
    keys = np.arange(1, B + 1, dtype=np.int32)
    vals = np.zeros((B, V), np.int32)
    kv.upsert(keys, vals)                       # compiled outside the trace
    rounds = kv.rounds
    with jax.profiler.trace(str(tmp_path)):
        assert obs.span("x") is not NOOP_SPAN
        kv.upsert(keys, vals + 1)
    assert obs.span("x") is NOOP_SPAN
    assert len(obs.trace.TRACER) == 0
    spans = _profiled_spans(tmp_path)
    apply = [s for s in spans if s[0] == "f2.sharded.apply_round"]
    assert [s[1] for s in apply] == [{"B": B, "round": rounds}]
    bounds = [s for s in spans if s[0] == "f2.compact.bounds"]
    assert bounds and all(s[1] == {} for s in bounds)
    assert _inside(bounds[0], apply[0])


def test_session_round_spans_nest_in_the_profiler_trace(tmp_path):
    """Enqueue, step and poll of the session service, through a durable
    store, each under its own span: the round's one pool fetch and the
    collection inside the poll, the WAL append inside the routed round,
    the group commit inside the step; with obs armed the buffer records
    them too."""
    import jax

    from repro.core.durability import DurabilityConfig, DurableKV
    from repro.serve.sessions import KVSessionService
    svc = KVSessionService(DurableKV(_sharded(), DurabilityConfig(
        dir=str(tmp_path / "wal"))), max_sessions=2, session_depth=64)
    s = svc.open_session()
    keys = np.arange(1, 17, dtype=np.int32)
    ops = np.full(16, OP_UPSERT, np.int32)

    def one_round():
        tk = s.enqueue(keys, ops, np.ones((16, V), np.int32))
        svc.step()
        done, _, _ = s.poll(tk)
        assert done.all()
        return int(tk[0])

    one_round()                                 # compiled outside the trace
    obs.configure(enabled=True)
    with jax.profiler.trace(str(tmp_path / "trace")):
        first = one_round()
    spans = _profiled_spans(tmp_path / "trace")
    by = {}
    for sp in spans:
        by.setdefault(sp[0], []).append(sp)
    (enq,) = by["f2.sessions.enqueue"]
    assert enq[1] == {"sid": s.sid, "ticket": first, "n": 16}
    (step,) = by["f2.sessions.step"]
    assert step[1] == {"round": svc.pack_rounds - 1}
    (poll,) = by["f2.sessions.poll"]
    assert poll[1] == {"sid": s.sid, "n": 16}
    (collect,) = by["f2.sessions.collect"]
    assert collect[1] == {"n": 16} and _inside(collect, poll)
    (fetch,) = by["f2.sessions.fetch"]
    assert _inside(fetch, poll) and not _inside(fetch, collect)
    (apply,) = by["f2.sharded.apply_round"]
    (log,) = by["f2.wal.log_slab"]
    (sync,) = by["f2.wal.sync"]
    assert _inside(apply, step) and _inside(log, apply)
    assert _inside(sync, step) and not _inside(sync, apply)
    assert log[1]["bytes"] == sync[1]["bytes"] > 16 * 4 * (2 + V)
    assert all(_inside(b, apply) for b in by["f2.compact.bounds"])
    buffered = {ev["name"] for ev in obs.trace.TRACER.snapshot()[
        "traceEvents"]}
    assert {name[3:] for name in by} <= buffered
    svc.kv.close()


# ---------------------------------------------------------------------------
# journal
# ---------------------------------------------------------------------------

def test_journal_bounded_eviction():
    j = Journal(capacity=8)
    for i in range(20):
        j.emit("unit.tick", i=i)
    assert len(j) == 8
    assert j.total == 20 and j.dropped == 12
    evs = j.events()
    assert [e["seq"] for e in evs] == list(range(12, 20))   # oldest evicted
    snap = j.snapshot()
    assert (snap["capacity"], snap["total"], snap["dropped"]) == (8, 20, 12)


def test_journal_prefix_and_exact_filters():
    j = Journal()
    j.emit("compaction.hot_cold", facade="kv")
    j.emit("compaction.chunk_gc", facade="kv")
    j.emit("rebalance.migrated", buckets=2)
    assert [e["kind"] for e in j.events("compaction.")] == [
        "compaction.hot_cold", "compaction.chunk_gc"]
    assert len(j.events("rebalance.migrated")) == 1
    assert j.kinds() == ["compaction.hot_cold", "compaction.chunk_gc",
                         "rebalance.migrated"]


def test_compaction_emits_journal_and_counter():
    obs.configure(enabled=True)
    kv = KV(tiny_cfg(), trigger=0.6, compact_batch=64, donate=False)
    rng = np.random.default_rng(3)
    for _ in range(8):          # enough writes to trip the pressure trigger
        keys = rng.integers(1, 400, B).astype(np.int32)
        kv.upsert(keys, rng.integers(0, 100, (B, V)).astype(np.int32))
    kinds = obs.journal.events("compaction.")
    assert kinds, "no compaction fired under trigger=0.6"
    total = sum(c.value for _, c in
                obs.get_registry().get("f2_compactions_total").samples())
    assert total == len(kinds)


# ---------------------------------------------------------------------------
# registry-backed stats(): bit-compat across every facade
# ---------------------------------------------------------------------------

def _kv():
    return KV(tiny_cfg(), trigger=0.6, compact_batch=64, donate=False)


def _sharded():
    return ShardedKV(tiny_cfg(), 4, trigger=0.6, compact_batch=64,
                     donate=False)


def _replicated():
    return ReplicatedKV(tiny_cfg(), 2, n_replicas=2, trigger=0.6,
                        compact_batch=64, donate=False)


def _sessions():
    return make_session_service(tiny_cfg(), ServiceConfig(
        n_shards=2, lanes=32, max_sessions=2, session_depth=32,
        store_kwargs=dict(trigger=0.6, compact_batch=64, donate=False)))


def _durable(tmp):
    from repro.core.durability import DurabilityConfig, DurableKV
    return DurableKV(_sharded(), DurabilityConfig(
        dir=str(tmp), snapshot_every_rounds=4))


FACADES = ["kv", "sharded", "replicated", "sessions", "durable"]


def _build(name, tmp):
    if name == "durable":
        d = tmp / f"d{len(list(tmp.iterdir()))}"
        d.mkdir()
        return _durable(d)
    return {"kv": _kv, "sharded": _sharded, "replicated": _replicated,
            "sessions": _sessions}[name]()


def _drive(store):
    """A deterministic mixed op stream that trips compaction; returns the
    per-batch (status, values) outputs for bit-exactness checks."""
    rng = np.random.default_rng(7)
    outs = []
    for _ in range(4):
        keys = (rng.zipf(1.3, B) % 200).astype(np.int32) + 1
        ops = rng.choice([OP_READ, OP_UPSERT, OP_RMW, OP_DELETE], B,
                         p=[.3, .4, .15, .15]).astype(np.int32)
        vals = rng.integers(0, 1000, (B, V)).astype(np.int32)
        st, rv = store.apply(keys, ops, vals)
        outs.append((np.asarray(st), np.asarray(rv)))
    st, rv = store.read(np.arange(1, 129, dtype=np.int32))
    outs.append((np.asarray(st), np.asarray(rv)))
    return outs


@pytest.mark.parametrize("name", FACADES)
def test_stats_bit_compatible_enabled_vs_disabled(name, tmp_path):
    """Twin stores, identical op stream: the registry-backed stats() tree
    must equal the raw disabled tree leaf for leaf, and the serving
    outputs must be bit-exact — observability changes nothing callers
    see."""
    obs.configure(enabled=False, reset=True)
    off_store = _build(name, tmp_path)
    off_out = _drive(off_store)
    off_stats = off_store.stats()

    obs.configure(enabled=True, reset=True)
    on_store = _build(name, tmp_path)
    on_out = _drive(on_store)
    on_stats = on_store.stats()

    for (st_a, rv_a), (st_b, rv_b) in zip(off_out, on_out):
        np.testing.assert_array_equal(st_a, st_b)
        np.testing.assert_array_equal(rv_a, rv_b)
    assert on_stats == off_stats
    # every leaf round-trips type-intact through the gauges
    _assert_same_leaf_types(off_stats, on_stats)
    # and the enabled side actually went through the registry
    assert any(n.startswith("f2_stats_io_")
               for n in obs.get_registry().names())


@pytest.mark.parametrize("name", FACADES)
def test_prometheus_names_valid_for_facade_stats(name, tmp_path):
    """A facade's stats() (engine call sites included, whose keys hold
    dots) must leave a /metrics page a Prometheus scraper accepts."""
    obs.configure(enabled=True, reset=True)
    store = _build(name, tmp_path)
    _drive(store)
    engines = store.stats()["engines"]
    assert engines["read.hot"]["backend"] == "fused_ref"
    assert "f2_stats_engines_read_hot_backend" in obs.get_registry().names()
    for line in export.prometheus_text().splitlines():
        if line.startswith("#"):
            metric = line.split()[2]
        else:
            metric = line.split("{")[0].split()[0]
        assert METRIC_NAME.fullmatch(metric), line


def _assert_same_leaf_types(a, b, path=()):
    assert type(a) is type(b), (path, type(a), type(b))
    if isinstance(a, dict):
        assert a.keys() == b.keys(), path
        for k in a:
            _assert_same_leaf_types(a[k], b[k], path + (k,))


def test_chain_hops_histogram():
    cfg = tiny_cfg()
    kv = KV(cfg, trigger=2.0, donate=False)
    keys = np.arange(1, 129, dtype=np.int32)
    kv.upsert(keys, np.stack([keys] * V, 1).astype(np.int32))
    hops_off = kv.chain_hops(keys)
    assert obs.get_registry().get("f2_chain_hops") is None

    obs.configure(enabled=True)
    hops_on = kv.chain_hops(keys)
    np.testing.assert_array_equal(hops_off, hops_on)
    h = obs.get_registry().get("f2_chain_hops").labels(facade="kv")
    assert h.count == len(keys)


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------

def test_prometheus_text_format():
    obs.configure(enabled=True)
    obs.count("f2_unit_total", 3, facade="kv")
    obs.gauge_set("f2_unit_fill", [0.5, 1.5], facade="kv")
    obs.gauge_set("f2_unit_mode", "round_robin", facade="kv")
    obs.observe("f2_unit_rounds", [1, 3], buckets=(1.0, 2.0, 4.0),
                facade="kv")
    text = export.prometheus_text()
    assert '# TYPE f2_unit_total counter' in text
    assert 'f2_unit_total{facade="kv"} 3' in text
    assert 'f2_unit_fill{facade="kv",idx="0"} 0.5' in text   # list fan-out
    assert "f2_unit_mode{" not in text                       # strings skipped
    assert 'f2_unit_rounds_bucket{facade="kv",le="1"} 1' in text
    assert 'f2_unit_rounds_bucket{facade="kv",le="+Inf"} 2' in text
    assert 'f2_unit_rounds_count{facade="kv"} 2' in text
    assert text.endswith("\n")


def test_bench_envelope_schema(tmp_path):
    obs.configure(enabled=True)
    obs.count("f2_unit_total", 1, facade="kv")
    path = str(tmp_path / "BENCH_unit.json")
    export.write_bench_json(path, bench="unit", config={"tiny": True},
                            results={"ops_per_s": 1e4})
    with open(path) as f:
        doc = json.load(f)
    assert set(doc) == {"schema_version", "bench", "config", "git_sha",
                       "results", "metrics_snapshot"}
    assert doc["schema_version"] == export.SCHEMA_VERSION
    assert doc["bench"] == "unit"
    assert "f2_unit_total" in doc["metrics_snapshot"]


def test_snapshot_and_report_summarize(tmp_path):
    obs.configure(enabled=True)
    obs.count("f2_unit_total", 2, facade="kv")
    obs.observe("f2_unit_rounds", [1, 1, 9], buckets=(1.0, 2.0, 4.0),
                facade="kv")
    obs.journal.emit("compaction.hot_cold", facade="kv", records=8)
    path = export.save_snapshot(str(tmp_path / "obs.json"))
    with open(path) as f:
        doc = json.load(f)
    assert doc["schema_version"] == export.SCHEMA_VERSION
    assert doc["journal"]["total"] == 1

    out = summarize(doc)                        # full snapshot shape
    assert "f2_unit_total{facade=kv} = 2" in out
    assert "compaction.hot_cold x1" in out
    assert "p99<=inf" in out                    # 9 overflows the last edge
    # the other two shapes the CLI accepts
    assert "f2_unit_total" in summarize(doc["metrics"])
    env = export.bench_envelope("unit", {}, {})
    assert "bench: unit" in summarize(env)
    assert summarize({}) == "(empty snapshot)"
