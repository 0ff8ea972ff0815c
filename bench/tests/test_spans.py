"""The program's spans in a traced run (`bench/lib/spans.py`): the idle
split by innermost span on hand-made events, and the readers on a run
that wrote no span and on a rehearsal on the CPU."""
import gzip
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench.lib import spans
from bench.lib.spans import ProgramSpans, layer_of
from bench.lib.spec import load_reader
from bench.lib.trace import WINDOW, Event, Trace

DEV = "/device:TPU:0"
MS = 1_000_000
IDLE_SHARES = ("poll_idle_pct", "enqueue_idle_pct", "step_idle_pct",
               "compaction_idle_pct", "wal_idle_pct",
               "idle_outside_program_pct")


def _device_trace():
    """An 11 ms window whose device is busy in [0, 4] and [6, 7] ms."""
    step = {"module": "jit_routed_step"}
    ops = [Event("fusion.1 fusion", 0, 4 * MS, step),
           Event("fusion.9 fusion", 6 * MS, 7 * MS,
                 {"module": "jit__masked_hc_step"}),
           Event("fusion.2 fusion", 12 * MS, 20 * MS, step)]
    mods = [Event("jit_routed_step(1)", 0, 4 * MS),
            Event("jit__masked_hc_step(2)", 6 * MS, 7 * MS),
            Event("jit_routed_step(1)", 12 * MS, 20 * MS)]
    return Trace(ops={DEV: ops}, modules={DEV: mods},
                 host=[Event(WINDOW, 0, 11 * MS)])


def _span(name, lo_ms, hi_ms, **args):
    return Event("f2." + name, int(lo_ms * MS), int(hi_ms * MS), args)


SPANS = [
    _span("compact.cold_cold", 0.2, 0.6, shards=1),
    _span("compact.hot_cold", 1, 2.5, shards=1),
    _span("compact.chunk_gc", 1.2, 1.4, shards=1),      # inside a pass
    _span("compact.bounds", 2, 2.4),
    _span("sessions.step", 3, 5, round=7),
    _span("compact.bounds", 4.5, 5),
    _span("sessions.poll", 5.5, 9, sid=0, n=3),
    _span("sessions.fetch", 7.5, 8),
    _span("sessions.collect", 8, 9, n=2),
    _span("sessions.fetch", 8, 8.3),                    # starts with it
    _span("sessions.enqueue", 9.5, 10, sid=1, ticket=0, n=4),
    _span("wal.sync", 10, 10.5, bytes=96),
    _span("sessions.enqueue", 10.8, 12, sid=2, ticket=4, n=4),
    _span("compact.hot_cold", 11.5, 12, shards=1),      # after the window
    _span("sessions.poll", 13, 14, sid=0, n=3),         # outside it
]


def _ctx(spans_=SPANS):
    t = _device_trace()
    return SimpleNamespace(trace=t, rounds=1,
                           program_spans=ProgramSpans.of(t, spans_))


def test_layers():
    assert [layer_of(n) for n in (
        "f2.sessions.poll", "f2.sessions.fetch", "f2.sessions.collect",
        "f2.sessions.enqueue", "f2.sessions.step", "f2.sessions.fold",
        "f2.sharded.apply_round", "f2.rebalance.check", "f2.compact.bounds",
        "f2.compact.hot_cold", "f2.wal.log_slab", "f2.durability.snapshot",
        None)] == ["poll", "poll", "poll", "enqueue", "step", "step", "step",
                   "step", "compaction", "compaction", "wal", "wal",
                   "outside"]


def test_idle_goes_to_the_innermost_program_span():
    """Idle [4, 6] and [7, 11] ms: each piece to the span that started
    last among those covering it, the child on a tied start."""
    ps = _ctx().program_spans
    assert len(ps.spans) == 13               # the last two lie outside
    assert ps.idle == [(4 * MS, 6 * MS), (7 * MS, 11 * MS)]
    assert ps.idle_by_span == pytest.approx({
        "f2.sessions.step": 0.0005, "f2.compact.bounds": 0.0005,
        "f2.sessions.poll": 0.001, "f2.sessions.fetch": 0.0008,
        "f2.sessions.collect": 0.0007, "f2.sessions.enqueue": 0.0007,
        "f2.wal.sync": 0.0005, None: 0.0013})


def test_idle_shares_sum_to_device_idle():
    ctx = _ctx()
    got = {m: load_reader(m).read(ctx) for m in IDLE_SHARES}
    assert got == pytest.approx({
        "poll_idle_pct": 100 * 2.5 / 11, "enqueue_idle_pct": 100 * 0.7 / 11,
        "step_idle_pct": 100 * 0.5 / 11,
        "compaction_idle_pct": 100 * 0.5 / 11,
        "wal_idle_pct": 100 * 0.5 / 11,
        "idle_outside_program_pct": 100 * 1.3 / 11})
    assert sum(got.values()) == pytest.approx(
        load_reader("device_idle_pct").read(ctx))
    rated = {m: load_reader(m + ".rate").read(ctx) for m in IDLE_SHARES}
    assert rated == got


def test_program_span_durations():
    """A pass is timed where no other pass encloses it and it starts in
    the window; a layer with no span in the window reads nothing."""
    ctx = _ctx()
    assert load_reader("compaction_pass_ms").read(ctx) == pytest.approx(
        (0.4 + 1.5) / 2)
    assert load_reader("wal_sync_ms").read(ctx) == pytest.approx(0.5)
    ctx = _ctx([sp for sp in SPANS if not sp.name.startswith(
        ("f2.wal.", "f2.compact.h", "f2.compact.c"))])
    for name in ("wal_sync_ms", "wal_idle_pct", "compaction_pass_ms"):
        assert load_reader(name).read(ctx) is None
    assert load_reader("compaction_idle_pct").read(ctx) is not None


def test_a_run_without_program_spans_reads_nothing_new(tmp_path,
                                                       monkeypatch):
    """A program that writes no span to the profile (the small chip
    trace's, recorded before the spans existed), an untraced run, and a
    profile of another window: the new readers read nothing, and the old
    ones what that run reported."""
    data = Path(__file__).parent / "data"
    t = Trace.from_bytes(gzip.decompress(
        (data / "small.xplane.pb.gz").read_bytes()))
    rec = json.loads((data / "small.json").read_text())
    monkeypatch.setattr(spans, "RUN_TRACE_DIR", tmp_path)
    names = IDLE_SHARES + ("compaction_pass_ms", "wal_sync_ms")
    for ctx in (SimpleNamespace(trace=t, rounds=1),
                SimpleNamespace(trace=None, rounds=1)):
        assert [load_reader(n).read(ctx) for n in names] == [None] * 8
    ctx = SimpleNamespace(trace=t, rounds=1)
    for name in ("device_idle_pct", "store_step_dev_ms"):
        assert load_reader(name).read(ctx) == pytest.approx(
            rec["metrics"][name]["value"])
    ctx = SimpleNamespace(trace=_device_trace())
    (tmp_path / "x.xplane.pb").write_bytes(gzip.decompress(
        (data / "small.xplane.pb.gz").read_bytes()))
    assert load_reader("poll_idle_pct").read(ctx) is None


@pytest.mark.parametrize("name", ["f2d-4m.ycsb-a-zipf",
                                  "f2-4m.ycsb-b-zipf-over-knee"])
def test_traced_rehearsal_reads_the_program_spans(run_tiny, tmp_path,
                                                  monkeypatch, name):
    """A traced run of a tiny cell on the CPU: the program's spans are in
    its profile, every layer reads, and the shares sum to the device's
    idle share (100% here: no device plane)."""
    from conftest import ROOT

    from bench.lib.spec import cell_spec
    monkeypatch.setattr(spans, "RUN_TRACE_DIR", tmp_path / "run" / "trace")
    res = run_tiny(name, trace=True)
    assert res["correct"], res["checks"]
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(m) <= {x["name"] for x in cell_spec(name, ROOT).per_layer}
    suffix = ".rate" if name.endswith("over-knee") else ""
    shares = [s + suffix for s in IDLE_SHARES
              if suffix == "" or s != "wal_idle_pct"]
    assert set(shares) <= set(m)
    assert sum(m[s] for s in shares) == pytest.approx(
        m["device_idle_pct" + suffix])
    assert m["compaction_idle_pct" + suffix] > 0        # the bounds reads
    if suffix == "":
        assert m["wal_sync_ms"] > 0 and m["wal_idle_pct"] > 0
