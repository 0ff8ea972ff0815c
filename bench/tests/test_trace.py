"""The trace reduction, on hand-made events and on a small trace recorded
on the chip (`data/`)."""
import gzip
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench.lib.spec import load_reader
from bench.lib.trace import WINDOW, Event, Trace, _union

DEV = "/device:TPU:0"
MS = 1_000_000


def _trace():
    step = {"module": "jit_routed_step"}
    ops = [Event("fusion.1 fusion", 0 * MS, 2 * MS, step),
           Event("closed_call.7 custom-call", 1 * MS, 4 * MS,
                 dict(step, hlo='%closed_call.7 = s32[8] custom-call(s32[1] '
                                '%max.3), custom_call_target="tpu_custom_call"')),
           Event("fusion.9 fusion", 6 * MS, 7 * MS,
                 {"module": "jit__masked_hc_step"}),
           Event("fusion.2 fusion", 12 * MS, 20 * MS, step)]
    mods = [Event("jit_routed_step(1)", 0, 4 * MS),
            Event("jit__masked_hc_step(2)", 6 * MS, 7 * MS),
            Event("jit_routed_step(1)", 12 * MS, 20 * MS)]
    host = [Event(WINDOW, 0, 11 * MS),
            Event("bench.step", 0, 4 * MS),
            Event("bench.poll", 4 * MS, 9 * MS),
            Event("bench.topup", 9 * MS, 11 * MS)]
    return Trace(ops={DEV: ops}, modules={DEV: mods}, host=host)


def test_union_merges_overlaps():
    assert _union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def test_busy_inside_the_window():
    t = _trace()
    assert t.window_s() == pytest.approx(0.011)
    assert t.busy_s() == pytest.approx(0.005)     # [0, 4] and [6, 7]


def test_module_and_op_time():
    t = _trace()
    assert t.module_count(r"routed_step") == 1    # the second is outside
    assert t.module_s(r"routed_step") == pytest.approx(0.004)
    assert t.module_s(r"_masked_hc_step") == pytest.approx(0.001)
    assert t.op_s(r"tpu_custom_call", r"routed_step") == pytest.approx(
        0.003)
    assert t.op_s(r"tpu_custom_call", r"masked") == 0
    assert t.op_s(r"^fusion\.\d+ fusion$", r"") == pytest.approx(0.003)


def test_idle_gaps_go_to_the_innermost_host_span():
    # idle [4, 6] has its middle in bench.poll; idle [7, 11] has its
    # middle at 9, covered by the end of bench.poll and all of the
    # shorter bench.topup
    assert dict(_trace().idle_gaps()) == pytest.approx(
        {"bench.poll": 0.002, "bench.topup": 0.004})


def test_top_ops_orders_by_time_inside_the_window():
    top = _trace().top_ops(2)
    assert [k for k, _ in top] == ["jit_routed_step/closed_call.7 custom-call",
                                   "jit_routed_step/fusion.1 fusion"]
    assert [v for _, v in top] == pytest.approx([0.003, 0.002])


# -- a 0.1 s window of a small store's traced run on a TPU v5 lite, with
#    what that run reported (`data/small.json`) -------------------------------

DATA = Path(__file__).parent / "data"


@pytest.fixture(scope="module")
def chip():
    data = gzip.decompress((DATA / "small.xplane.pb.gz").read_bytes())
    return Trace.from_bytes(data), json.loads(
        (DATA / "small.json").read_text())


def test_chip_trace_reduces_as_on_the_chip(chip):
    t, rec = chip
    assert t.devices == ["/device:TPU:0"]
    assert t.window_s() == pytest.approx(rec["device"]["window_s"])
    assert t.busy_s() == pytest.approx(rec["device"]["busy_s"])
    assert t.top_ops(10) == [[k, pytest.approx(v)]
                             for k, v in rec["breakdown"]["device_ops"]]
    gaps = t.idle_gaps(10)
    assert gaps == [[k, pytest.approx(v)]
                    for k, v in rec["breakdown"]["idle_gaps"]]
    assert sum(v for _, v in gaps) + t.busy_s() == pytest.approx(
        t.window_s())


def test_chip_trace_ops_belong_to_programs(chip):
    t, _ = chip
    lo, hi = t.window()
    ops = [x for x in t.ops["/device:TPU:0"] if lo <= x.start < hi]
    assert ops and all(x.stats["module"] != "?" for x in ops)
    names = {m.name for m in t.modules["/device:TPU:0"]}
    assert {"jit_routed_step", "jit_pack", "jit__enqueue_kernel",
            "jit__commit_kernel", "jit__free_kernel"} <= names


def test_chip_trace_layer_readers(chip):
    t, rec = chip
    ctx = SimpleNamespace(trace=t, rounds=1)
    for name in ("device_idle_pct", "store_step_dev_ms"):
        assert load_reader(name).read(ctx) == pytest.approx(
            rec["metrics"][name]["value"])


def test_chip_trace_step_roofline(chip):
    """The step's share of its roofline: the least bytes of the ops the
    rounds ran over the routed step's device time, whichever engine runs
    a site; a step that ran no op reads nothing."""
    from bench.lib.ops import OP_READ, OP_UPSERT
    from bench.lib.peaks import peaks_for
    from bench.lib.roofline import least_bytes, op_bytes
    t, _ = chip
    peaks = peaks_for("TPU v5 lite")
    ops = {OP_READ: 900, OP_UPSERT: 124}
    ctx = SimpleNamespace(trace=t, ops_executed=ops, peaks=peaks,
                          config={"store": {"value_width": 2}})
    got = load_reader("store_step_roofline").read(ctx)
    step_s = t.module_s(r"routed_step")
    assert least_bytes(ops, 2) == 1024 * op_bytes(OP_READ, 2) == 1024 * 32
    assert got == pytest.approx(
        100 * 1024 * 32 / peaks["hbm_bytes_per_s"] / step_s)
    assert 0 < got < 100
    ctx.ops_executed = {}
    assert load_reader("store_step_roofline").read(ctx) is None


def test_reader_missing_its_program_reads_nothing_and_says_so(capsys):
    """A renamed program leaves the step readers empty, not 0, and says
    which pattern matched nothing."""
    from bench.lib.peaks import peaks_for
    t = _trace()
    t.modules = {DEV: []}
    t.ops = {DEV: [Event("fusion.1 fusion", 0, MS, {"module": "jit_x"})]}
    ctx = SimpleNamespace(trace=t, rounds=3, ops_executed={1: 10},
                          peaks=peaks_for("TPU v5 lite"),
                          config={"store": {"value_width": 2}})
    assert load_reader("store_step_dev_ms").read(ctx) is None
    assert load_reader("store_step_roofline").read(ctx) is None
    err = capsys.readouterr().err
    assert err.count("no device program matched") == 2


def test_suffixed_metric_reads_with_its_stem():
    assert load_reader("device_idle_pct.rate").read is not None
    assert load_reader("device_idle_pct.rate").__file__ == \
        load_reader("device_idle_pct").__file__


def test_cell_refuses_a_metric_that_moves_what_it_does_not_report(
        tmp_path):
    import shutil

    from bench.lib.spec import cell_spec
    root = Path(__file__).resolve().parents[2]
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for name in (w["name"] for w in bench["workloads"]):
        cell_spec(name, root)          # the committed file is consistent
    cell = next(m for m in bench["per_layer"] if m["moves"] == "p99_ms")
    cell["workloads"] = [w["name"] for w in bench["workloads"]]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    shutil.copytree(root / "bench" / "configs", tmp_path / "bench" / "configs")
    over = next(m for m in bench["end_to_end"]
                if m["name"] == "ops_per_s")["workloads"][0]
    with pytest.raises(ValueError, match="does not report"):
        cell_spec(over, tmp_path)
