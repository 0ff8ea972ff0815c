"""The cell logic at a tiny size on the CPU, against the reference, and
the command's refusal to run without a chip."""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from conftest import ROOT

CELLS = [w["name"] for w in json.loads(
    (ROOT / "BENCHMARK.json").read_text())["workloads"]]


def _cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "2147483659", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _has_result(stdout: str) -> bool:
    lines = stdout.strip().splitlines()
    return bool(lines) and lines[-1].lstrip().startswith("{")


def test_cli_exits_nonzero_without_tpu():
    p = _cli(ROOT)
    assert p.returncode != 0, p.stdout
    assert not _has_result(p.stdout)
    assert "TPU" in p.stderr


def test_cli_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(tmp_path)
    assert p.returncode != 0
    assert not _has_result(p.stdout)


@pytest.mark.parametrize("name", CELLS)
def test_cell_matches_reference(run_tiny, name):
    res = run_tiny(name)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert all(c["value"] == 0 for c in res["checks"].values())
    from bench.lib.spec import cell_spec
    assert set(res["metrics"]) == {m["name"] for m in cell_spec(
        name, ROOT).end_to_end}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("name,counters", [
    ("f2d-4m.ycsb-a-zipf", {"slab_occupancy_pct", "stable_reads_per_kop",
                            "wal_fsync_ms"}),
    ("f2-4m.ycsb-b-zipf-over-knee", {"slab_occupancy_pct.rate",
                                     "stable_reads_per_kop.rate"}),
])
def test_traced_cell_reports_its_counters(run_tiny, name, counters):
    from bench.lib.spec import cell_spec
    res = run_tiny(name, trace=True)
    assert res["correct"], res["checks"]
    m = res["metrics"]
    # no device plane on the CPU: only the program's counters read here
    assert counters <= set(m) <= {x["name"] for x in cell_spec(
        name, ROOT).per_layer}
    assert all(m[c]["value"] > 0 for c in counters)
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_window_opens_on_an_idle_service(tmp_path):
    """The warm-up's traffic is all answered before the window opens, so a
    warm-up slowed by compiles leaves the window nothing to catch up on,
    and the window's arrivals count from its opening."""
    from conftest import tiny_cell

    from bench.lib import cell as runner
    c = tiny_cell("f2-4m.ycsb-a-zipf")
    dep = runner.set_up(c, 3, tmp_path / "run")
    loop = runner.Loop(dep.svc, 4, 64, annotate=False)
    seen = {}

    def on_open():
        seen["outstanding"] = loop.outstanding()
        seen["offered_all"] = loop.offered_all()
        seen["acks"] = len(loop.acks)

    with dep.stack:
        w = runner.run_window(dep, loop, 2000.0, 0.5, 1.0, on_open)
    assert seen["outstanding"] == 0 and seen["offered_all"]
    assert seen["acks"] > 0
    due = np.concatenate([d[1] for d in loop.due])
    assert due[due >= w.t0].min() == pytest.approx(w.t0)
    assert len(w.timeline) > 0 and all(len(r) == 6 for r in w.timeline)
