"""Find a cell's configuration, traffic mix and metrics by name in
`BENCHMARK.json`.  Everything that belongs to one configuration, one
traffic mix or one per-layer metric sits in a file of its own:

    bench/configs/<config>.json     (the `file` of the configuration)
    bench/traffic/<traffic>.json    (one mix)
    bench/metrics/<metric>.py       (one per-layer reader)

One quantity that moves different end-to-end metrics in different cells
is declared once for each, as `<metric>` and `<metric>.<suffix>`; both
are read by `bench/metrics/<metric>.py`.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from typing import List

ROOT = Path(__file__).resolve().parents[2]
BENCH_DIR = Path(__file__).resolve().parents[1]


@dataclass
class Cell:
    name: str
    chips: int
    config: dict            # the configuration file's contents
    traffic: dict           # the traffic file's contents
    end_to_end: List[dict]  # the cell's end-to-end metric entries
    per_layer: List[dict]   # the cell's per-layer metric entries


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _applies(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def cell_spec(name: str, root: Path = ROOT) -> Cell:
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(root / conf["file"]) as f:
        config = json.load(f)
    with open(BENCH_DIR / "traffic" / f"{w['traffic']}.json") as f:
        traffic = json.load(f)
    e2e = [m for m in bench["end_to_end"] if _applies(m, name)]
    reported = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"] if _applies(m, name)]
    for m in layer:
        if m["moves"] not in reported:
            raise ValueError(f"per-layer metric {m['name']!r} moves "
                             f"{m['moves']!r}, which cell {name!r} does "
                             f"not report")
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=layer)


def load_reader(metric: str):
    """The module `bench/metrics/<metric>.py`, or for `<stem>.<suffix>`
    without a file of its own `bench/metrics/<stem>.py`: its `read(ctx)`
    returns the metric's value, or None where the run gave it nothing to
    read."""
    path = BENCH_DIR / "metrics" / f"{metric}.py"
    if not path.exists():
        path = BENCH_DIR / "metrics" / f"{metric.split('.', 1)[0]}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
