#!/usr/bin/env python3
"""The chip benchmark: one run of one cell.

    python3 bench/run.py --workload f2-4m.ycsb-a-zipf --seed 7 \\
        --seconds 51 --trace 0

A cell is one entry of `workloads` in BENCHMARK.json: a deployment
(`bench/configs/<config>.json`) under a traffic mix
(`bench/traffic/<traffic>.json`).  With `--trace 0` the run reports the
cell's end-to-end metrics; with `--trace 1` it records a profiler trace of
the window and reports the cell's per-layer metrics, each read by
`bench/metrics/<metric>.py`, plus the device's busy time and a breakdown.

The last line of standard output is one JSON object (`correct`,
`attempted`, `failed`, `metrics`, `device`, with `--trace 1` also
`breakdown`, and last `checks`: every number compared with its limit).
The same checks are the last lines of standard error.  Without a TPU, or
with fewer chips than the cell asks for, the run exits non-zero and
prints no result.

JAX's persistent compilation cache lives in `.jax_cache/` at the root of
the checkout, so only a cell's first run there compiles; run files (the
durable cell's WAL, the trace) live in `.bench_run/` and are removed at
the end of each run.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / ".jax_cache"
RUN_DIR = ROOT / ".bench_run"
NO_CHIP_EXIT = 3


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # the cache directory is part of the cache key: a fixed path inside
    # the checkout, set before JAX reads its configuration
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench.lib import cell as runner
    from bench.lib.spec import cell_spec

    cell = cell_spec(args.workload, ROOT)
    from repro import compile_cache
    compile_cache.enable()
    try:
        res = runner.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), t_start=T_START,
                              run_dir=RUN_DIR)
    except runner.NoChip as e:
        print(f"bench: {e}; nothing run", file=sys.stderr)
        return NO_CHIP_EXIT
    for name, c in res["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
