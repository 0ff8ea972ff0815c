#!/usr/bin/env python3
"""Readings for the limits of the check: runs of one cell on several
seeds in one process, as the program runs them or with a control (or a
fault) from `bench/lib/faults.py` in the program's place.

    python3 bench/control.py --workload f2-4m.ycsb-a-zipf \\
        --seconds 30 --seeds 11,12,13 --fault int16_values

Prints one JSON line per seed: the seed, the fault, `correct` and every
number compared with its limit.  The benchmark's own runs never run a
control; this script is how its readings are taken on the chip.
"""
import argparse
import gc
import json
import os
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--fault", default="",
                    help="a control or fault of bench/lib/faults.py")
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench.lib import cell as runner
    from bench.lib import faults
    from bench.lib.spec import cell_spec

    cell = cell_spec(args.workload, ROOT)
    for seed in (int(s) for s in args.seeds.split(",")):
        fault = faults.by_name(args.fault) if args.fault else None
        try:
            res = runner.run_cell(cell, seed, args.seconds, False,
                                  t_start=time.perf_counter(),
                                  run_dir=ROOT / ".bench_run", fault=fault)
            out = {"seed": seed, "fault": args.fault or None,
                   "correct": res["correct"], "failed": res["failed"],
                   "attempted": res["attempted"],
                   "checks": {k: v["value"]
                              for k, v in res["checks"].items()}}
        except runner.NoChip:
            raise
        except Exception as e:  # a control that crashes has failed
            traceback.print_exc()
            out = {"seed": seed, "fault": args.fault or None,
                   "correct": False, "crashed": repr(e)}
        print(json.dumps(out), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
