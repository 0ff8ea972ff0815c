#!/usr/bin/env python3
"""Find the highest rate a cell's deployment sustains: one set-up, then
windows at rising offered rates, each followed by a drain.

    python3 bench/sweep.py --workload f2-4m.ycsb-b-zipf-over-knee --seed 5 \\
        --seconds 10 --rates 30000,40000,50000,60000

Prints one JSON line per rate: ops offered and acknowledged per second in
the window, p50/p99 of the ops due in it, and how late the generator ran.
A rate is sustained while the acknowledged rate keeps up with the offered
one and the generator's lateness stays bounded; a cell below the knee
offers about four fifths of the highest sustained rate, and a cell above
it about 1.1 times (PERF.md).  The benchmark's own runs never sweep.
"""
import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
WARMUP_S = 2.0
DRAIN_S = 60.0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True, help="comma-separated ops/s")
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax
    import numpy as np

    from bench.lib import cell as runner
    from bench.lib.spec import cell_spec

    cell = cell_spec(args.workload, ROOT)
    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU; nothing run", file=sys.stderr)
        return 3
    dep = runner.set_up(cell, args.seed, ROOT / ".bench_run")
    dep.svc.trace_schedule = False      # a sweep checks no answers
    loop = runner.Loop(dep.svc, int(dep.traffic["sessions"]),
                       int(dep.traffic["chunk"]), annotate=False)
    loop.watch = dep.watch
    with dep.stack:
        for rate in (float(r) for r in args.rates.split(",")):
            w = runner.run_window(dep, loop, rate, WARMUP_S, args.seconds)
            loop.run_out(DRAIN_S)
            due, lat, n_acked = runner.window_ops(loop, w)
            lat = lat[~np.isnan(lat)]
            win = w.t1 - w.t0
            print(json.dumps({
                "rate": rate, "offered_per_s": len(due) / win,
                "acked_per_s": n_acked / win,
                "p50_ms": float(np.percentile(lat, 50) * 1e3),
                "p99_ms": float(np.percentile(lat, 99) * 1e3),
                "lag_max_ms": float(w.lags.max() * 1e3),
                "lag_end_ms": float(w.lags[-1] * 1e3),
                "outstanding_at_close": w.outstanding}), flush=True)
            loop.acks, loop.due, loop.enq = [], [], []
    close = getattr(dep.svc.kv, "close", None)
    if close is not None:
        close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
