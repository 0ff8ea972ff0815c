"""store_step_dev_ms (layer: store step): device time of the routed
store-step program (`ShardedKV`'s jitted `routed_step`, which runs
`store.apply` on every shard) per round, from the profiler trace."""
import sys

MODULE = r"routed_step"


def read(ctx):
    if ctx.trace is None or not any(ctx.trace.ops.values()):
        return None                     # no device plane was traced
    n = ctx.trace.module_count(MODULE)
    if n == 0:
        if ctx.rounds:
            print(f"store_step_dev_ms: rounds ran, but no device program "
                  f"matched {MODULE!r}: the reader needs updating",
                  file=sys.stderr, flush=True)
        return None
    return 1e3 * ctx.trace.module_s(MODULE) / n
