"""compaction_dev_ms_per_s (layer: compaction scheduler): device time of
the masked compaction and GC programs the scheduler runs
(`ShardedKV.maybe_compact`: hot->cold and cold->cold steps, the two
truncations, chunk GC), per second of traced window.  A window in which
no pass ran reads 0: the scheduler took no device time."""

MODULES = r"_masked_(hc_step|cc_step|sl_step|hot_trunc|cold_trunc|chunk_gc)"


def read(ctx):
    if ctx.trace is None or not any(ctx.trace.ops.values()):
        return None
    return 1e3 * ctx.trace.module_s(MODULES) / ctx.trace.window_s()
