"""wal_sync_ms (layer: durability): mean host time of one WAL group
commit (flush and fsync) that started in the traced window, from the
program's `f2.wal.sync` span: the in-program counterpart of
`wal_fsync_ms`."""
from bench.lib.spans import mean_span_ms


def read(ctx):
    return mean_span_ms(ctx, {"wal.sync"})
