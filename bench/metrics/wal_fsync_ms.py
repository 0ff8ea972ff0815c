"""wal_fsync_ms (layer: durability): mean host time of one WAL fsync in
the window: the group commit that each round with a write pays before its
acks (`DurableKV._commit`).  Timed around every `os.fsync` of the WAL's
files by the benchmark's own observer (`FsyncWatch`), so the program runs
as in the untraced runs."""


def read(ctx):
    if not ctx.fsync_s:
        return None
    return 1e3 * sum(ctx.fsync_s) / len(ctx.fsync_s)
