"""stable_reads_per_kop (layer: read cache and cold index): random reads
the store charged to its stable tier (`io_stats()["read_ops"]`: reads the
read cache and the in-memory log regions did not absorb) per 1000 ops
acknowledged in the window."""


def read(ctx):
    if ctx.ops <= 0:
        return None
    reads = ctx.after["io"]["read_ops"] - ctx.before["io"]["read_ops"]
    return 1000.0 * reads / ctx.ops
