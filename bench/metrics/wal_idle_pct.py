"""wal_idle_pct (layer: durability): the share of the traced window in
which the device idled under `f2.wal.*` (the WAL's copy-out, encode and
append in `f2.wal.log_slab`, the group commit's flush and fsync in
`f2.wal.sync`) or another `f2.durability.*` span
(`bench/lib/spans.py`)."""
from bench.lib.spans import idle_pct


def read(ctx):
    return idle_pct(ctx, "wal")
