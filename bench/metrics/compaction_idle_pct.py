"""compaction_idle_pct (layer: compaction scheduler): the share of the
traced window in which the device idled under `f2.compact.*`: the
scheduler's per-round bounds read (`f2.compact.bounds`) and its passes
(`bench/lib/spans.py`)."""
from bench.lib.spans import idle_pct


def read(ctx):
    return idle_pct(ctx, "compaction")
