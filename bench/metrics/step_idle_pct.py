"""step_idle_pct (layer: store step): the share of the traced window in
which the device idled under the host part of a round, as the innermost
program span: `f2.sessions.step` and `.fold`, `f2.sharded.apply_round`
and `.fold`, `f2.rebalance.check`, and any other program span outside
the sessions, compaction and durability layers (`bench/lib/spans.py`).
The round's bounds read, compaction passes and WAL work are their own
layers' children and not counted here."""
from bench.lib.spans import idle_pct


def read(ctx):
    return idle_pct(ctx, "step")
