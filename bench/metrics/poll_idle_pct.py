"""poll_idle_pct (layer: sessions): the share of the traced window in
which the device idled while the host was in the session service's poll,
as its innermost program span: `f2.sessions.poll` and its children
`f2.sessions.fetch` (the slot-state and result `device_get`s) and
`f2.sessions.collect` (`bench/lib/spans.py`)."""
from bench.lib.spans import idle_pct


def read(ctx):
    return idle_pct(ctx, "poll")
