"""enqueue_idle_pct (layer: sessions): the share of the traced window in
which the device idled under `f2.sessions.enqueue`, the session
service's enqueue (`bench/lib/spans.py`)."""
from bench.lib.spans import idle_pct


def read(ctx):
    return idle_pct(ctx, "enqueue")
