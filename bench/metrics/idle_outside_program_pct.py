"""idle_outside_program_pct (layer: device): the share of the traced
window in which the device idled while the host was in no program span:
the benchmark client's own code.  With the five program layers' idle
shares it sums to `device_idle_pct` (`bench/lib/spans.py`).  Reads
nothing where the program wrote no span to the trace."""
from bench.lib.spans import idle_pct


def read(ctx):
    return idle_pct(ctx, "outside")
