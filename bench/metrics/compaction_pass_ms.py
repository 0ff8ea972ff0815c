"""compaction_pass_ms (layer: compaction scheduler): mean host time of
one compaction pass that started in the traced window, from its program
span (`f2.compact.hot_cold`, `.cold_cold`, `.single_log` or
`.chunk_gc`, counted where no other of them encloses it).  Reads
nothing where no pass ran in the window."""
from bench.lib.spans import PASSES, mean_span_ms


def read(ctx):
    return mean_span_ms(ctx, PASSES)
