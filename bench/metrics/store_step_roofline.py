"""store_step_roofline (layer: store step): the routed store step's share
of its roofline.  The least bytes are those every op executed in the
window's rounds must move (`bench/lib/roofline.py`), whichever engine
(Pallas kernel or `fused_ref`) runs each probe and write site; the time is
the device time of the routed-step program (`ShardedKV`'s jitted
`routed_step`) in the traced window.  A step that moves more than the
least, or waits, only lowers the share."""
import sys

from bench.lib.roofline import least_bytes, roofline_share_pct

MODULE = r"routed_step"


def read(ctx):
    if ctx.trace is None or not any(ctx.trace.ops.values()):
        return None                     # no device plane was traced
    need = least_bytes(ctx.ops_executed,
                       int(ctx.config["store"]["value_width"]))
    if need == 0:
        return None
    secs = ctx.trace.module_s(MODULE)
    if secs <= 0:
        print(f"store_step_roofline: rounds ran, but no device program "
              f"matched {MODULE!r}: the reader needs updating",
              file=sys.stderr, flush=True)
        return None
    return roofline_share_pct(need, secs, ctx.peaks["hbm_bytes_per_s"])
