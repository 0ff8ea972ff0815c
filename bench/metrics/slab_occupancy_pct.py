"""slab_occupancy_pct (layer: sessions): the share of the packed rounds'
slab lanes that carried an op in the window, from the session service's
packing counters (`stats()["sessions"]`, the same numbers
`svc.slab_occupancy()` folds)."""


def read(ctx):
    rounds = ctx.after["pack_rounds"] - ctx.before["pack_rounds"]
    if rounds <= 0:
        return None
    lanes = ctx.after["packed_lanes"] - ctx.before["packed_lanes"]
    return 100.0 * lanes / (rounds * ctx.after["slab_lanes"])
