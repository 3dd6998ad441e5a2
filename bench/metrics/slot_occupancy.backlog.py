"""Share of slots active per decode tick, over the window's ticks only:
from engine.stats() at both ends (occupancy x decode_ticks)."""


def read(ctx):
    s0, s1 = ctx.get("stats0"), ctx.get("stats1")
    if not s0 or not s1:
        return None
    d = s1["decode_ticks"] - s0["decode_ticks"]
    if d <= 0:
        return None
    occ = (s1["occupancy"] * s1["decode_ticks"]
           - s0["occupancy"] * s0["decode_ticks"])
    return occ / d
