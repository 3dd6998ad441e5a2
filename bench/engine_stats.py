"""Readings of `ServeEngine.stats()` taken at both ends of the window
(`stats0`, `stats1` in a serving run's context).  Each returns None where
the stats lack a counter (an engine that keeps none) or the window decoded
nothing."""
from __future__ import annotations

from typing import Optional

# the host's own work between chunk programs.  `host_wait_s` is left out:
# it spans the whole chunk on the device and, after it, the two readbacks
# of the token block, so it cannot tell device time from readback time,
# and the device idle that falls inside it is not counted here
HOST_PARTS = ("host_coverage_s", "host_dispatch_s", "host_harvest_s")


def delta(ctx, key: str) -> Optional[float]:
    s0, s1 = ctx.get("stats0"), ctx.get("stats1")
    if not s0 or not s1 or key not in s0 or key not in s1:
        return None
    return s1[key] - s0[key]


def host_ms_per_decode_tick(ctx) -> Optional[float]:
    ticks = delta(ctx, "decode_ticks")
    parts = [delta(ctx, k) for k in HOST_PARTS]
    if not ticks or None in parts:
        return None
    return 1e3 * sum(parts) / ticks


def ticks_per_chunk(ctx) -> Optional[float]:
    ticks, chunks = delta(ctx, "decode_ticks"), delta(ctx, "decode_chunks")
    if not ticks or not chunks:
        return None
    return ticks / chunks
