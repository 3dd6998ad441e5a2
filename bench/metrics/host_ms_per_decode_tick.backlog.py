"""Host milliseconds per decode tick over the window: the engine's
`host_coverage_s + host_dispatch_s + host_harvest_s` (page growth, block
table upload and chunk call, walk over the token block) over its decode
ticks, from `ServeEngine.stats()` at both ends of the window."""
from bench import engine_stats


def read(ctx):
    return engine_stats.host_ms_per_decode_tick(ctx)
