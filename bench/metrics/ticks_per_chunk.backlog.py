"""Decode ticks per chunk dispatch over the window (`decode_ticks /
decode_chunks` of `ServeEngine.stats()` at both ends): below the cap of
8 where a slot near its budget shortens the chunk."""
from bench import engine_stats


def read(ctx):
    return engine_stats.ticks_per_chunk(ctx)
