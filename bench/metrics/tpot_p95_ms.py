"""95th percentile over requests finished in the window of (last token - first
token) / (tokens - 1).
"""
from bench import readers


def read(ctx):
    return readers.p95(ctx, "tpot_ms")
