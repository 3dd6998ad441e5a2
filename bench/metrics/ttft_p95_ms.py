"""95th percentile over every request due in the window of (first token
harvested - due time); a request with no first token by the window's end
counts its wait so far.
"""
from bench import readers


def read(ctx):
    return readers.p95(ctx, "ttft_ms")
