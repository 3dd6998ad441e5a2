"""95th percentile of (serve.admit instant - due time) over requests due in the
window; one not admitted by the window's end counts its wait so far.
"""
from bench import readers


def read(ctx):
    return readers.p95(ctx, "queue_wait_ms")
