"""Model FLOPs of the window's prefills and decode ticks over the window and
the bf16 peak, percent.
"""
from bench import readers


def read(ctx):
    return readers.mfu(ctx) if "output_tokens" in ctx else None
