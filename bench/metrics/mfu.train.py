"""Model FLOPs (6 per parameter per token plus causal attention, no
recomputation) of the window's steps over the window and the chips' bf16
peak, percent.
"""
from bench import readers


def read(ctx):
    return readers.mfu(ctx) if "train_tokens" in ctx else None
