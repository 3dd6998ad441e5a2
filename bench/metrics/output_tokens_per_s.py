"""Output tokens harvested in the window over the window's seconds."""
from bench import readers


def read(ctx):
    return readers.per_s(ctx, "output_tokens")
