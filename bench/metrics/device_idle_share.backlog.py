"""1 - device busy (union of op intervals, mean over chips) / traced window."""
from bench import readers


def read(ctx):
    return readers.idle_share(ctx)
