"""Collective time not overlapped by compute, over the traced window, mean
over the chips: per chip, the union of its all-gather, reduce-scatter,
all-reduce and other collective ops less every interval in which another
op ran on it (ops that hold no other: a loop's event spans its body)."""
from bench import readers


def read(ctx):
    return readers.collective_exposed_share(ctx)
