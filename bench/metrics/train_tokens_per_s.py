"""Tokens of the training steps that ended in the window over the window's
seconds.
"""
from bench import readers


def read(ctx):
    return readers.per_s(ctx, "train_tokens")
