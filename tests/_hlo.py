"""Tally of the values in compiled HLO text, by op and shape."""
import re

_VALUE = re.compile(r"= \w+\[([\d,]*)\](?:\{[^}]*\})? ([\w-]+)\(")


def values(text):
    """(op, dims) of every array value in compiled HLO text, fused bodies
    included; leading size-1 dims dropped (a sliced layer is [1,Np,...])."""
    out = []
    for m in _VALUE.finditer(text):
        dims = tuple(int(d) for d in m.group(1).split(",") if d)
        while dims[:1] == (1,):
            dims = dims[1:]
        out.append((m.group(2), dims))
    return out


def pool_traffic(text, pool):
    """For a stacked KV pool of shape `pool` (L, Np, P, Hk, dh): the copies,
    slices, restacks and broadcasts that make a value shaped like the pool
    or like one layer of it, and the number of scatters that write it."""
    shapes = {tuple(pool), tuple(pool[1:])}
    vals = values(text)
    moves = [(op, d) for op, d in vals if d in shapes and op in
             ("copy", "dynamic-slice", "dynamic-update-slice", "broadcast")]
    writes = sum(op == "scatter" and d == tuple(pool) for op, d in vals)
    return moves, writes
