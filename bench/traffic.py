"""The one traffic generator: every mix under bench/traffic/ is a file of
parameters for it, so a new mix needs no new code.

Kinds of mix:

- ``open_loop``: requests arrive at `rate_per_s` (Poisson gaps) from t=0
  on; prompt and output lengths are drawn from clipped lognormals.
- ``backlog``: `count` requests, all due at t=0.
- ``lm``: a training stream of `seq`-token rows from the program's seeded
  synthetic source (the driver draws the rows; this file only names them).

A seed fixes only the ORDER of the sizes and gaps: every seed takes the
same set of prompt lengths, output lengths and inter-arrival gaps (the
distributions' quantiles at evenly spaced points), shuffled, and its own
token ids.  So seeds change which request meets which, not how much work an
open-loop window holds (a backlog window reaches only the head of its queue,
whose sizes do vary with the seed).

Prompt lengths round UP to `grid` when one is given.
"""
from __future__ import annotations

import bisect
import dataclasses
import math
from statistics import NormalDist
from typing import Any, Dict, List

import numpy as np

from bench.common import np_rng

_N = NormalDist()


@dataclasses.dataclass
class Arrival:
    rid: int
    t: float            # seconds after the window opens
    prompt: np.ndarray  # int32 token ids
    max_new: int


def _quantile_points(n):
    return (np.arange(n) + 0.5) / n


def _lognormal(spec, n, rng):
    z = np.array([_N.inv_cdf(float(x)) for x in _quantile_points(n)])
    rng.shuffle(z)
    x = spec["median"] * np.exp(spec["sigma"] * z)
    x = np.clip(np.ceil(x), spec["min"], spec["max"]).astype(int)
    grid = spec.get("grid")
    if grid:
        g = sorted(grid)
        x = np.array([g[bisect.bisect_left(g, v)] for v in x])
    return x


def _gaps(rate, n, rng):
    """Exponential (Poisson) inter-arrival gaps."""
    g = -np.log1p(-_quantile_points(n)) / rate
    rng.shuffle(g)
    return g


def count_for(mix: Dict[str, Any], seconds: float) -> int:
    if mix["kind"] == "open_loop":
        return int(math.ceil(mix["rate_per_s"] * seconds)) + 1
    if mix["kind"] == "backlog":
        return int(mix["count"])
    raise ValueError(f"mix kind {mix['kind']} has no requests")


def requests(mix: Dict[str, Any], seed: int, seconds: float,
             vocab: int) -> List[Arrival]:
    """The arrivals of one run, sorted by time."""
    kind = mix["kind"]
    n = count_for(mix, seconds)
    rng = np_rng(seed, 1)
    plen = _lognormal(mix["prompt"], n, rng)
    olen = _lognormal(mix["output"], n, rng)
    if kind == "open_loop":
        t = np.cumsum(_gaps(mix["rate_per_s"], n, rng))
    else:
        t = np.zeros(n)
    tok_rng = np_rng(seed, 2)
    out = []
    for i in range(n):
        p = tok_rng.integers(0, vocab, int(plen[i]), dtype=np.int32)
        out.append(Arrival(i, float(t[i]), p, int(olen[i])))
    return out
