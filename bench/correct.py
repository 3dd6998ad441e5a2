"""The comparisons that decide `correct`, and the numbers they print.

Serving: a sample of the finished requests, drawn from the seed and always
holding the longest, is run through the plain reference once per request
(prompt plus served tokens).  The compared number is the widest gap, over
every served token, between the reference's best logit and its logit of
the served token (`logit_gap`); and the count of requests that did not
get exactly their budget of tokens (`wrong_length`, limit 0).

Training: the first three steps that set-up drives through the window's
own step and feed, against the reference's three AdamW steps on the same
rows and weights:
- `loss_gap`: the largest |program loss - reference loss| over the steps;
- `grad_norm_gap`: over every leaf (one layer's tensor), the gap between
  the program's and the reference's norm of the first gradient as the
  optimizer got it, over the larger of the reference's norm of that leaf
  and of the median leaf;
- `update_norm_gap`: the same for the norm of the weights' change after
  three steps, over the leaves whose reference gradient is at least a
  thousandth of the median leaf's (others move by round-off alone).
"""
from __future__ import annotations

import statistics
import sys
from typing import Dict, List, Sequence, Tuple

import numpy as np

from bench.common import np_rng


# --------------------------------------------------------------------------
# serving
# --------------------------------------------------------------------------
def serve_sample(finished, seed, min_tokens: int, max_requests: int):
    """The longest finished request, then others in an order drawn from
    the seed, until `min_tokens` served tokens or `max_requests`."""
    if not finished:
        return []
    order = sorted(finished, key=lambda f: (-len(f.tokens), f.rid))
    rest = order[1:]
    perm = np_rng(seed, 3).permutation(len(rest))
    pick = [order[0]] + [rest[j] for j in perm]
    out, tok = [], 0
    for f in pick:
        if tok >= min_tokens or len(out) >= max_requests:
            break
        out.append(f)
        tok += len(f.tokens)
    return out


def serve_gaps(ref, c, seed, sample, prompts, pad_to: int, dtype,
               control: str = None) -> List[np.ndarray]:
    """Per request, the gap of each served token under the f32 reference.
    With `control` ('fp8', 'bf16'), the gap of the token that the control
    precision would put first at each of those positions instead."""
    if not sample:
        return []
    w = ref.make_weights(c, seed, dtype)
    fwd = ref.Forward(c, w, "f32")
    ctl = ref.Forward(c, w, control) if control else None
    del w
    out = []
    for f in sample:
        if ctl is None:
            out.append(ref.served_gaps(fwd, prompts[f.rid], f.tokens, pad_to))
        else:
            out.append(ref.served_gaps(ctl, prompts[f.rid], f.tokens, pad_to,
                                       judge=fwd))
    return out


def serve_checks(gaps, wrong_len: int,
                 limits) -> Dict[str, Tuple[float, float]]:
    widest = max((float(np.max(g)) for g in gaps), default=float("inf"))
    return {"logit_gap": (widest, limits["logit_gap"]),
            "wrong_length": (float(wrong_len), 0.0)}


# --------------------------------------------------------------------------
# training
# --------------------------------------------------------------------------
def _rel_gap(prog: Dict[str, float], ref: Dict[str, float],
             keep: Sequence[str]) -> Tuple[float, str]:
    med = statistics.median(ref[k] for k in keep)
    worst, at = 0.0, ""
    for k in keep:
        if k not in prog:
            return float("inf"), k + " (missing)"
        g = abs(prog[k] - ref[k]) / max(ref[k], med)
        if g > worst:
            worst, at = g, k
    return worst, at


def train_numbers(prog, ref) -> Dict[str, float]:
    """prog, ref: {'losses': [..], 'grad_norms': {leaf: n},
    'change_norms': {leaf: n}}."""
    losses = [abs(a - b) for a, b in zip(prog["losses"], ref["losses"])]
    if len(prog["losses"]) != len(ref["losses"]) or not losses:
        loss_gap = float("inf")
    else:
        loss_gap = max(losses)
    if not all(np.isfinite(prog["losses"])):
        loss_gap = float("inf")
    gref = ref["grad_norms"]
    names = sorted(gref)
    gmed = statistics.median(gref.values())
    grad_gap, g_at = _rel_gap(prog["grad_norms"], gref, names)
    moving = [k for k in names if gref[k] >= 1e-3 * gmed]
    upd_gap, u_at = _rel_gap(prog["change_norms"], ref["change_norms"],
                             moving)
    return {"loss_gap": loss_gap, "grad_norm_gap": grad_gap,
            "update_norm_gap": upd_gap, "_grad_at": g_at, "_update_at": u_at,
            "_left_out": len(names) - len(moving)}


def train_checks(nums, limits) -> Dict[str, Tuple[float, float]]:
    return {k: (nums[k], limits[k])
            for k in ("loss_gap", "grad_norm_gap", "update_norm_gap")}


def print_checks(checks, out=sys.stderr) -> None:
    for k, (v, lim) in checks.items():
        print(f"check {k} {v!r} limit {lim!r}", file=out)
