"""Arithmetic shared by the per-metric readers in bench/metrics/.

Each reader takes the run's context (what the driver measured) and returns
a number, or None when the run holds nothing to read (a serving reader in
a training run, a trace reader in an untraced run)."""
from __future__ import annotations

from typing import Optional

from bench.common import quantile


def p95(ctx, key) -> Optional[float]:
    v = ctx.get(key)
    return quantile(v, 0.95) if v else None


def per_s(ctx, key) -> Optional[float]:
    v = ctx.get(key)
    return None if v is None else v / ctx["window_s"]


def mfu(ctx) -> Optional[float]:
    """Model FLOPs of the window's work over the window and the chips'
    bf16 peak, in percent."""
    f = ctx.get("window_flops")
    if not f:
        return None
    return 100.0 * f / ctx["window_s"] / (
        ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"])


def idle_share(ctx) -> Optional[float]:
    s = ctx.get("trace")
    if not s:
        return None
    return 1.0 - s["busy_s"] / s["window_s"]
