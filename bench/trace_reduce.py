"""Profiler trace (`.xplane.pb`) -> device busy and idle time, op times by
name, collective time left exposed, and idle gaps labelled with what the
harness was doing on the host.

Device planes are `/device:<KIND>:<n>`; their `XLA Ops` line holds one
event per operation run, their `XLA Modules` line one per program run.
The harness marks its measured window with a TraceAnnotation named
`bench.window`, which the host plane holds on the same clock.  Times are
nanoseconds on the trace's clock; the summary gives seconds.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW = "bench.window"
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all|"
    r"allgather|allreduce|reducescatter|send|recv", re.I)

Interval = Tuple[float, float]


@dataclasses.dataclass
class Trace:
    ops: Dict[str, List[Tuple[str, float, float]]]      # device -> events
    modules: Dict[str, List[Tuple[str, float, float]]]  # device -> programs
    host: List[Tuple[str, float, float]]                # bench.* annotations


def op_name(text: str) -> str:
    """'%fusion.3 = bf16[..] fusion(...)' -> 'fusion.3'."""
    return text.split(" = ", 1)[0].lstrip("%")


def find_xplane(root: str) -> str:
    hits = sorted(glob.glob(os.path.join(root, "**", "*.xplane.pb"),
                            recursive=True))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {root}")
    return hits[-1]


def _is_device(name: str) -> bool:
    return name.startswith("/device:") and "CPU" not in name.upper()[:12]


def read(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops: Dict[str, list] = {}
    modules: Dict[str, list] = {}
    host: list = []
    for plane in pd.planes:
        if _is_device(plane.name):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dst = ops.setdefault(plane.name, [])
                elif line.name == "XLA Modules":
                    dst = modules.setdefault(plane.name, [])
                else:
                    continue
                for e in line.events:
                    s = float(e.start_ns)
                    dst.append((op_name(e.name), s,
                                s + float(e.duration_ns)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        s = float(e.start_ns)
                        host.append((e.name, s, s + float(e.duration_ns)))
    return Trace(ops, modules, sorted(host, key=lambda x: x[1]))


def window(trace: Trace) -> Interval:
    w = [(s, e) for n, s, e in trace.host if n == WINDOW]
    if not w:
        raise ValueError("trace holds no bench.window annotation")
    return w[-1]


def clip(iv: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi
            and min(e, hi) > max(s, lo)]


def union(iv: Sequence[Interval]) -> List[Interval]:
    out: List[list] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(iv: Sequence[Interval]) -> float:
    return sum(e - s for s, e in iv)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Parts of union(a) not covered by union(b)."""
    a, b = union(a), union(b)
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return subtract([(lo, hi)], busy)


def op_times(events, lo: float, hi: float) -> Dict[str, float]:
    t: Dict[str, float] = collections.Counter()
    for n, s, e in events:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            t[n] += e - s
    return dict(t)


def label(iv: Interval, spans: Sequence[Tuple[str, float, float]]) -> str:
    """The host span that overlaps the interval most; 'none' if none."""
    best, name = 0.0, "none"
    for n, s, e in spans:
        o = min(e, iv[1]) - max(s, iv[0])
        if o > best:
            best, name = o, n
    return name


def summarize(trace: Trace, spans: Sequence[Tuple[str, float, float]] = (),
              perf_window: Optional[Interval] = None, top: int = 10) -> dict:
    """Everything the per-layer readers need, in seconds.

    `spans` are the harness's own (name, t0, t1) on its host clock (seconds)
    and `perf_window` the window on that clock: the offset between the two
    clocks comes from the window annotation."""
    lo, hi = window(trace)
    win = (hi - lo) / 1e9
    off = None
    if perf_window is not None:
        off = lo - perf_window[0] * 1e9
    host = [(n, s * 1e9 + off, e * 1e9 + off) for n, s, e in spans] \
        if off is not None else []
    per_dev = {}
    for dev, evs in sorted(trace.ops.items()):
        iv = clip([(s, e) for _, s, e in evs], lo, hi)
        busy = union(iv)
        coll = [(s, e) for n, s, e in evs if COLLECTIVE.search(n)]
        comp = [(s, e) for n, s, e in evs if not COLLECTIVE.search(n)]
        exposed = subtract(clip(coll, lo, hi), clip(comp, lo, hi))
        per_dev[dev] = {"busy_s": length(busy) / 1e9,
                        "collective_s":
                            length(union(clip(coll, lo, hi))) / 1e9,
                        "collective_exposed_s": length(exposed) / 1e9,
                        "gaps": gaps(busy, lo, hi)}
    if not per_dev:
        raise ValueError("trace holds no device operations")
    first = sorted(per_dev)[0]
    ops0 = op_times(trace.ops[first], lo, hi)
    top_ops = sorted(ops0.items(), key=lambda kv: -kv[1])[:top]
    g0 = sorted(per_dev[first]["gaps"], key=lambda g: g[0] - g[1])[:top]
    idle = [[label(g, host), (g[1] - g[0]) / 1e9] for g in g0]
    mods = collections.Counter()
    for n, s, e in trace.modules.get(first, []):
        s2, e2 = max(s, lo), min(e, hi)
        if e2 > s2:
            mods[n] += (e2 - s2) / 1e9
    return {
        "window_s": win,
        "busy_s": sum(d["busy_s"] for d in per_dev.values()) / len(per_dev),
        "collective_s": per_dev[first]["collective_s"],
        "collective_exposed_s": per_dev[first]["collective_exposed_s"],
        "module_s": dict(mods),
        "breakdown": {"device_ops": [[n, t / 1e9] for n, t in top_ops],
                      "idle_gaps": idle},
    }


def module_seconds(summary: dict, prefix: str) -> Optional[float]:
    """Device seconds of the programs whose name starts with `prefix`
    (e.g. 'jit_chunk'), or None when the trace holds none."""
    t = [v for k, v in summary["module_s"].items() if k.startswith(prefix)]
    return sum(t) if t else None
