"""bench/trace_reduce.py: interval arithmetic on hand-made events, and the
whole reduction on a small trace recorded on a TPU v5e chip
(bench/tests/data/tpu_v5e_small.xplane.pb: a bf16 matmul program and a sum
program, three times each, inside a `bench.window` annotation)."""
import os

import pytest

from bench import trace_reduce as T

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "tpu_v5e_small.xplane.pb")


def test_union_subtract_gaps():
    iv = [(0, 2), (1, 3), (5, 6), (6, 7), (9, 10)]
    assert T.union(iv) == [(0, 3), (5, 7), (9, 10)]
    assert T.length(T.union(iv)) == 6
    assert T.subtract([(0, 10)], iv) == [(3, 5), (7, 9)]
    assert T.gaps(iv, -1, 11) == [(-1, 0), (3, 5), (7, 9), (10, 11)]
    assert T.clip([(0, 4), (8, 12)], 2, 10) == [(2, 4), (8, 10)]


def _trace():
    ops = {"/device:TPU:0": [
        ("fusion.1", 100, 200), ("all-gather.3", 200, 260),
        ("fusion.1", 300, 400), ("all-reduce-done", 380, 450),
        ("convolution.2", 500, 600)]}
    mods = {"/device:TPU:0": [("jit_step(1)", 100, 460),
                              ("jit_chunk(2)", 500, 600)]}
    host = [("bench.window", 50, 650)]
    return T.Trace(ops, mods, host)


def test_summarize_hand_made():
    s = T.summarize(_trace(), [("data", 0.0, 0.1), ("step", 0.3, 0.55)],
                    perf_window=(0.0, 0.6), top=3)
    # clock offset: window at 50 ns on the trace, 0 s on the host clock
    assert s["window_s"] == pytest.approx(600e-9)
    assert s["busy_s"] == pytest.approx((160 + 150 + 100) * 1e-9)
    assert s["collective_s"] == pytest.approx((60 + 70) * 1e-9)
    # all-reduce-done overlaps fusion.1 over 380-400
    assert s["collective_exposed_s"] == pytest.approx((60 + 50) * 1e-9)
    assert s["breakdown"]["device_ops"][0] == ["fusion.1", pytest.approx(2e-7)]
    gaps = s["breakdown"]["idle_gaps"]
    assert [g[1] for g in gaps] == pytest.approx([50e-9, 50e-9, 50e-9])
    assert T.module_seconds(s, "jit_chunk") == pytest.approx(100e-9)
    assert T.module_seconds(s, "jit_nothing") is None


def test_labels_come_from_the_host_span_that_overlaps_most():
    spans = [("a", 0, 10), ("b", 8, 30)]
    assert T.label((9, 20), spans) == "b"
    assert T.label((40, 50), spans) == "none"


def test_recorded_tpu_trace():
    tr = T.read(DATA)
    assert list(tr.ops) == ["/device:TPU:0"]
    lo, hi = T.window(tr)
    s = T.summarize(tr)
    assert 0 < s["busy_s"] < s["window_s"] == pytest.approx((hi - lo) / 1e9)
    names = [n for n, _ in s["breakdown"]["device_ops"]]
    assert names and all(isinstance(n, str) for n in names)
    assert s["collective_s"] == 0
    assert sum(v for v in s["module_s"].values()) <= s["window_s"]
