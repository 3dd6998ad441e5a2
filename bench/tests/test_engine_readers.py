"""The readers of the serving engine's counters.

They take `ServeEngine.stats()` at both ends of the window; an
engine without the counters (an older program) gives them nothing to read,
and they return None rather than raise."""
import jax
import pytest

from bench import common, run
from bench.tests import tiny

SEED = 2**31 + 23

STATS0 = {"decode_ticks": 100, "decode_chunks": 20, "occupancy": 1.0,
          "host_coverage_s": 0.1, "host_dispatch_s": 0.2,
          "host_wait_s": 5.0, "host_harvest_s": 0.3}
STATS1 = {"decode_ticks": 900, "decode_chunks": 140, "occupancy": 1.0,
          "host_coverage_s": 0.9, "host_dispatch_s": 1.0,
          "host_wait_s": 50.0, "host_harvest_s": 1.1}
# what an engine without the counters reports
PARENT = {"decode_ticks": 100, "occupancy": 1.0, "prefill_ticks": 3}

COUNTER_READERS = ("host_ms_per_decode_tick.steady",
                   "host_ms_per_decode_tick.backlog",
                   "ticks_per_chunk.steady", "ticks_per_chunk.backlog")


@pytest.mark.parametrize("cell", ["steady", "backlog"])
def test_counter_readers_read_the_window(cell):
    ctx = {"stats0": STATS0, "stats1": STATS1}
    host = common.load_reader(f"host_ms_per_decode_tick.{cell}")(ctx)
    # (0.8 + 0.8 + 0.8) s over 800 ticks; the wait is left out
    assert host == pytest.approx(3.0)
    tpc = common.load_reader(f"ticks_per_chunk.{cell}")(ctx)
    assert tpc == pytest.approx(800 / 120)


@pytest.mark.parametrize("name", COUNTER_READERS)
@pytest.mark.parametrize("ctx", [
    {"stats0": dict(PARENT), "stats1": dict(PARENT, decode_ticks=900)},
    {"stats0": STATS0, "stats1": STATS0},   # nothing decoded
    {"train_tokens": 10},                   # not a serving run
])
def test_counter_readers_without_counters_read_nothing(name, ctx):
    assert common.load_reader(name)(ctx) is None


def test_counter_readers_on_a_real_engine():
    """The serve driver's own context, from a tiny paged engine on the CPU:
    the readers find the counters and read plausible values."""
    names = ("host_ms_per_decode_tick.steady", "ticks_per_chunk.steady")
    bench = {"end_to_end": [{"name": n, "unit": "x"} for n in names],
             "per_layer": []}
    out = run.execute(tiny.SERVE, tiny.CONFIG, tiny.SERVE_MIX, SEED, 2.0, 0,
                      platform="cpu", devices=jax.devices("cpu")[:1],
                      bench=bench, cell="tiny-serve")
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(m) == set(names), out
    assert m["host_ms_per_decode_tick.steady"] > 0
    assert 1 <= m["ticks_per_chunk.steady"] <= 8


def test_serving_cells_list_the_counter_metrics():
    bench = common.load_benchmark()
    for cell, tag in (("serve-steady", "steady"),
                      ("serve-backlog", "backlog")):
        traced = {m["name"] for m in common.cell_metrics(bench, cell, True)}
        assert {f"host_ms_per_decode_tick.{tag}",
                f"ticks_per_chunk.{tag}"} <= traced
        untraced = {m["name"] for m in common.cell_metrics(bench, cell,
                                                           False)}
        assert not any(n.startswith(("host_ms", "ticks_per")) for n in
                       untraced)
    train = {m["name"] for m in common.cell_metrics(bench, "train-1chip",
                                                    True)}
    assert not any(n.startswith(("host_ms", "ticks_per")) for n in train)
    for m in bench["per_layer"]:
        common.load_reader(m["name"])  # every metric has its reader
