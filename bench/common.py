"""Shared plumbing of the benchmark: names to files, the chip check, the
compile clock, seeds, spans and the result line.

Everything a cell, a traffic mix, a configuration or a metric needs is a
file of its own, found by name:

    bench/workloads/<cell>.json     which config, mix, driver and sizes
    bench/configs/<config>.json     model sizes, published source, cuts
    bench/traffic/<mix>.json        parameters of `traffic.py`'s generator
    bench/metrics/<metric>.py       read(ctx) -> number or None
    bench/drivers/<driver>.py       set-up, window, check for one kind of job
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import pathlib
import sys
from typing import Any, Callable, Dict, List, Optional

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
SRC = ROOT / "src"


def load_json(kind: str, name: str) -> Dict[str, Any]:
    path = BENCH / kind / f"{name}.json"
    with open(path) as f:
        return json.load(f)


def load_benchmark() -> Dict[str, Any]:
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def load_reader(metric: str) -> Callable[[Dict[str, Any]], Optional[float]]:
    """bench/metrics/<metric>.py's `read`; metric names may hold dots, so
    the file is loaded by path, not imported by module name."""
    path = BENCH / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_driver(name: str):
    return importlib.import_module(f"bench.drivers.{name}")


def load_reference(family: str):
    return importlib.import_module(f"bench.reference.{family}")


def cell_metrics(bench: Dict[str, Any], cell: str, trace: bool) -> List[dict]:
    """The metrics a cell reports: its end-to-end ones untraced, its
    per-layer ones traced.  A metric without `workloads` is reported by
    every cell that reports the end-to-end metric it moves (per-layer) or
    by every cell (end-to-end)."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in names)]


# --------------------------------------------------------------------------
# seeds
# --------------------------------------------------------------------------
def seed_words(seed: int, n: int = 2):
    """Any whole number (beyond 32 bits too) -> n uint32 words, the same
    words for the same seed."""
    import numpy as np
    state = np.random.SeedSequence(int(seed)).generate_state(n)
    return [int(w) for w in state]


def jax_key(seed: int, salt: int = 0):
    import jax
    import numpy as np
    w = seed_words(seed, 2)
    return jax.random.wrap_key_data(
        np.array([w[0] ^ salt, w[1]], np.uint32), impl="threefry2x32")


def np_rng(seed: int, salt: int = 0):
    import numpy as np
    return np.random.default_rng([int(seed) & ((1 << 63) - 1), salt])


# --------------------------------------------------------------------------
# chip, compile cache, compile clock, memory
# --------------------------------------------------------------------------
def require_devices(chips: int, platform: str = "tpu"):
    """Exit non-zero unless JAX sees at least `chips` devices of
    `platform`.  No fallback: a CPU number is not a chip number."""
    import jax
    devs = jax.devices()
    if devs[0].platform != platform or len(devs) < chips:
        print(f"bench: need {chips} {platform} device(s), JAX found "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        raise SystemExit(3)
    return devs[:chips]


def use_compile_cache() -> None:
    """The program's own cache choice (JAX_COMPILATION_CACHE_DIR, else
    `<checkout>/.jax_cache`), with every program cached, however fast it
    compiled, so small decode chunks come from the cache too."""
    import jax
    sys.path.insert(0, str(SRC))
    from repro.launch import cli
    cli.use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling, and the number
    of backend compiles, read from jax.monitoring (as in chip_smoke.py)."""

    def __init__(self):
        import jax
        self.secs = 0.0
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **_):
        if name.startswith("/jax/core/compile/"):
            self.secs += secs
            self.compiles += name.endswith("backend_compile_duration")


def peak_bytes(devices) -> Optional[int]:
    vals = [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devices]
    vals = [v for v in vals if v is not None]
    return max(vals) if vals else None


def device_info(devices) -> Dict[str, Any]:
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices)}


def quantile(values, q: float) -> Optional[float]:
    """Nearest-rank quantile (q in (0,1]) over all values."""
    import math
    v = sorted(values)
    if not v:
        return None
    return v[max(0, math.ceil(q * len(v)) - 1)]


class Tracer:
    """The profiler around the window, when `--trace 1`: writes under
    TMPDIR, reduces the trace, deletes it."""

    def __init__(self, enabled: bool):
        self.enabled = enabled
        self.dir = None

    def start(self) -> None:
        if not self.enabled:
            return
        import tempfile
        import jax
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # no per-call Python events
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._ann = jax.profiler.TraceAnnotation("bench.window")
        self._ann.__enter__()

    def stop(self, spans, perf_window):
        if not self.enabled:
            return None
        import shutil
        import jax
        from bench import trace_reduce
        self._ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        try:
            tr = trace_reduce.read(trace_reduce.find_xplane(self.dir))
            return trace_reduce.summarize(tr, spans, perf_window)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
