"""Program spans and engine host-time counters (repro.obs.spans).

* Under the JAX profiler, the serving engine's chunk spans, the prefill,
  the data draw and the launcher's step land on the profile's host plane,
  each child inside its parent, so a device gap can be put beside them.
* The same spans still reach an installed obs recorder, and the
  launchers' `--profile-dir` / `--trace-out` write a profile and a trace
  with backend compiles as `jax.compile` instants.
* `ServeEngine.stats()` counts chunk dispatches exactly, and its host
  seconds are non-negative and fit inside the run's wall time; the serve
  launcher prints them for the operator.
"""
import glob
import json
import time

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.serve import serve
from repro.launch.train import train
from repro.models import model as MD
from repro.obs import Recorder, recording
from repro.serving import Request, ServeEngine
from repro.serving.engine import ServeProgram

HOST_PARTS = ("coverage", "dispatch", "wait", "harvest", "prefill")


@pytest.fixture(scope="module")
def tiny():
    cfg = get_config("qwen3-0.6b", smoke=True).with_(
        param_dtype="float32", compute_dtype="float32")
    return cfg, MD.init_model(cfg, jax.random.PRNGKey(0))


def _requests(cfg, n=5):
    rng = np.random.default_rng(0)
    return [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size,
                                               8 + 3 * i).astype(np.int32),
                    max_new_tokens=3 + 2 * i) for i in range(n)]


def _host_events(profile_dir):
    """(name, start, end) of every event on the profile's host planes."""
    path = sorted(glob.glob(f"{profile_dir}/**/*.xplane.pb",
                            recursive=True))[-1]
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns))
    return out


def _inside(child, parents):
    return any(s <= child[1] and child[2] <= e for _, s, e in parents)


def test_profiler_host_plane_holds_program_spans(tiny, tmp_path):
    cfg, params = tiny
    eng = ServeEngine(params, cfg, num_slots=2, cache_len=48, page_size=4)
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.run(_requests(cfg))
        train(["--arch", "qwen3-0.6b", "--smoke", "--steps", "2",
               "--batch", "2", "--seq", "16", "--log-every", "1"])
    finally:
        jax.profiler.stop_trace()
    evs = _host_events(tmp_path)
    by = {}
    for ev in evs:
        by.setdefault(ev[0], []).append(ev)
    for name in ("serve.decode", "serve.coverage", "serve.dispatch",
                 "serve.wait", "serve.harvest", "serve.prefill",
                 "data.draw", "train.data", "train.step"):
        assert by.get(name), f"{name} missing from the host plane"
    assert len(by["serve.decode"]) == eng.decode_chunks
    assert len(by["serve.prefill"]) == eng.prefill_ticks
    for child in ("serve.coverage", "serve.dispatch", "serve.wait",
                  "serve.harvest"):
        for ev in by[child]:
            assert _inside(ev, by["serve.decode"]), (child, ev)
    for ev in by["data.draw"]:
        assert _inside(ev, by["train.data"]), ev


def test_spans_reach_the_recorder(tiny):
    cfg, params = tiny
    eng = ServeEngine(params, cfg, num_slots=2, cache_len=48, page_size=4)
    with recording(Recorder()) as rec:
        eng.run(_requests(cfg, 3))
    names = [e.name for e in rec.events if e.ph == "X"]
    assert names.count("serve.decode") == eng.decode_chunks
    assert names.count("serve.prefill") == 3
    assert names.count("request") == 3
    decode = [e for e in rec.events if e.name == "serve.decode"]
    assert all(e.args["k"] >= 1 for e in decode)


@pytest.mark.parametrize("paged", [True, False])
def test_stats_counters_add_up(tiny, monkeypatch, paged):
    cfg, params = tiny
    calls = []
    real_chunk = ServeProgram.chunk

    def counting_chunk(self, k):
        fn = real_chunk(self, k)

        def call(*a):
            calls.append(k)
            return fn(*a)
        return call

    monkeypatch.setattr(ServeProgram, "chunk", counting_chunk)
    eng = ServeEngine(params, cfg, num_slots=2, cache_len=48,
                      page_size=4 if paged else None)
    reqs = _requests(cfg)
    t0 = time.perf_counter()
    done = eng.run(reqs)
    wall = time.perf_counter() - t0
    st = eng.stats()
    assert st["decode_chunks"] == len(calls) > 0
    assert st["decode_ticks"] == sum(calls)
    host = [st[f"host_{p}_s"] for p in HOST_PARTS]
    assert all(h >= 0 for h in host)
    assert sum(host) <= wall
    assert (st["host_coverage_s"] > 0) == paged
    assert st["emitted_tokens"] == sum(len(f.tokens) for f in done) == \
        sum(r.max_new_tokens for r in reqs)
    eng.reset()
    assert eng.stats()["decode_chunks"] == 0
    assert eng.stats()["host_wait_s"] == 0.0


def test_serve_launcher_prints_host_counters(capsys):
    out = serve(["--arch", "qwen3-0.6b", "--smoke", "--continuous",
                 "--paged", "--page-size", "4", "--batch", "2",
                 "--requests", "3", "--prompt-len", "8", "--gen", "4"])
    st = out["stats"]
    line = [ln for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("host: ")]
    assert line == [
        f"host: chunks={st['decode_chunks']} "
        f"emitted={st['emitted_tokens']} " + " ".join(
            f"{p}={st[f'host_{p}_s']:.3f}s" for p in HOST_PARTS)]
    assert st["decode_chunks"] > 0
    assert st["emitted_tokens"] == st["generated_tokens"]


def test_launcher_profile_dir_and_compile_instants(tmp_path):
    prof, trace = tmp_path / "prof", tmp_path / "trace.json"
    train(["--arch", "qwen3-0.6b", "--smoke", "--steps", "2", "--batch",
           "2", "--seq", "16", "--profile-dir", str(prof),
           "--trace-out", str(trace)])
    names = {e[0] for e in _host_events(prof)}
    assert {"train.data", "train.step", "data.draw"} <= names
    evs = json.loads(trace.read_text())["traceEvents"]
    steps = [e for e in evs if e["name"] == "train.step"]
    assert [e["args"]["step"] for e in steps] == [0, 1]
    compiles = [e for e in evs if e["name"] == "jax.compile"]
    assert compiles and all(e["args"]["secs"] > 0 for e in compiles)
