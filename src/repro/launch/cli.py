"""Shared launcher plumbing for the ``repro.launch`` entry points.

Every launcher (train, serve, rl) grows the same cluster surface — which
transport backs the control plane (``--transport``), where injected
failures come from (``--failure-trace``), where dying workers flush
their flight rings (``--flight-dir``) — plus the same "record the run
and write a Perfetto trace" wrapper (``--trace-out``) and JAX profiler
capture (``--profile-dir``).  They live here
once, as argparse argument groups and small factories, so a flag's
spelling, default, and semantics cannot drift between entry points:

* `add_cluster_args(ap, ...)`  — the cluster flag group
* `add_trace_args(ap)`         — the observability flag group
* `load_failure_trace(args)`   — ``--failure-trace`` JSON -> FailureTrace
* `make_transport(args, trace)`— flags -> SimTransport / ProcTransport
* `run_traced(args, fn)`       — run under a Recorder and/or the JAX
  profiler, write trace.json / the profile
* `use_compile_cache()`        — JAX's persistent compilation cache

All repro imports are lazy: parsing ``--help`` must not pay the jax
startup tax.
"""
from __future__ import annotations

import argparse
import os
import pathlib
from typing import Any, Callable, Optional

# fixed, never per run, so a later run finds what an earlier one compiled
CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> None:
    """Turn on JAX's persistent compilation cache for this process.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its
    cache there and nothing is set here; otherwise the cache is the
    checkout's ``.jax_cache``.  Entry points call this first; importing a
    module never does."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))


def add_cluster_args(ap: argparse.ArgumentParser, *,
                     context: str = "the fleet",
                     workers: Optional[int] = None,
                     workers_help: Optional[str] = None):
    """Add the shared cluster control-plane flags.

    ``context`` names the launcher's fleet in help text (e.g.
    ``"--elastic"``, ``"--replicas"``).  ``--workers`` is added only
    when a default is given — serve sizes its fleet with ``--replicas``
    and rl with ``--actors``/``--replay-shards`` instead.
    """
    g = ap.add_argument_group(
        "cluster", "control plane shared by every launcher "
        "(repro.cluster; see repro.launch.cli)")
    g.add_argument("--transport", default="sim", choices=["sim", "proc"],
                   help=f"{context} control plane: 'sim' replays the "
                        "failure trace on the simulated clock; 'proc' "
                        "runs real worker processes with per-host "
                        "heartbeat RPC and injects the trace against "
                        "them (repro.cluster.ProcTransport)")
    g.add_argument("--failure-trace", default=None,
                   help="JSON trace of fail/hang/recover/join/slow "
                        "events to inject "
                        "(repro.elastic.membership.FailureTrace)")
    g.add_argument("--flight-dir", default=None,
                   help="--transport=proc: directory where dying/"
                        "stopped workers flush their flight-recorder "
                        "ring (flight_host<id>.json)")
    if workers is not None:
        g.add_argument("--workers", type=int, default=workers,
                       help=workers_help
                       or f"logical workers in {context}")
    return g


def add_trace_args(ap: argparse.ArgumentParser):
    """Add the shared observability flags."""
    g = ap.add_argument_group("observability (repro.obs)")
    g.add_argument("--trace-out", default=None,
                   help="record the run and write a Chrome/Perfetto "
                        "trace.json here (open in ui.perfetto.dev); "
                        "see repro.obs")
    g.add_argument("--profile-dir", default=None,
                   help="capture a JAX profiler trace of the run here "
                        "(TensorBoard / xprof): the program's spans "
                        "(repro.obs.spans) and the device's ops on one "
                        "clock")
    return g


def load_failure_trace(args, default=None):
    """``--failure-trace`` JSON -> FailureTrace (``default`` if the flag
    was absent or the launcher never added the group)."""
    path = getattr(args, "failure_trace", None)
    if not path:
        return default
    from repro.elastic.membership import FailureTrace
    return FailureTrace.load(path)


def make_transport(args, trace=None):
    """Transport from the shared cluster flags: sim replays ``trace`` on
    the simulated clock, proc injects it against real worker processes
    (flight rings land in ``--flight-dir``)."""
    if getattr(args, "transport", "sim") == "proc":
        from repro.cluster.proc import ProcTransport
        return ProcTransport(inject=trace,
                             flight_dir=getattr(args, "flight_dir", None))
    from repro.cluster.sim import SimTransport
    from repro.elastic.membership import FailureTrace
    return SimTransport(trace or FailureTrace())


def run_traced(args, fn: Callable[[], Any]) -> Any:
    """Run ``fn()``; with ``--profile-dir`` under the JAX profiler, and
    with ``--trace-out`` under a Recorder that also notes every backend
    compile as a ``jax.compile`` instant with its duration, writing the
    Chrome/Perfetto trace on the way out (even on error — a trace of a
    failed run is the one you want most)."""
    profile_dir = getattr(args, "profile_dir", None)
    if profile_dir:
        import jax
        jax.profiler.start_trace(profile_dir)
        try:
            return _run_recorded(args, fn)
        finally:
            jax.profiler.stop_trace()
            print(f"wrote profile: {profile_dir}", flush=True)
    return _run_recorded(args, fn)


def _run_recorded(args, fn: Callable[[], Any]) -> Any:
    if not getattr(args, "trace_out", None):
        return fn()
    import jax
    from repro.obs import recorder as obs
    from repro.obs.trace import write_trace

    def on_duration(name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            rec.event("jax.compile", cat="jax", secs=secs)

    with obs.recording(obs.Recorder()) as rec:
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        try:
            return fn()
        finally:
            jax.monitoring.unregister_event_duration_listener(on_duration)
            write_trace(args.trace_out, rec.events)
            print(f"wrote trace: {args.trace_out} "
                  f"({len(rec.events)} events)", flush=True)
