"""repro.obs — the observability spine: structured tracing, a metrics
registry, and a fleet flight recorder, shared by every layer.

Why: the control plane reacts to stragglers, failures, and stalls, but
its telemetry was fragmented — ThroughputMonitor EMAs here, membership
transition logs there, bench JSON blobs, scattered prints. None of it
could be correlated on one timeline. This package is the instrument the
ROADMAP's tuning items (straggler backups, SLO admission, autoscaling)
read from.

Event model
-----------
Three phases, mirroring Chrome trace semantics (`recorder.Event`):

* span ("X")   — an interval with a duration: a training round, a
  recovery, a heartbeat RPC, a checkpoint fsync. Produced by
  `Recorder.span(name)` (context manager) or `Recorder.complete(...)`
  (retroactive).
* instant ("i") — a point event: a membership transition, a request
  admission, a commit report.
* counter ("C") — a sampled registry value on the timeline.

Alongside the timeline sits a flat metrics **registry** (dotted
name -> value) fed by `count()`/`gauge()`;
`repro.obs.registry.bench_report` rewrites benchmark JSON as a view over
it.

Clock sources
-------------
`Recorder.clock` is pluggable:

* real runs use `time.monotonic` (the default);
* `run_elastic` re-points it at the driver's simulated wall clock
  (`ModeContext.sim_time`), so trace-replayed runs emit bit-identical
  timelines — `tests/test_obs.py` pins byte-equal `trace.json` across
  runs;
* ProcTransport worker children stamp their flight rings relative to
  worker start (their own monotonic clock); merged onto the driver
  timeline they are offset by the driver-observed spawn time, i.e.
  per-host lanes are exact in *order* and host-local spacing, not in
  cross-host alignment.

Export surfaces
---------------
* `repro.obs.trace.write_trace(path, rec.events)` — Chrome/Perfetto
  `trace.json`, one thread lane per host (driver tid 0, worker w
  tid w+1, PS shard s tid 1000+s). Load at https://ui.perfetto.dev.
* `repro.obs.flight.FlightRecorder` — bounded ring every worker keeps
  and flushes to `flight_host<id>.json` on die/stop/SIGTERM, so a
  post-mortem of a killed host shows its last N events. Survivor rings
  are pulled over the ack channel (`ProcTransport.host_events`).
* `repro.obs.registry.bench_report` — bench JSON from the registry.
* `repro.obs.log` — the stdlib logger (`repro.*`) library code uses
  instead of print; WARNING-quiet by default, launchers `configure()`.
* `repro.obs.spans.span` — the span that program code on the chip's
  host opens: it records here and also enters a
  `jax.profiler.TraceAnnotation`, so a profiler capture (`--profile-dir`
  on the launchers) holds it on the device trace's clock. It imports
  jax, so this package does not import it.

The default recorder is a `NullRecorder`: every producer call is a
no-op returning shared objects, so un-instrumented hot paths allocate
nothing (pinned by the counting-shim test). Enable with
`obs.install(obs.Recorder())` or `with obs.recording(...)`, or via
`--trace-out=PATH` on the launchers. Everything in this package is
stdlib-only: worker subprocesses import it and must never load jax.
"""
from repro.obs.recorder import (Event, NullRecorder, Recorder, Span, get,
                                install, recording)
from repro.obs.registry import bench_report, emit_metrics, registry_view
from repro.obs.trace import chrome_trace, trace_json, write_trace
from repro.obs.flight import FlightRecorder, load_flight
from repro.obs import log

__all__ = [
    "Event", "NullRecorder", "Recorder", "Span",
    "get", "install", "recording",
    "bench_report", "emit_metrics", "registry_view",
    "chrome_trace", "trace_json", "write_trace",
    "FlightRecorder", "load_flight", "log",
]
