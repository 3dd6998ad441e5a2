"""Program spans with two sinks: the obs recorder and the JAX profiler.

    from repro.obs.spans import span
    with span("serve.decode", host=self.host, cat="serving") as s:
        ...
    self.host_decode_s += s.seconds

Each span
* records the usual `obs` "X" Event when a recorder is installed, so
  `--trace-out`, flight rings and tests see it as before;
* enters a `jax.profiler.TraceAnnotation` of the same name, so inside a
  profiler capture it lies on the host plane, on the device trace's clock,
  where a device gap can be put beside the host work that caused it;
* measures its own duration with `time.perf_counter` (`seconds`), always,
  for callers that keep cumulative host-time counters.

The annotation gets the span's keyword arguments only while a profiler is
collecting: the profiler formats them into the event, and with it off they
would be formatted for nothing.

This module imports jax, so `repro.obs` never imports it: ProcTransport
worker children import `repro.obs` and must stay free of jax.
"""
from __future__ import annotations

import time
from typing import Any, Optional

from jax.profiler import TraceAnnotation

from repro.obs import recorder as obs


class span:
    """Context manager: one program span, recorded in both sinks."""

    __slots__ = ("name", "host", "cat", "args", "seconds", "_t0", "_ann",
                 "_rec")

    def __init__(self, name: str, *, host: Any = None, cat: str = "",
                 **args: Any):
        self.name = name
        self.host = host
        self.cat = cat
        self.args = args
        self.seconds = 0.0
        self._rec: Optional[obs.Span] = None

    def __enter__(self) -> "span":
        rec = obs.get()
        if rec.enabled:
            self._rec = rec.span(self.name, host=self.host, cat=self.cat,
                                 **self.args)
            self._rec.__enter__()
        if self.args and TraceAnnotation.is_enabled():
            self._ann = TraceAnnotation(self.name, **self.args)
        else:
            self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc: Any) -> bool:
        self.seconds = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        if self._rec is not None:
            self._rec.__exit__(*exc)
            self._rec = None
        return False
