"""Host-side span tracer with Chrome/Perfetto trace JSON export.

Records named wall-clock spans around the training loop's phases (etl,
host→device transfer, dispatch, telemetry flush, eval, checkpoint) and
writes the Chrome Trace Event Format — load the file at
https://ui.perfetto.dev or chrome://tracing. ``ts`` counts microseconds
from ``t0``, a ``time.perf_counter`` reading that the export carries as
``otherData["t0_perf_counter_s"]``: drop one
``jax.profiler.TraceAnnotation`` holding a ``perf_counter_ns`` into a
jax.profiler capture and the host spans line up against its device lanes.

Disabled tracers are free: ``span()`` short-circuits before touching the
clock, so the default NULL_TRACER can stay wired into every fit loop.
"""

from __future__ import annotations

import json
import os
import threading
import time
from contextlib import contextmanager
from typing import List, Optional


class SpanTracer:
    def __init__(self, enabled: bool = True,
                 max_events: int = 200_000):
        self.enabled = enabled
        self.max_events = max_events
        self._events: List[dict] = []
        self._lock = threading.Lock()
        self.t0 = time.perf_counter()       # the zero every ``ts`` counts from
        self._dropped = 0

    # ---- recording ------------------------------------------------------
    @contextmanager
    def span(self, name: str, cat: str = "train", **args):
        if not self.enabled:
            yield
            return
        start = time.perf_counter()
        try:
            yield
        finally:
            self.add_span(name, start, time.perf_counter(), cat=cat, **args)

    def add_span(self, name: str, start_s: float, end_s: float,
                 cat: str = "train", **args):
        """Record a span retroactively from measured endpoints (the fit
        loop already times ETL windows; re-measuring would skew them)."""
        if not self.enabled:
            return
        ev = {
            "name": name, "cat": cat, "ph": "X",
            "ts": (start_s - self.t0) * 1e6,        # µs, trace-relative
            "dur": max(0.0, (end_s - start_s) * 1e6),
            "pid": os.getpid(), "tid": threading.get_ident(),
        }
        if args:
            ev["args"] = args
        with self._lock:
            if len(self._events) >= self.max_events:
                self._dropped += 1
                return
            self._events.append(ev)

    def instant(self, name: str, cat: str = "train", **args):
        """Zero-duration marker (e.g. a recompile sighting)."""
        if not self.enabled:
            return
        now = time.perf_counter()
        ev = {"name": name, "cat": cat, "ph": "i", "s": "t",
              "ts": (now - self.t0) * 1e6,
              "pid": os.getpid(), "tid": threading.get_ident()}
        if args:
            ev["args"] = args
        with self._lock:
            if len(self._events) < self.max_events:
                self._events.append(ev)

    # ---- export ---------------------------------------------------------
    @property
    def events(self) -> List[dict]:
        with self._lock:
            return list(self._events)

    @property
    def dropped_events(self) -> int:
        return self._dropped

    def clear(self):
        with self._lock:
            self._events.clear()
            self._dropped = 0

    def to_chrome_trace(self) -> dict:
        return {"traceEvents": self.events, "displayTimeUnit": "ms",
                "otherData": {"tracer": "deeplearning4j_tpu.observe",
                              "dropped_events": self._dropped,
                              "t0_perf_counter_s": self.t0}}

    def save(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.to_chrome_trace(), f)
        return path


class _NullTracer(SpanTracer):
    """Shared always-off tracer; wiring it in costs one ``if``."""

    def __init__(self):
        super().__init__(enabled=False)


NULL_TRACER = _NullTracer()


def get_tracer(model=None) -> SpanTracer:
    """The tracer attached to a model, else the shared no-op."""
    t: Optional[SpanTracer] = getattr(model, "tracer", None)
    return t if t is not None else NULL_TRACER


_ON_THIS_THREAD = threading.local()


def set_thread_tracer(tracer: SpanTracer) -> None:
    """Name ``tracer`` as the one of the work this thread does: the
    prefetch worker of a traced ``fit()`` does, so that what runs inside
    its ``produce`` span (a ``DataSetPreProcessor``) can record spans of
    its own without being handed a tracer."""
    _ON_THIS_THREAD.tracer = tracer


def thread_tracer() -> SpanTracer:
    """The tracer named for this thread, else the shared no-op."""
    return getattr(_ON_THIS_THREAD, "tracer", NULL_TRACER)
