"""Span tracer: Chrome-trace ("catapult") JSON, and the JAX profiler's
trace when one is recording.

Spans cover the host-side serving path (session enqueue / poll / step,
`apply_round`, deferral loops), the pressure scheduler's bounds reads and
compaction passes, the WAL, bucket migrations, replica resync/rebuild,
snapshots and recovery.

API: `span(name, cat, **args)` is a context manager; it is the only
span API.  Each span goes to up to two places:

* while a JAX profiler session records (`jax.profiler.trace`,
  `start_trace`), a `jax.profiler.TraceAnnotation` named `f2.<name>`
  with `args` as its metadata: it lands on the trace's `/host:CPU`
  plane, on the clock of the device ops, whether or not obs is armed;
* while obs is armed (`obs.configure(enabled=True)`), the bounded
  in-process buffer below, saved as Chrome JSON for `chrome://tracing`
  or Perfetto (`ui.perfetto.dev`).

With neither on, every call returns the shared no-op singleton: one
flag check and one `TraceAnnotation.is_enabled()` call, no event object,
no timestamp read, no allocation.

Buffer events use the Chrome trace "complete" phase (`ph: "X"`): one
record per span with microsecond `ts`/`dur` relative to tracer start.
The buffer is bounded; once full, new events are counted in `dropped`
instead of growing without bound."""
from __future__ import annotations

import json
import os
import threading
import time

from jax.profiler import TraceAnnotation

from . import _flags

DEFAULT_CAPACITY = 200_000
PROFILER_PREFIX = "f2."


class _NoopSpan:
    """The disabled-path singleton: entering/exiting does nothing."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NOOP_SPAN = _NoopSpan()


class _Span:
    __slots__ = ("_tracer", "name", "cat", "args", "_t0", "_ann")

    def __init__(self, tracer, name, cat, args, ann=None):
        self._tracer = tracer
        self.name = name
        self.cat = cat
        self.args = args
        self._ann = ann             # the profiler's annotation, if recording

    def __enter__(self):
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        self._tracer._add({
            "name": self.name, "cat": self.cat, "ph": "X",
            "ts": (self._t0 - self._tracer._t0) / 1e3,
            "dur": (t1 - self._t0) / 1e3,
            "pid": self._tracer._pid, "tid": threading.get_ident(),
            "args": self.args,
        })
        if self._ann is not None:
            self._ann.__exit__(*exc)
        return False


class Tracer:
    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        self.capacity = int(capacity)
        self._lock = threading.Lock()
        self._events: list = []
        self.dropped = 0
        self._t0 = time.perf_counter_ns()
        self._pid = os.getpid()

    def _add(self, ev: dict):
        with self._lock:
            if len(self._events) >= self.capacity:
                self.dropped += 1
                return
            self._events.append(ev)

    def span(self, name: str, cat: str = "f2", **args) -> _Span:
        return _Span(self, name, cat, args)

    def __len__(self):
        with self._lock:
            return len(self._events)

    def snapshot(self) -> dict:
        """The Chrome trace JSON object (`{"traceEvents": [...]}`)."""
        with self._lock:
            events = list(self._events)
        return {"traceEvents": events, "displayTimeUnit": "ms",
                "otherData": {"dropped": self.dropped}}

    def save(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.snapshot(), f)
        return path

    def clear(self):
        with self._lock:
            self._events = []
            self.dropped = 0
            self._t0 = time.perf_counter_ns()


TRACER = Tracer()


def span(name: str, cat: str = "f2", **args):
    """A traced region: `f2.<name>` in the profiler's trace while one
    records, an event in `TRACER` while obs is armed, the no-op singleton
    otherwise.  Give only host values as `args` (ints, strings): the
    caller evaluates them even when nothing records."""
    profiling = TraceAnnotation.is_enabled()
    if not _flags.ENABLED:
        if profiling:
            return TraceAnnotation(PROFILER_PREFIX + name, **args)
        return NOOP_SPAN
    return _Span(TRACER, name, cat, args,
                 TraceAnnotation(PROFILER_PREFIX + name, **args)
                 if profiling else None)
