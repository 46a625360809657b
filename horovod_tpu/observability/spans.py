"""Python-side span recorder + Chrome-trace merge.

The core's timeline (``csrc/timeline.cc``, enabled with ``HVD_TIMELINE``)
records the C++ half of a job — negotiation, fusion memcpys, TCP
transfers — as Chrome-trace events. This module records the *Python*
half (user-visible op calls, elastic resets, data-loading sections,
anything wrapped in :func:`span`) in the same event schema, and
:func:`merge_traces` folds any number of such files into ONE
Perfetto/chrome://tracing-loadable JSON, so host-plane C++ phases and
Python framework time line up on a single timeline.

:func:`span` is the one way the program opens a span, and one ``with``
feeds two sinks:

- the profiler: once ``jax`` is imported, a span is a
  ``jax.profiler.TraceAnnotation(name, **args)``, so it lands on the
  ``/host:CPU`` plane of the ``.xplane.pb`` on the clock of the device
  planes. The open profiler session (``jax.profiler.start_trace``,
  ``hvd.profiler.start``) is the switch: with none open the annotation is
  a no-op inside the runtime. This module itself never imports jax.
- the Chrome recorder below: off unless ``HVD_METRICS=1`` (or
  ``metrics.enable()``), same discipline as the metrics registry — while
  disabled no clock read, no lock, no allocation of its own.

Names are stable and carry no per-call value: a value (rid, fill, step)
goes in ``args``, never in the name.

Event schema (the subset both Chrome and Perfetto accept):
``{"name", "ph": "X", "ts": µs, "dur": µs, "pid", "tid"}`` for spans and
``"ph": "i"`` instants — exactly what ``csrc/timeline.cc`` emits, so
merged files are homogeneous.
"""

import contextlib
import json
import os
import sys
import threading
import time

from . import metrics as _metrics

_NOOP = contextlib.nullcontext()


class SpanRecorder:
    def __init__(self, pid=None):
        self._lock = threading.Lock()
        self._events = []
        # pid slot in the trace: the core timeline uses the rank; Python
        # spans use the OS pid by default so a merged multi-process trace
        # keeps rows distinct (override per-recorder for rank alignment).
        self.pid = os.getpid() if pid is None else pid

    @contextlib.contextmanager
    def _span(self, name, cat, args, inner=_NOOP):
        t0 = time.perf_counter_ns()
        try:
            with inner:
                yield
        finally:
            dur_us = (time.perf_counter_ns() - t0) // 1000
            ev = {"name": name, "ph": "X",
                  "ts": time.time_ns() // 1000 - dur_us, "dur": dur_us,
                  "pid": self.pid, "tid": threading.current_thread().name}
            if cat:
                ev["cat"] = cat
            if args:
                ev["args"] = dict(args)
            with self._lock:
                self._events.append(ev)

    def span(self, name, cat="python", **args):
        """Context manager for one span: a profiler annotation where jax
        is loaded, one complete Chrome event too while metrics are
        enabled, the shared no-op context where neither applies."""
        profiler = sys.modules.get("jax.profiler")
        annotation = (profiler.TraceAnnotation(name, **args)
                      if profiler is not None else _NOOP)
        if not _metrics.enabled():
            return annotation
        return self._span(name, cat, args, annotation)

    def event(self, name, ts_us, dur_us, cat="python", **args):
        """Record one complete event with caller-supplied wall-clock
        timestamps (µs, ``time.time_ns() // 1000`` epoch) — for derived
        sub-phases (e.g. pipeline warmup/steady/cooldown estimates)
        where a context manager can't wrap the phase as it runs."""
        if not _metrics.enabled():
            return
        ev = {"name": name, "ph": "X", "ts": int(ts_us),
              "dur": max(0, int(dur_us)), "pid": self.pid,
              "tid": threading.current_thread().name}
        if cat:
            ev["cat"] = cat
        if args:
            ev["args"] = dict(args)
        with self._lock:
            self._events.append(ev)

    def instant(self, name, **args):
        if not _metrics.enabled():
            return
        ev = {"name": name, "ph": "i", "ts": time.time_ns() // 1000,
              "pid": self.pid, "s": "p"}
        if args:
            ev["args"] = dict(args)
        with self._lock:
            self._events.append(ev)

    def events(self):
        with self._lock:
            return list(self._events)

    def clear(self):
        with self._lock:
            self._events.clear()

    def dump(self, path):
        """Write the recorded events as Chrome-trace JSON
        (``{"traceEvents": [...]}`` — the object form, so metadata can
        ride along and Perfetto accepts it directly)."""
        with open(path, "w") as f:
            json.dump({"traceEvents": self.events(),
                       "displayTimeUnit": "ms"}, f)
        return path


# Process-wide recorder + module-level conveniences.
recorder = SpanRecorder()
span = recorder.span
event = recorder.event
instant = recorder.instant
dump = recorder.dump


# ---------------------------------------------------------------------------
# Merge

def _load_trace_events(path):
    """Events from a Chrome-trace file in either shape (bare array or
    ``{"traceEvents": ...}``). The core's writer only emits the closing
    ``]`` at Shutdown, so a file snapshotted mid-job is unterminated —
    repair the common truncations instead of failing the whole merge."""
    with open(path) as f:
        text = f.read()
    try:
        data = json.loads(text)
    except ValueError:
        t = text.rstrip().rstrip(",")
        for suffix in ("]", "}]", '"}]'):
            try:
                data = json.loads(t + suffix)
                break
            except ValueError:
                continue
        else:
            raise ValueError(f"{path}: not parseable as Chrome-trace JSON "
                             f"(even after truncation repair)")
    if isinstance(data, dict):
        data = data.get("traceEvents", [])
    if not isinstance(data, list):
        raise ValueError(f"{path}: expected an event array or "
                         f"{{'traceEvents': [...]}}")
    return [e for e in data if isinstance(e, dict)]


def merge_traces(out_path, *paths, extra_events=()):
    """Merge Chrome-trace files (core timeline, Python span dumps, rankN
    sidecars) into one Perfetto-loadable JSON at ``out_path``.

    Events are concatenated and time-sorted; the per-file pid/tid rows
    keep sources distinct in the viewer. Returns ``out_path``.
    """
    events = list(extra_events)
    for p in paths:
        events.extend(_load_trace_events(p))
    events.sort(key=lambda e: e.get("ts", 0))
    with open(out_path, "w") as f:
        json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, f)
    return out_path
