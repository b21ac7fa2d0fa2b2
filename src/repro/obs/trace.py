"""Host-side span tracer: the engine's spans -> ring buffer + device trace.

A :class:`SpanTracer` records complete ("ph": "X") spans into a
bounded ring buffer; :meth:`SpanTracer.export` renders the Chrome
trace-event format that ``chrome://tracing`` and Perfetto load
directly.  The engine opens spans at its layer boundaries (tick phases,
prefill, the wait on the device, Merkle upkeep) when constructed with
``trace=...``; each shard engine traces under its own ``pid`` so a
cluster export shows the per-shard overlap the
dispatch-all-before-collect-any tick is supposed to buy.

Every span opened with :meth:`SpanTracer.span` is also a
``jax.profiler.TraceAnnotation`` named ``engine.<name>`` (a
``StepTraceAnnotation`` when given a ``step_num``), so under a profiler
session the same span lands in the ``.xplane.pb`` on the device
trace's own clock.  Spans recorded with :meth:`SpanTracer.add` after
the fact (a request's queue wait, which began at ``submit``) reach the
ring buffer only: a profiler annotation cannot be backdated.

Timing uses ``time.perf_counter_ns`` against a per-process origin, so
spans from tracers created at different times still land on one
comparable timeline.  Code that traces only when a tracer is attached
uses :data:`NO_SPAN`, one shared no-op context, otherwise.
"""

from __future__ import annotations

import contextlib
import json
import time
from collections import deque
from typing import Optional

from jax import profiler as jax_profiler

__all__ = ["NO_SPAN", "SpanTracer", "owner_span"]

# One origin per process: every tracer's timestamps are offsets from
# here, so multi-tracer (cluster) exports share a timeline.
_ORIGIN_NS = time.perf_counter_ns()

PREFIX = "engine."      # profiler-trace names: engine.<span name>

# The span of an untraced engine: enters and leaves without a clock
# read, an allocation or a profiler annotation.
NO_SPAN = contextlib.nullcontext()


class _Span:
    """One open span: a profiler annotation and a ring-buffer record."""

    __slots__ = ("tracer", "name", "hist", "step_num", "args", "note",
                 "t0")

    def __init__(self, tracer, name, hist, step_num, args):
        self.tracer, self.name, self.hist = tracer, name, hist
        self.step_num, self.args = step_num, args

    def __enter__(self):
        if self.step_num is None:
            self.note = jax_profiler.TraceAnnotation(PREFIX + self.name)
        else:
            self.note = jax_profiler.StepTraceAnnotation(
                PREFIX + self.name, step_num=self.step_num)
        self.note.__enter__()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        self.note.__exit__(*exc)
        self.tracer.add(self.name, self.t0, t1, self.args)
        if self.hist is not None:
            self.hist.observe((t1 - self.t0) / 1e9)
        return False


def owner_span(owner, name: str, rid: Optional[int] = None,
               step_num: Optional[int] = None):
    """The span ``name`` of an engine or cluster: its ``_span`` method.

    ``owner`` has ``tracer`` (a :class:`SpanTracer` or ``None``),
    ``tick`` and ``_span_hists`` (span name -> histogram).  Traced, the
    span carries the tick (``step_num`` when given, for the tick span
    itself) and the request's ``rid``.  Untraced, it is :data:`NO_SPAN`:
    no clock read, no allocation, no annotation."""
    if owner.tracer is None:
        return NO_SPAN
    args = {"tick": owner.tick if step_num is None else step_num}
    if rid is not None:
        args["rid"] = rid
    return owner.tracer.span(name, hist=owner._span_hists.get(name),
                             step_num=step_num, **args)


class SpanTracer:
    """Ring buffer of completed spans, Chrome-trace exportable."""

    def __init__(self, *, pid: int = 0, tid: int = 0,
                 capacity: int = 65536):
        self.pid, self.tid = pid, tid
        self._events: deque = deque(maxlen=capacity)

    def __len__(self) -> int:
        return len(self._events)

    def add(self, name: str, t0_ns: int, t1_ns: int,
            args: Optional[dict] = None) -> None:
        """Record one completed span (absolute perf_counter_ns pair)."""
        event = {
            "name": name,
            "ph": "X",
            "ts": (t0_ns - _ORIGIN_NS) / 1000.0,     # microseconds
            "dur": (t1_ns - t0_ns) / 1000.0,
            "pid": self.pid,
            "tid": self.tid,
        }
        if args:
            event["args"] = args
        self._events.append(event)

    def span(self, name: str, *, hist=None, step_num: Optional[int] = None,
             **args) -> _Span:
        """Context manager recording one span around its body.

        Under a profiler session the body is also the annotation
        ``engine.<name>`` (a step annotation numbered ``step_num`` when
        given).  ``hist``, a histogram, observes the span's seconds."""
        return _Span(self, name, hist, step_num, args or None)

    def events(self) -> list:
        """The buffered spans as Chrome trace-event dicts (oldest first)."""
        return list(self._events)

    def clear(self) -> None:
        self._events.clear()

    def export(self, path: Optional[str] = None, *,
               extra_events: Optional[list] = None) -> dict:
        """The Chrome trace-event JSON document; written when ``path``.

        ``extra_events`` lets a cluster merge its shard tracers into
        one file (every tracer stamps its own ``pid``).
        """
        events = self.events() + list(extra_events or [])
        events.sort(key=lambda e: e["ts"])
        doc = {"traceEvents": events, "displayTimeUnit": "ms"}
        if path is not None:
            with open(path, "w") as f:
                json.dump(doc, f)
        return doc
