"""Host-side span tracer: the framework's always-available timeline recorder.

Replaces the profiler's aggregate-only ``_event_stats`` dict with a real
event stream: every span keeps (name, ts, dur, tid, args) in a bounded ring
buffer and exports genuine chrome-trace JSON (``trace_events`` format), so
host markers can be loaded into Perfetto/chrome://tracing next to the
``jax.profiler`` device timeline. The reference analogue is
HostEventRecorder + the chrome-trace serializer in
paddle/fluid/platform/profiler/chrometracing_logger.cc.

Two-tier cost model (the subsystem is meant to stay ON in production):

- aggregates (count/total/max/min per span name) are ALWAYS maintained —
  a dict update per span end, the same cost the old ``_event_stats`` paid;
- ``boundary()`` spans — the handful an engine opens per dispatch
  (``serve.*``, ``engine.*``) — are ALWAYS recorded into the ring: two clock
  reads, one deque append, no device sync, no I/O. Each carries its own id,
  the id of the span that caused it (the innermost boundary span open on
  the thread) and, for serving, the request id(s) in its args. Each also opens a
  ``jax.profiler.TraceAnnotation`` of the same name when jax is loaded,
  which costs nothing without a profiler session and puts the span on the
  device trace's clock when there is one;
- every other event (``span()``, per-op ``RecordEvent``, ``instant()``) is
  recorded ONLY while ``enable()`` is active; when tracing is disabled,
  ``span()`` returns a shared no-op context manager: no timestamp is
  taken, no allocation, no I/O;
- the ring buffer has a fixed capacity (old events are dropped, memory is
  bounded), and this module never imports jax.

Thread safety: one lock guards the ring buffer and the aggregate table;
span objects themselves are not shared across threads (each ``span()`` call
makes its own). tid is the OS thread ident so nested spans from different
threads land on separate chrome-trace rows.
"""
from __future__ import annotations

import collections
import itertools
import json
import os
import sys
import threading
import time
from typing import Dict, List, Optional

# chrome trace wants microseconds; all internal timestamps are seconds from
# the process-wide origin below so exported traces from one process align.
_ORIGIN = time.perf_counter()

_span_ids = itertools.count(1)


class _NullSpan:
    """Shared disabled-path context manager: enter/exit do nothing."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class _Span:
    """RAII span bound to one tracer; records a complete event on exit.
    ``id`` is process-unique; ``parent`` is the id of the span that caused
    this one (None at the root)."""

    __slots__ = ("_tracer", "name", "args", "id", "parent", "t0", "t1",
                 "_always", "_ta")

    def __init__(self, tracer: "Tracer", name: str, args: Optional[dict],
                 always: bool = False):
        self._tracer = tracer
        self.name = name
        self.args = args
        self.id = next(_span_ids)
        self.parent = None
        self._always = always
        self._ta = None
        self.t1 = None
        self.t0 = time.perf_counter()

    def __enter__(self):
        if self._always:
            stack = self._tracer._open_spans()
            if stack:
                self.parent = stack[-1].id
            stack.append(self)
            jax = sys.modules.get("jax")
            if jax is not None:
                self._ta = jax.profiler.TraceAnnotation(self.name)
                self._ta.__enter__()
        return self

    def __exit__(self, *exc):
        self.end()
        return False

    @property
    def ms(self) -> Optional[float]:
        """Duration in milliseconds once the span has ended."""
        return None if self.t1 is None else (self.t1 - self.t0) * 1e3

    def end(self):
        if self.t1 is not None:
            return
        if self._ta is not None:
            self._ta.__exit__(None, None, None)
            self._ta = None
        self.t1 = time.perf_counter()
        if self._always:
            stack = self._tracer._open_spans()
            if self in stack:
                del stack[stack.index(self):]
        self._tracer.record_complete(
            self.name, self.t0, self.t1, self.args, span_id=self.id,
            parent=self.parent, always=self._always)


class Tracer:
    def __init__(self, capacity: int = 100_000):
        self._lock = threading.Lock()
        self._events: collections.deque = collections.deque(maxlen=capacity)
        self._stats: Dict[str, list] = {}  # name -> [count, total, max, min]
        self.enabled = False
        self._dropped = 0
        self._local = threading.local()   # open boundary spans, per thread

    def _open_spans(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    # ---- control ----
    def enable(self) -> None:
        self.enabled = True

    def disable(self) -> None:
        self.enabled = False

    def clear(self) -> None:
        with self._lock:
            self._events.clear()
            self._dropped = 0

    def clear_stats(self) -> None:
        with self._lock:
            self._stats.clear()

    # ---- recording ----
    def span(self, name: str, **args):
        """Context manager timing a region. Free when tracing is disabled
        AND no aggregate is wanted — aggregates come from explicit
        RecordEvent/record_complete callers, so the fast path here is a
        single attribute check."""
        if not self.enabled:
            return _NULL_SPAN
        return _Span(self, name, args or None)

    def boundary(self, name: str, **args):
        """An engine-boundary span: recorded whether or not ``enable()`` is
        active, with an id and a parent (the innermost boundary span open
        on this thread), and mirrored as a ``jax.profiler.TraceAnnotation``.
        For the few spans an engine opens per dispatch, never per op or per
        token."""
        return _Span(self, name, args or None, always=True)

    def record_complete(self, name: str, t0: float, t1: float,
                        args: Optional[dict] = None,
                        tid: Optional[int] = None,
                        aggregate: bool = True,
                        span_id: Optional[int] = None,
                        parent: Optional[int] = None,
                        always: bool = False) -> None:
        """Record a finished [t0, t1] perf_counter interval."""
        dur = t1 - t0
        with self._lock:
            if aggregate:
                st = self._stats.get(name)
                if st is None:
                    st = self._stats[name] = [0, 0.0, 0.0, float("inf")]
                st[0] += 1
                st[1] += dur
                if dur > st[2]:
                    st[2] = dur
                if dur < st[3]:
                    st[3] = dur
            if always or self.enabled:
                if len(self._events) == self._events.maxlen:
                    self._dropped += 1
                self._events.append((name, t0 - _ORIGIN, dur,
                                     tid if tid is not None
                                     else threading.get_ident(), args,
                                     span_id, parent))

    def instant(self, name: str, **args) -> None:
        """Zero-duration marker (chrome-trace 'i' event)."""
        if not self.enabled:
            return
        t = time.perf_counter()
        with self._lock:
            self._events.append((name, t - _ORIGIN, None,
                                 threading.get_ident(), args or None,
                                 None, None))

    # ---- inspection / export ----
    def events(self) -> List[dict]:
        """Snapshot of buffered events as dicts (ts/dur in seconds from
        the process origin; ``id``/``parent`` on spans that carry them)."""
        with self._lock:
            return [
                {"name": n, "ts": ts, "dur": dur, "tid": tid,
                 **({"args": args} if args else {}),
                 **({"id": sid, "parent": parent} if sid is not None else {})}
                for n, ts, dur, tid, args, sid, parent in self._events
            ]

    def stats(self) -> Dict[str, list]:
        """name -> [count, total_s, max_s, min_s] aggregate table."""
        with self._lock:
            return {n: list(v) for n, v in self._stats.items()}

    @property
    def dropped(self) -> int:
        return self._dropped

    def chrome_trace(self, process_name: str = "paddle_tpu host") -> dict:
        """The buffered timeline in chrome-trace ``trace_events`` format
        (complete 'X' events in microseconds), ready to json.dump or to
        merge with a jax.profiler perfetto export."""
        pid = os.getpid()
        trace_events = [{
            "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
            "args": {"name": process_name},
        }]
        with self._lock:
            for name, ts, dur, tid, args, sid, parent in self._events:
                ev = {"name": name, "pid": pid, "tid": tid,
                      "ts": round(ts * 1e6, 3)}
                if dur is None:
                    ev["ph"] = "i"
                    ev["s"] = "t"
                else:
                    ev["ph"] = "X"
                    ev["dur"] = round(dur * 1e6, 3)
                if args or sid is not None:
                    ev["args"] = dict(args or {})
                    if sid is not None:
                        ev["args"].update(span_id=sid, parent_span=parent)
                trace_events.append(ev)
        return {"traceEvents": trace_events, "displayTimeUnit": "ms"}

    def export_chrome_trace(self, path: str) -> str:
        """Write the chrome trace JSON to ``path`` and return the path."""
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f)
        return path


_global_tracer = Tracer()


def new_span_id() -> int:
    """Process-unique id for cross-component span parentage (fleet trace
    context): the router mints one per placement span; engine-side child
    spans carry it as ``parent_span`` so one chrome trace links routing
    decision -> queue wait -> prefill/decode for a single request."""
    return next(_span_ids)


def get_tracer() -> Tracer:
    return _global_tracer


def enabled() -> bool:
    return _global_tracer.enabled


def span(name: str, **args):
    """Module-level sugar over the global tracer."""
    return _global_tracer.span(name, **args)
