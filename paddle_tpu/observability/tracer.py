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
- start-up's events are ALWAYS recorded too, through
  ``record_complete(always=True)``: ``startup.import`` (the package's own
  import), ``exec.first_call`` (``core/exec_registry.py``: the call of a
  registered executable that compiled) and ``jit.trace`` / ``jit.lower`` /
  ``jit.backend`` / ``jit.cache_load`` (``core/compile_cache.py``: every jit
  phase of the process, as jax times it). They fire only when jax compiles;
- ``span_table()`` / ``phase_table()`` reduce the ring to one table: count,
  total and SELF time a span name, the time no span was open (``caller``),
  and the jit phases inside and outside an ``exec.first_call``;
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
import functools
import heapq
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

    def current_span_id(self) -> Optional[int]:
        """The id of the innermost boundary span open on this thread, the
        parent of whatever happens now; None at the root."""
        stack = self._open_spans()
        return stack[-1].id if stack else None

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

    def phase_table(self, until: Optional[float] = None,
                    since: Optional[float] = None) -> dict:
        """`span_table` of the ring between two `time.perf_counter()`
        readings (default: all it holds); every time in the result is on
        that clock too."""
        table = span_table(
            self.events(),
            None if since is None else since - _ORIGIN,
            None if until is None else until - _ORIGIN)
        table["since"] += _ORIGIN
        table["until"] += _ORIGIN
        for gap in table["caller_longest"]:
            gap["start"] += _ORIGIN
        return table

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


# ---- the table over the ring ---------------------------------------------
JIT_PHASES = ("jit.trace", "jit.lower", "jit.backend")
JIT_EVENTS = JIT_PHASES + ("jit.cache_load",)    # the load lies in a backend
FIRST_CALL = "exec.first_call"
CALLER = "caller"             # no span of the program open on any thread


def _label(e: dict) -> str:
    """A span's name; a jit event's with its function."""
    fun = (e.get("args") or {}).get("fun")
    return f"{e['name']}:{fun}" if fun else e["name"]


def span_table(events: List[dict], since: Optional[float] = None,
               until: Optional[float] = None) -> dict:
    """Reduce span events (`Tracer.events()`: name, ts, dur, tid, args) to
    one table over [since, until] (default: first start to last end; events
    are clipped to it), on whatever axis `ts` is on:

    - `rows`: a span name -> `count`, `total_s` and `self_s`. Nesting is by
      INTERVAL on a thread (an `exec.first_call` is recorded after the fact
      and holds the jit events that ran inside it, whatever their `parent`
      says). `total_s` leaves out an event nested in one of its own name,
      and a `jit.trace` / `jit.lower` / `jit.backend` nested in any of the
      three (a jit traced inside another's trace, a trace a lowering fires:
      only the OUTERMOST counts, so the phases' totals share no second);
      `self_s` is an event's duration minus what
      its direct children cover, so the rows' self times and `caller` add up
      to the table's length on one thread.
    - `caller`: the time no span was open on any thread, as a row, and
      `caller_longest`, its three longest intervals with the top-level span
      that ended before and the one that started after each.
    - `jit`: for each jit event name `count`, `total_s` and its split
      `in_first_call_s` / `outside_s` (outermost events only, inside or
      outside an `exec.first_call`); `unregistered_s`, the three phases'
      `outside_s` summed; `largest`, the ten longest by `fun`, each with the
      span of the program it ran `under`."""
    spans = [e for e in events if e.get("dur") is not None]
    if since is None:
        since = min((e["ts"] for e in spans), default=0.0)
    if until is None:
        until = max((e["ts"] + e["dur"] for e in spans), default=since)
    by_tid: Dict[int, list] = {}
    for e in spans:
        a, b = max(e["ts"], since), min(e["ts"] + e["dur"], until)
        if a < b or (a == b and e["dur"] == 0.0):
            by_tid.setdefault(e["tid"], []).append([a, b, e, 0.0])
    rows: Dict[str, dict] = {}
    jit = {n: {"count": 0, "total_s": 0.0, "in_first_call_s": 0.0,
               "outside_s": 0.0} for n in JIT_EVENTS}
    largest, top = [], []
    for items in by_tid.values():
        # clipped to one interval, the one that was longer holds the other
        items.sort(key=lambda it: (it[0], -it[1], it[2]["ts"],
                                   -it[2]["ts"] - it[2]["dur"]))
        stack: list = []
        for it in items:
            while stack and stack[-1][1] <= it[0]:
                stack.pop()
            if stack:
                it[1] = min(it[1], stack[-1][1])   # an overlap is clipped
                stack[-1][3] += it[1] - it[0]
            else:
                top.append(it)
            name, dur = it[2]["name"], it[1] - it[0]
            row = rows.setdefault(name, {"count": 0, "total_s": 0.0,
                                         "self_s": 0.0})
            row["count"] += 1
            held = JIT_PHASES if name in JIT_PHASES else (name,)
            if not any(up[2]["name"] in held for up in stack):
                row["total_s"] += dur
                if name in jit:
                    inside = any(up[2]["name"] == FIRST_CALL for up in stack)
                    j = jit[name]
                    j["count"] += 1
                    j["total_s"] += dur
                    j["in_first_call_s" if inside else "outside_s"] += dur
                    under = next((up[2]["name"] for up in reversed(stack)
                                  if up[2]["name"] not in jit
                                  and up[2]["name"] != FIRST_CALL), CALLER)
                    largest.append({
                        "name": name, "dur_s": dur, "under": under,
                        "fun": (it[2].get("args") or {}).get("fun"),
                        "in_first_call": inside})
            stack.append(it)
        for it in items:
            rows[it[2]["name"]]["self_s"] += (it[1] - it[0]) - it[3]
    gaps, at, before = [], since, "start"
    for a, b, e, _ in sorted(top, key=lambda it: it[0]):
        if a > at:
            gaps.append({"start": at, "dur_s": a - at, "before": before,
                         "after": _label(e)})
        if b >= at:
            at, before = b, _label(e)
    if until > at:
        gaps.append({"start": at, "dur_s": until - at, "before": before,
                     "after": "end"})
    idle = sum(g["dur_s"] for g in gaps)
    rows[CALLER] = {"count": len(gaps), "total_s": idle, "self_s": idle}
    jit["unregistered_s"] = sum(jit[n]["outside_s"] for n in JIT_PHASES)
    jit["largest"] = heapq.nlargest(10, largest, key=lambda r: r["dur_s"])
    return {"since": since, "until": until, "total_s": until - since,
            "rows": rows, "jit": jit,
            "caller_longest": heapq.nlargest(3, gaps,
                                             key=lambda g: g["dur_s"])}


def events_from_chrome(trace: dict) -> List[dict]:
    """The events of an exported chrome trace (`export_chrome_trace`) in
    `Tracer.events()`' form, seconds from the exporting process's origin:
    what `span_table` takes (tools/trace_summary.py)."""
    out = []
    for ev in trace.get("traceEvents", []):
        if ev.get("ph") != "X":
            continue
        args = dict(ev.get("args") or {})
        sid, parent = args.pop("span_id", None), args.pop("parent_span", None)
        out.append({"name": ev["name"], "ts": ev["ts"] / 1e6,
                    "dur": ev["dur"] / 1e6,
                    "tid": (ev.get("pid"), ev.get("tid")),
                    **({"args": args} if args else {}),
                    **({"id": sid, "parent": parent}
                       if sid is not None else {})})
    return out


def format_span_table(table: dict) -> str:
    """`span_table`'s result as text: the rows by self time, the longest
    stretches of `caller`, the jit phases and the largest jit events."""
    total = table["total_s"] or 1.0
    lines = [f"{table['total_s']:.3f} s from {table['since']:.3f} to "
             f"{table['until']:.3f}",
             f"{'span':<28}{'count':>7}{'total_s':>10}{'self_s':>10}"
             f"{'self %':>8}"]
    for name, r in sorted(table["rows"].items(),
                          key=lambda kv: -kv[1]["self_s"]):
        lines.append(f"{name:<28}{r['count']:>7}{r['total_s']:>10.3f}"
                     f"{r['self_s']:>10.3f}{100 * r['self_s'] / total:>8.2f}")
    for g in table["caller_longest"]:
        lines.append(f"caller {g['dur_s']:.3f} s at {g['start']:.3f}: after "
                     f"{g['before']}, before {g['after']}")
    jit = table["jit"]
    for name in JIT_EVENTS:
        j = jit[name]
        lines.append(f"{name:<16}{j['count']:>6} events {j['total_s']:>9.3f} "
                     f"s: {j['in_first_call_s']:.3f} in a first call, "
                     f"{j['outside_s']:.3f} outside")
    lines.append(f"jit outside every first call: {jit['unregistered_s']:.3f} s")
    for r in jit["largest"]:
        lines.append(f"  {r['name']:<14}{r['dur_s']:>9.3f} s  {r['fun'] or '-'}  "
                     f"(under {r['under']}"
                     f"{', in a first call' if r['in_first_call'] else ''})")
    return "\n".join(lines)


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


def phase_table(until: Optional[float] = None,
                since: Optional[float] = None) -> dict:
    """The global tracer's `Tracer.phase_table`: the table of start-up
    (`until` = the first step's start) or of a window (`since`, `until`),
    on `time.perf_counter()`'s axis."""
    return _global_tracer.phase_table(until, since)


def in_boundary(name: str):
    """Decorator: the call runs inside a `boundary` span of the global
    tracer (an engine's constructor)."""
    def wrap(fn):
        @functools.wraps(fn)
        def spanned(*args, **kwargs):
            with _global_tracer.boundary(name):
                return fn(*args, **kwargs)
        return spanned
    return wrap


def span(name: str, **args):
    """Module-level sugar over the global tracer."""
    return _global_tracer.span(name, **args)
