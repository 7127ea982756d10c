"""Readers of start-up's spans (PR 37). The program keeps every jit phase of
the process (`jit.trace` / `jit.lower` / `jit.backend`, as jax times them),
each call of a registered executable that compiled (`exec.first_call`), its
own import (`startup.import`) and the engines' constructors
(`serve.engine.init`, `engine.init`) in its span ring, always, and reduces
the ring to one table (`observability/tracer.py` `phase_table`). These
readers call that function: with `until` = the window's start for set-up,
over the window for the retraces inside it. A program that has no such
function, as the parent of PR 37 has not, or whose ring holds no jit event,
gives every reader here nothing, and the line leaves the metric out."""
from __future__ import annotations

import sys

_tables: dict = {}


def _clock_origin():
    """`benchmarks/run.py`'s `_T0`: process start on `time.perf_counter()`,
    which `setup_s` is counted from."""
    for name in ("__main__", "benchmarks.run"):
        t0 = getattr(sys.modules.get(name), "_T0", None)
        if t0 is not None:
            return t0
    return None


def window(run):
    """(start, end) of the measured window on `time.perf_counter()`. The
    serving runners hand it over; the train runner hands over its sub-windows
    and its rate, which give the length, and the start is `setup_s` after
    the process's."""
    if "window" in run:
        return tuple(run["window"])
    t0 = _clock_origin()
    if t0 is None or "setup_s" not in run or not run.get("windows"):
        return None
    steps = sum(k for _, k in run["windows"])
    seconds = (steps * run["batch_per_chip"] * run["seq"]
               / run["tokens_per_s_per_chip"])
    return (t0 + run["setup_s"], t0 + run["setup_s"] + seconds)


def _table(run, of_window: bool):
    try:
        from paddle_tpu.observability.tracer import phase_table
    except ImportError:
        return None
    w = window(run)
    if w is None:
        return None
    since, until = w if of_window else (
        w[0] - run["setup_s"] if "setup_s" in run else None, w[0])
    if (since, until) not in _tables:
        _tables[(since, until)] = phase_table(until=until, since=since)
    return _tables[(since, until)]


def setup_table(run):
    """The table of everything before the window, None without jit events
    (a process cannot reach a window without tracing something)."""
    table = _table(run, False)
    if table is None or not table["jit"]["jit.trace"]["count"]:
        return None
    return table


def span_seconds(run, name: str, key: str):
    """`total_s` or `self_s` of one span name during set-up."""
    table = setup_table(run)
    if table is None or name not in table["rows"]:
        return None
    return table["rows"][name][key]


def jit_seconds(run, phase: str):
    """The OUTERMOST `jit.<phase>` intervals of set-up, summed: a jit traced
    inside another's trace counts once, in the outer's."""
    table = setup_table(run)
    return None if table is None else table["jit"]["jit." + phase]["total_s"]


def jit_unregistered_seconds(run):
    """The part of the trace, lowering and backend sums that lies inside no
    `exec.first_call`: jits that no registry holds (initializers, eager
    rules, the check's reference, the train engine's own helpers)."""
    table = setup_table(run)
    return None if table is None else table["jit"]["unregistered_s"]


def window_jit_traces(run):
    """Outermost `jit.trace` events inside the window: 0 expected. A retrace
    of anything, registered or not, counts; `phase_table(since, until)
    ["jit"]["largest"]` names the function."""
    if setup_table(run) is None:
        return None
    return _table(run, True)["jit"]["jit.trace"]["count"]
