"""Readers of the program's own spans (PR 26): the `serve_step` sink records
carry each dispatch's span times and `host_gap_ms`, and the engines'
boundary spans sit in the program's span ring (`observability/tracer.py`),
always recorded. A program that has neither, as the parent of PR 26 has not,
gives every reader here nothing, and the line leaves the metric out."""
from __future__ import annotations

from benchmarks.lib import stats


def _window_steps(run):
    """The `serve_step` sink records written inside the window."""
    if "window" not in run:
        return []
    w0, w1 = (t + run["wall_minus_perf"] for t in run["window"])
    return [r for r in run.get("sink", [])
            if r.get("event") == "serve_step" and w0 <= r["ts"] <= w1]


def host_gap_ms_p50(run):
    """Median `host_gap_ms` a dispatch: from the end of the last dispatch's
    `serve.decode.fetch` to the end of this one's `serve.decode.dispatch`,
    the time the decode program had nothing enqueued (prefills between the
    two included)."""
    return stats.median([r["host_gap_ms"] for r in _window_steps(run)
                         if r.get("host_gap_ms") is not None])


def prefill_sync_ms_p50(run):
    """Median `serve.prefill.sync`: the host blocked on a prefill's first
    token (`int(tok)`), one a request admitted in the window."""
    return stats.median([ms for r in _window_steps(run)
                         for ms in r.get("spans_ms", {}).get(
                             "prefill_sync", [])])


def host_dispatch_ms_p50(run):
    """Median `engine.step` span (enter to the return of the enqueue, never
    a sync) over the steps that did not compile. The ring holds the whole
    run: the window's steps, and the few of set-up and of the closing visit
    to batch 0, which a median does not feel."""
    try:
        from paddle_tpu.observability import tracer
    except ImportError:
        return None
    return stats.median([
        e["dur"] * 1e3 for e in tracer.get_tracer().events()
        if e["name"] == "engine.step" and e.get("dur") is not None
        and (e.get("args") or {}).get("compiled") is False])
