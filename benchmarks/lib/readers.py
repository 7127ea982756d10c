"""Readers that several per-layer metrics share (one metric per cell, since a
metric moves one end-to-end metric and the cells report different ones)."""
from __future__ import annotations

from benchmarks.lib import stats


def idle_share(run):
    """1 - (union of the device's instruction intervals) / (traced
    sub-window), averaged over the chips, in percent."""
    trace = run.get("trace")
    if not trace or not trace["window_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])


def serve_step_ms_p50(run):
    """Median host time of one `eng.step()` inside the window, admissions
    included, over the decode steps one dispatch fuses."""
    if not run.get("steps") or "window" not in run:
        return None
    w0, w1 = run["window"]
    return stats.median([(b - a) * 1e3 / run["steps_per_dispatch"]
                         for a, b, _ in run["steps"] if w0 <= a and b <= w1])
