"""Median time of one optimizer step: sub-windows of a few steps, each ended
by fetching the loss, divided by their steps (host clock)."""
from benchmarks.lib import stats

LAYER, UNIT, MOVES, SOURCE = "train_engine", "ms", "train_tokens_per_s", "host_clock"


def read(run):
    if not run.get("windows"):
        return None
    return stats.median([dt / n * 1e3 for dt, n in run["windows"]])
