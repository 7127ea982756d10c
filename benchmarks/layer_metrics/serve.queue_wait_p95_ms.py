"""From the instant a request was due to its admission (`admit_ts`), 95th
percentile over the requests due in the window that were admitted."""
from benchmarks.lib import stats

LAYER, UNIT, MOVES, SOURCE = "serving_engine", "ms", "ttft_p95_ms", "program_span"


def read(run):
    waits = [(r["admit"] - r["due"]) * 1e3 for r in run.get("requests", [])
             if r["in_window"] and r["admit"] is not None]
    return stats.percentile(waits, 0.95)
