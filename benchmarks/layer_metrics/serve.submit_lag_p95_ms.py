"""How late the generator ran: from the instant a request was due to
`submit_ts`. The one driving thread submits between blocking `step()`s, so
this is the part of the wait that the entry point's blocking call imposes."""
from benchmarks.lib import stats

LAYER, UNIT, MOVES, SOURCE = "entry_points", "ms", "ttft_p95_ms", "program_span"


def read(run):
    lags = [(r["submit"] - r["due"]) * 1e3 for r in run.get("requests", [])
            if r["in_window"]]
    return stats.percentile(lags, 0.95)
