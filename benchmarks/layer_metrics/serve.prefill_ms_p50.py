"""From admission to the first token (`admit_ts` to `first_token_ts`): one
synced prefill, median over the requests due in the window."""
from benchmarks.lib import stats

LAYER, UNIT, MOVES, SOURCE = "serving_engine", "ms", "ttft_p95_ms", "program_span"


def read(run):
    spans = [(r["first"] - r["admit"]) * 1e3 for r in run.get("requests", [])
             if r["in_window"] and r["admit"] is not None
             and r["first"] is not None]
    return stats.median(spans)
