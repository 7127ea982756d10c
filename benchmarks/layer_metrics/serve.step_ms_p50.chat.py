"""Median host time round `eng.step()` over `steps_per_dispatch`, in the
chat cell."""
from benchmarks.lib.readers import serve_step_ms_p50 as read  # noqa: F401

LAYER, UNIT, MOVES, SOURCE = "serving_engine", "ms", "tpot_p95_ms", "host_clock"
