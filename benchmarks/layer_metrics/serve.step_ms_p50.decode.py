"""Median host time round `eng.step()` over `steps_per_dispatch`, in the
decode cell."""
from benchmarks.lib.readers import serve_step_ms_p50 as read  # noqa: F401

LAYER, UNIT, MOVES, SOURCE = "serving_engine", "ms", "serve_tokens_per_s", "host_clock"
