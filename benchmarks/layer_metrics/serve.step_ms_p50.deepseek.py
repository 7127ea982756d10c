"""Median host time of one decode step in the DeepSeek-V2 decode cell: the
time of `eng.step()` (admissions and their prefills included) over the 8
steps a dispatch fuses."""
from benchmarks.lib.readers import serve_step_ms_p50 as read  # noqa: F401

LAYER, UNIT, MOVES, SOURCE = "serving_engine", "ms", "serve_tokens_per_s", "host_clock"
