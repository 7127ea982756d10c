"""Median host time of one forward in the SDAR diffusion cell: the time of
`eng.step()` (admissions and their prefills included) over the 10 block
forwards a dispatch fuses."""
from benchmarks.lib.readers import serve_step_ms_p50 as read  # noqa: F401

LAYER, UNIT, MOVES, SOURCE = "serving_engine", "ms", "serve_tokens_per_s", "host_clock"
