"""Mean share of the 64 slots that were live in a forward, over the
`serve_step` sink records of the window, in the SDAR diffusion cell."""
from benchmarks.lib.sink_readers import occupancy as read  # noqa: F401

LAYER, UNIT, MOVES, SOURCE = "serving_engine", "%", "serve_tokens_per_s", "program_counter"
