"""Outermost `jit.trace` events of the span ring inside the chat cell's
window: a retrace of ANY jit (an admission's `jnp.asarray` of a new shape, an
eager rule) costs milliseconds a dispatch; 0 expected."""
from benchmarks.lib.startup_readers import window_jit_traces as read  # noqa: F401

LAYER, UNIT, MOVES, SOURCE = "serving_engine", "count", "tpot_p95_ms", "program_counter"
