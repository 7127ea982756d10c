"""Outermost `jit.trace` events of the span ring inside the window of a
decode cell: a retrace of ANY jit, where `no_compile_after_set_up` sees the
registry's executables alone; 0 expected."""
from benchmarks.lib.startup_readers import window_jit_traces as read  # noqa: F401

LAYER, UNIT, MOVES, SOURCE = "serving_engine", "count", "serve_tokens_per_s", "program_counter"
