"""Outermost `jit.trace` events of the span ring inside the window: a
retrace of ANY jit, where `train.recompiles` sees the step's executable
alone; 0 expected."""
from benchmarks.lib.startup_readers import window_jit_traces as read  # noqa: F401

LAYER, UNIT, MOVES, SOURCE = "train_engine", "count", "train_tokens_per_s", "program_counter"
