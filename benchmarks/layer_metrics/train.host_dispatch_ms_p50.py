"""Median host time of one `engine.step` span (enter to the return of the
enqueue) over the steps that did not compile, from the program's span ring."""
from benchmarks.lib.span_readers import host_dispatch_ms_p50 as read  # noqa: F401

LAYER, UNIT, MOVES, SOURCE = "train_engine", "ms", "train_tokens_per_s", "program_span"
