"""Median time a dispatch in which the decode program had nothing enqueued
(`host_gap_ms` of the `serve_step` sink records), in the decode cell."""
from benchmarks.lib.span_readers import host_gap_ms_p50 as read  # noqa: F401

LAYER, UNIT, MOVES, SOURCE = "serving_engine", "ms", "serve_tokens_per_s", "program_span"
