"""Median `host_gap_ms` a dispatch in the SDAR diffusion cell: the time the
block-step program had nothing enqueued, prefills of 128 to 2,048 tokens
between two dispatches included; 0.0 for a dispatch enqueued ahead."""
from benchmarks.lib.span_readers import host_gap_ms_p50 as read  # noqa: F401

LAYER, UNIT, MOVES, SOURCE = "serving_engine", "ms", "serve_tokens_per_s", "program_span"
