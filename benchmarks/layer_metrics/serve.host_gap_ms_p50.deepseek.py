"""Median `host_gap_ms` a dispatch in the DeepSeek-V2 decode cell: the time
the decode program had nothing enqueued, prefills of 512 to 3,584 tokens
between two dispatches included; 0.0 for a dispatch enqueued ahead."""
from benchmarks.lib.span_readers import host_gap_ms_p50 as read  # noqa: F401

LAYER, UNIT, MOVES, SOURCE = "serving_engine", "ms", "serve_tokens_per_s", "program_span"
