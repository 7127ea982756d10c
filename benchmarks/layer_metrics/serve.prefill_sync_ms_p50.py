"""Median time the host blocks on a prefill's first token (the span
`serve.prefill.sync`, from the `serve_step` sink records)."""
from benchmarks.lib.span_readers import prefill_sync_ms_p50 as read  # noqa: F401

LAYER, UNIT, MOVES, SOURCE = "serving_engine", "ms", "ttft_p95_ms", "program_span"
