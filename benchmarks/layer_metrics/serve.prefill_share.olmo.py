"""The prefill executables' share of the device's busy time in the traced
sub-window of the Olmo-Hybrid decode cell: what admissions, each a chunked
delta-rule scan over a rung, take from decode."""
from benchmarks.lib.sink_readers import prefill_share as read  # noqa: F401

LAYER, UNIT, MOVES, SOURCE = "serving_engine", "%", "serve_tokens_per_s", "device_trace"
