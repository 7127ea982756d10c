"""The prefill executables' share of the device's busy time in the traced
sub-window of the DeepSeek-V2 decode cell: what admissions, each a prefill in
the expanded form at 128 heads, take from decode."""
from benchmarks.lib.sink_readers import prefill_share as read  # noqa: F401

LAYER, UNIT, MOVES, SOURCE = "serving_engine", "%", "serve_tokens_per_s", "device_trace"
