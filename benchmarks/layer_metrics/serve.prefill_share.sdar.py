"""The prefill executables' share of the device's busy time in the traced
sub-window of the SDAR diffusion cell: what admissions, each a block-causal
prefill of a prompt's whole blocks with no logits, take from the block
steps."""
from benchmarks.lib.sink_readers import prefill_share as read  # noqa: F401

LAYER, UNIT, MOVES, SOURCE = "serving_engine", "%", "serve_tokens_per_s", "device_trace"
