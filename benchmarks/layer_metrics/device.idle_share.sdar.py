"""The device's idle share of the traced sub-window, in the SDAR diffusion
cell."""
from benchmarks.lib.readers import idle_share as read  # noqa: F401

LAYER, UNIT, MOVES, SOURCE = "device", "%", "serve_tokens_per_s", "device_trace"
