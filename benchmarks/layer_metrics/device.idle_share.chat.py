"""The device's idle share of the traced sub-window, in the chat cell."""
from benchmarks.lib.readers import idle_share as read  # noqa: F401

LAYER, UNIT, MOVES, SOURCE = "device", "%", "tpot_p95_ms", "device_trace"
