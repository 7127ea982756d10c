"""The decode step's share of its bytes roofline in the Trinity decode cell:
the bytes the traced steps must read (weights used, experts touched, cache
rows; benchmarks/lib/decode_bytes.py) over 819 GB/s, over the decode
executable's device time. The share of the whole step that bounds any later
claim on `serve_tokens_per_s` in this cell; memory is the bound (16 rows a
step against 5.7 GB)."""
from benchmarks.lib.sink_readers import decode_bytes_roofline as read  # noqa: F401

LAYER, UNIT, MOVES, SOURCE = "model", "%", "serve_tokens_per_s", "device_trace"
