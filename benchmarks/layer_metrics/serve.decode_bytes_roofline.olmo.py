"""The decode step's share of its bytes roofline in the Olmo-Hybrid decode
cell: the bytes the traced steps must move (every matrix once, the head, the
full layers' rows, each state read and written;
benchmarks/lib/decode_bytes_hybrid.py) over 819 GB/s, over the decode
executable's device time. The share of the whole step that bounds any later
claim on `serve_tokens_per_s` in this cell; memory is the bound (16 rows a
step against 8 to 9 GB)."""
from benchmarks.lib.hybrid_readers import decode_bytes_roofline as read  # noqa: F401

LAYER, UNIT, MOVES, SOURCE = "model", "%", "serve_tokens_per_s", "device_trace"
