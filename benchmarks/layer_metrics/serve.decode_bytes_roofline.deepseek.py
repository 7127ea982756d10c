"""The decode step's share of its roofline in the DeepSeek-V2 decode cell:
the bytes the traced steps must read (every matrix once, `W_kvb` once, the
experts held that were touched, the sliced head, the router, 1,152 B a held
position a layer; benchmarks/lib/decode_bytes_mla.py) over 819 GB/s, or the
step's operations over 197 TFLOP/s where that is the larger, over the decode
executable's device time. The share of the whole step that bounds any later
claim on `serve_tokens_per_s` in this cell; memory is the bound (64 rows a
step against about 10.6 GB)."""
from benchmarks.lib.mla_readers import decode_roofline as read  # noqa: F401

LAYER, UNIT, MOVES, SOURCE = "model", "%", "serve_tokens_per_s", "device_trace"
