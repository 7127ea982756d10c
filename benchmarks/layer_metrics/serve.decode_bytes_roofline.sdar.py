"""The block-step program's share of its bytes roofline in the SDAR
diffusion cell: the bytes the traced forwards must read (every attention
matrix, the float32 router and the head once a forward, the experts touched,
2,048 B a held position a layer; benchmarks/lib/decode_bytes_sdar.py) over
819 GB/s, over the block-step executable's device time. The share of the
whole step that bounds any later claim on `serve_tokens_per_s` in this cell;
it counts a forward as a forward, so fewer forwards a block show in the
`diffusion.*` metrics and not here."""
from benchmarks.lib.sdar_readers import decode_roofline as read  # noqa: F401

LAYER, UNIT, MOVES, SOURCE = "model", "%", "serve_tokens_per_s", "device_trace"
