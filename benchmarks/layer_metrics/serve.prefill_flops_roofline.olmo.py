"""The prefills' share of their FLOP roofline in the Olmo-Hybrid decode cell:
the operations the traced prefills need by their REAL prompt lengths
(benchmarks/lib/prefill_flops_hybrid.py) over 197 TFLOP/s, over the prefill
executables' device time. The rung's pad and the chunked scan's extra
products are the program's waste and lower it; compute is the bound."""
from benchmarks.lib.hybrid_readers import prefill_flops_roofline as read  # noqa: F401

LAYER, UNIT, MOVES, SOURCE = "model", "%", "serve_tokens_per_s", "device_trace"
