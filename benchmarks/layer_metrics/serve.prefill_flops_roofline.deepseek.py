"""The prefills' share of their FLOP roofline in the DeepSeek-V2 decode cell:
the operations the traced prefills need by their REAL prompt lengths in the
expanded form (benchmarks/lib/prefill_flops_mla.py: the matrices a token
passes through, 1.5 routed experts a token a layer here, causal attention at
128 heads of 192 + 128) over 197 TFLOP/s, over the prefill executables'
device time. The rung's pad and the grouped matmul's rows of experts held
elsewhere are the program's waste and lower it; compute is the bound."""
from benchmarks.lib.mla_readers import prefill_flops_roofline as read  # noqa: F401

LAYER, UNIT, MOVES, SOURCE = "model", "%", "serve_tokens_per_s", "device_trace"
