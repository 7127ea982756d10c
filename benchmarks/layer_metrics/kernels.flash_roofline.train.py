"""The flash kernels' share of their roofline: the least time one chip needs
for causal attention's required work of a step, forward and backward (the
larger of operations over peak FLOP/s and bytes over peak bytes/s, from
shapes: benchmarks/lib/peaks.py), over the Mosaic calls' device time a step.
Steps in the traced window = its length times the steps/s of the run."""
from benchmarks.lib import peaks

LAYER, UNIT, MOVES, SOURCE = "kernels", "%", "train_tokens_per_s", "device_trace"


def read(run):
    trace = run.get("trace")
    if not trace or "tokens_per_s_per_chip" not in run:
        return None
    mosaic = trace["ops"].get("tpu_custom_call")
    if not mosaic:
        return None
    b, s = run["batch_per_chip"], run["seq"]
    heads, hd = run["num_heads"], run["hidden"] // run["num_heads"]
    least, _ = peaks.roofline_seconds(
        run["num_layers"] * peaks.causal_attention_flops(b, heads, s, hd),
        run["num_layers"] * peaks.causal_attention_bytes(b, heads, s, hd),
        run["device_kind"])
    steps = trace["window_s"] * run["tokens_per_s_per_chip"] / (b * s)
    return 100.0 * least * steps / (mosaic / run["chips"])
