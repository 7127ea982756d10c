"""Device time of the Mosaic calls (the flash-attention kernels) over the
device's busy time, both from the trace of the same sub-window."""
LAYER, UNIT, MOVES, SOURCE = "kernels", "%", "train_tokens_per_s", "device_trace"


def read(run):
    trace = run.get("trace")
    if not trace or not trace["busy_s"]:
        return None
    mosaic = trace["ops"].get("tpu_custom_call")
    if mosaic is None:
        return None
    return 100.0 * mosaic / (trace["busy_s"] * run["chips"])
