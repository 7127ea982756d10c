"""Readers for the cells of an `olmo_hybrid` configuration: the two shares
of a roofline. A program whose records lack a field, or a run that was not
traced, gives a reader nothing, and the line leaves the metric out."""
from __future__ import annotations

from benchmarks.lib import peaks
from benchmarks.lib.decode_bytes_hybrid import decode_step_bytes
from benchmarks.lib.prefill_flops_hybrid import prefill_flops
from benchmarks.lib.sink_readers import DECODE_EXECUTABLE, _traced_steps


def decode_bytes_roofline(run):
    """The least time the chip needs to move what the traced decode steps
    must move (lib/decode_bytes_hybrid.py, from the configuration's shapes
    and each dispatch's `contexts`) at the peak HBM rate, over the decode
    executable's device time in the traced sub-window, in percent. A
    dispatch's contexts are those after its last step, so its earlier steps
    are counted with up to 7 positions a slot too many (under 0.1%)."""
    steps = _traced_steps(run)
    if not steps or any("contexts" not in r for r, _ in steps) \
            or "linear_num_key_heads" not in run.get("config", {}):
        return None
    device_s = sum(s for name, s in run["trace"]["modules"].items()
                   if name == DECODE_EXECUTABLE)
    if not device_s:
        return None
    need = sum(share * r["steps_per_dispatch"] * decode_step_bytes(
        run["config"], r["contexts"])["total"] for r, share in steps)
    least = need / peaks.peak(run["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * least / (device_s / run["chips"])


def prefill_flops_roofline(run):
    """The least time the chip needs for the operations of the prefills
    that ran inside the traced sub-window (lib/prefill_flops_hybrid.py, by
    each prompt's REAL length) at the peak bf16 rate, over the prefill
    executables' device time there, in percent. `prefills` is the runner's:
    (admitted, first token, prompt length) a request on the window's clock;
    a prefill is on the device between the two (the engine admits only
    with no decode chunk in flight, so none waits behind one: the host's
    intervals in the sub-window sum to 3 to 5% more than the trace's
    prefill time, PERF.md section 6, PR 33), and one that the sub-window's
    edge cuts counts for the share of it inside. The reduced trace keeps
    seconds a module and no events (lib/trace.py), so the prefills cannot
    be counted from it."""
    trace = run.get("trace")
    if not trace or not trace.get("window_s") or "window" not in run \
            or not run.get("prefills") \
            or "linear_num_key_heads" not in run.get("config", {}):
        return None
    end = run["window"][1]
    start = end - trace["window_s"]
    device_s = sum(s for name, s in trace["modules"].items()
                   if "prefill" in name)
    if not device_s:
        return None
    need = 0.0
    for a, b, length in run["prefills"]:
        lap = min(b, end) - max(a, start)
        if lap > 0 and b > a:
            need += lap / (b - a) * prefill_flops(run["config"],
                                                  length)["total"]
    least = need / peaks.peak(run["device_kind"])["bf16_flops_per_s"]
    return 100.0 * least / (device_s / run["chips"])
