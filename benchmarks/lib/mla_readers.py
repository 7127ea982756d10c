"""Readers for the cells of a `deepseek_v2` configuration: the experts held
here that a step touched, and the two shares of a roofline. A program whose
records lack a field, or a run that was not traced, gives a reader nothing,
and the line leaves the metric out."""
from __future__ import annotations

from benchmarks.lib import peaks
from benchmarks.lib.decode_bytes_mla import (decode_step_bytes,
                                             decode_step_flops)
from benchmarks.lib.prefill_flops_mla import prefill_flops
from benchmarks.lib.sink_readers import (DECODE_EXECUTABLE, _traced_steps,
                                         mean_field)


def _is_mla(run) -> bool:
    return "kv_lora_rank" in run.get("config", {})


def experts_touched_held(run):
    """Share of the routed experts HELD HERE that received at least one row,
    mean over a dispatch's steps and the expert layers (`moe_touched_held`
    of the `serve_step` records), mean over the window's dispatches, in
    percent."""
    if not _is_mla(run):
        return None
    return mean_field(run, "moe_touched_held",
                      100.0 / int(run["config"]["n_routed_experts"]))


def decode_roofline(run):
    """The least time the chip needs for the traced decode steps over the
    decode executable's device time in the traced sub-window, in percent.
    The least time is the larger of two bounds: what the steps must read
    (lib/decode_bytes_mla.py, from the configuration's shapes, each
    dispatch's `contexts` and `moe_touched_held`) at the peak HBM rate, and
    what they must compute at the peak bf16 rate; memory is the bound at the
    cell's sizes, and the share cannot pass 100% where the core turns
    compute-bound. A dispatch's contexts are those after its last step, so
    its earlier steps are counted with up to 7 positions a slot too many
    (under 0.1%)."""
    steps = _traced_steps(run)
    if not _is_mla(run) or not steps or any(
            "moe_touched_held" not in r or "contexts" not in r
            for r, _ in steps):
        return None
    device_s = sum(s for name, s in run["trace"]["modules"].items()
                   if name == DECODE_EXECUTABLE)
    if not device_s:
        return None
    peak = peaks.peak(run["device_kind"])
    least = 0.0
    for r, share in steps:
        n = share * r["steps_per_dispatch"]
        nbytes = decode_step_bytes(run["config"], r["contexts"],
                                   r["moe_touched_held"])["total"]
        flops = decode_step_flops(run["config"], r["contexts"])["total"]
        least += n * max(nbytes / peak["hbm_bytes_per_s"],
                         flops / peak["bf16_flops_per_s"])
    return 100.0 * least / (device_s / run["chips"])


def prefill_flops_roofline(run):
    """The least time the chip needs for the operations of the prefills
    that ran inside the traced sub-window (lib/prefill_flops_mla.py, by
    each prompt's REAL length) at the peak bf16 rate, over the prefill
    executables' device time there, in percent. `prefills` is the runner's:
    (admitted, first token, prompt length) a request on the window's clock;
    a prefill is on the device between the two, and one that the
    sub-window's edge cuts counts for the share of it inside (as
    lib/hybrid_readers.py, whose note on the host's intervals holds here)."""
    trace = run.get("trace")
    if not _is_mla(run) or not trace or not trace.get("window_s") \
            or "window" not in run or not run.get("prefills"):
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
