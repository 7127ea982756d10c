"""Readers over the engine's `serve_step` sink records (one a decode
dispatch) that the cells of one configuration family share. A program whose
records lack a field, as the parent of the PR that added the field has not,
gives the reader nothing, and the line leaves the metric out."""
from __future__ import annotations

from benchmarks.lib import peaks
from benchmarks.lib.decode_bytes import decode_step_bytes
from benchmarks.lib.span_readers import _window_steps


def mean_field(run, field: str, scale: float = 1.0):
    """Mean of one field over the window's dispatches."""
    values = [r[field] for r in _window_steps(run)
              if r.get(field) is not None]
    if not values:
        return None
    return scale * sum(values) / len(values)


def occupancy(run):
    """Mean share of slots that emitted a token, in percent."""
    return mean_field(run, "occupancy", 100.0)


def prefill_share(run):
    """The prefill executables' share of the device's busy time in the
    traced sub-window, in percent (`XLA Modules` by executable name)."""
    trace = run.get("trace")
    if not trace or not trace["busy_s"]:
        return None
    prefill = sum(s for name, s in trace["modules"].items()
                  if "prefill" in name)
    return 100.0 * prefill / (trace["busy_s"] * run["chips"])


def _traced_steps(run):
    """The window's dispatches that ran inside the traced sub-window (its
    last `window_s` seconds), each with the share of it that did: a dispatch
    is on the device from the start of `serve.decode.dispatch` to the end of
    `serve.decode.fetch`, and its record is written `emit` later."""
    trace = run.get("trace")
    if not trace or not trace.get("window_s") or "window" not in run:
        return []
    end = run["window"][1] + run["wall_minus_perf"]
    start = end - trace["window_s"]
    out = []
    for r in _window_steps(run):
        spans = r.get("spans_ms") or {}
        if "decode_fetch" not in spans:
            continue
        b = r["ts"] - spans.get("emit", 0.0) / 1e3
        a = b - (spans["decode_dispatch"] + spans["decode_fetch"]) / 1e3
        lap = min(b, end) - max(a, start)
        if lap > 0 and b > a:
            out.append((r, lap / (b - a)))
    return out


DECODE_EXECUTABLE = "jit_step_chunk"    # the engine's decode program


def decode_bytes_roofline(run):
    """The least time the chip needs to read what the traced decode steps
    must read (lib/decode_bytes.py, from the configuration's shapes, each
    dispatch's `contexts` and `moe_touched`) at the peak HBM rate, over the
    decode executable's device time in the traced sub-window, in percent.
    A dispatch's contexts are those after its last step, so its earlier
    steps are counted with up to 7 positions a slot too many (under 0.1%)."""
    steps = _traced_steps(run)
    if not steps or any("moe_touched" not in r or "contexts" not in r
                        for r, _ in steps):
        return None
    device_s = sum(s for name, s in run["trace"]["modules"].items()
                   if name == DECODE_EXECUTABLE)
    if not device_s:
        return None
    need = sum(share * r["steps_per_dispatch"] * decode_step_bytes(
        run["config"], r["contexts"], r["moe_touched"])["total"]
        for r, share in steps)
    least = need / peaks.peak(run["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * least / (device_s / run["chips"])
