"""Readers for the cells of an `sdar_moe` configuration, which generates by
diffusion over blocks: the forwards a block took, the tokens a forward gave,
the experts a forward touched and the block-step program's share of its
bytes roofline. A program whose records lack a field, or a run that was not
traced, gives a reader nothing, and the line leaves the metric out."""
from __future__ import annotations

from benchmarks.lib import peaks
from benchmarks.lib.decode_bytes_sdar import forward_bytes
from benchmarks.lib.sink_readers import _traced_steps, mean_field
from benchmarks.lib.span_readers import _window_steps

BLOCK_EXECUTABLE = "jit_block_chunk"    # the engine's block-step program


def _is_sdar(run) -> bool:
    return "block_length" in run.get("config", {})


def _ratio(run, over: str, under: str):
    """Sum of one field over the sum of another, over the window's
    dispatches that carry both."""
    if not _is_sdar(run):
        return None
    records = [r for r in _window_steps(run)
               if r.get(over) is not None and r.get(under) is not None]
    below = sum(r[under] for r in records)
    return sum(r[over] for r in records) / below if below else None


def forwards_per_block(run):
    """Slot-forwards of live slots over blocks committed (`forwards`,
    `blocks_committed` of the `serve_step` records) in the window."""
    return _ratio(run, "forwards", "blocks_committed")


def tokens_per_forward(run):
    """Output tokens handed to requests over slot-forwards of live slots
    (`tokens`, `forwards` of the `serve_step` records) in the window."""
    return _ratio(run, "tokens", "forwards")


def experts_touched_held(run):
    """Share of the routed experts held here that received at least one row
    (`moe_touched_held`), mean over a dispatch's forwards and the layers,
    mean over the window's dispatches, in percent."""
    if not _is_sdar(run):
        return None
    return mean_field(run, "moe_touched_held",
                      100.0 / int(run["config"]["num_experts"]))


def max_load(run):
    if not _is_sdar(run):
        return None
    return mean_field(run, "moe_max_load")


def decode_roofline(run):
    """The least time the chip needs to read what the traced forwards must
    read (lib/decode_bytes_sdar.py, from the configuration's shapes, each
    dispatch's `contexts` and `moe_touched_held`) at the peak HBM rate, over
    the block-step executable's device time in the traced sub-window, in
    percent. A dispatch's contexts are those after its last forward, so its
    earlier forwards are counted with up to 8 positions a slot too many
    (under 0.1% of the bytes)."""
    steps = _traced_steps(run)
    if not _is_sdar(run) or not steps or any(
            "moe_touched_held" not in r or "contexts" not in r
            for r, _ in steps):
        return None
    device_s = sum(s for name, s in run["trace"]["modules"].items()
                   if name == BLOCK_EXECUTABLE)
    if not device_s:
        return None
    need = sum(share * r["steps_per_dispatch"] * forward_bytes(
        run["config"], r["contexts"], r["moe_touched_held"])["total"]
        for r, share in steps)
    least = need / peaks.peak(run["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * least / (device_s / run["chips"])
