"""Forwards a committed block took in the SDAR diffusion cell: slot-forwards
of live slots over blocks committed (`forwards` / `blocks_committed` of the
window's `serve_step` sink records). 5.0 under the static schedule at 4
steps a block of 4 (four that unmask one position each, one that commits);
what a commit fused with the next block's first forward, or a dynamic
schedule, would lower."""
from benchmarks.lib.sdar_readers import forwards_per_block as read  # noqa: F401

LAYER, UNIT, MOVES, SOURCE = "serving_engine", "forwards", "serve_tokens_per_s", "program_counter"
