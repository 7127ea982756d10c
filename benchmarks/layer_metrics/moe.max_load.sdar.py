"""The most rows one expert received in one layer in one forward of a
dispatch (`moe_max_load` of the `serve_step` sink records), mean over the
window's dispatches: 2,048 rows spread evenly over 128 experts would be 16."""
from benchmarks.lib.sdar_readers import max_load as read  # noqa: F401

LAYER, UNIT, MOVES, SOURCE = "model", "rows", "serve_tokens_per_s", "program_counter"
