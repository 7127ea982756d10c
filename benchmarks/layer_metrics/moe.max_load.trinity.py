"""The most rows one expert received in one layer in one step of a dispatch
(`moe_max_load` of the `serve_step` sink records), mean over the window's
dispatches: 16 slots x 8 choices spread over 128 experts would be 1."""
from benchmarks.lib.sink_readers import mean_field

LAYER, UNIT, MOVES, SOURCE = "model", "rows", "serve_tokens_per_s", "program_counter"


def read(run):
    return mean_field(run, "moe_max_load")
