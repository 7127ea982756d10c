"""Share of the 40 routed experts HELD HERE that received at least one row,
mean over a dispatch's steps and the expert layers (`moe_touched_held` of
the `serve_step` sink records), mean over the window's dispatches. What sets
the expert weights a decode step must read on this chip; the router scores
all 160 and rows for the 120 held elsewhere are computed by nobody."""
from benchmarks.lib.mla_readers import experts_touched_held as read  # noqa: F401

LAYER, UNIT, MOVES, SOURCE = "model", "%", "serve_tokens_per_s", "program_counter"
