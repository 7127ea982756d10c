"""Share of the 128 routed experts that received at least one row, mean
over a dispatch's forwards and the six layers (`moe_touched_held` of the
`serve_step` sink records), mean over the window's dispatches: 64 slots x 4
positions x 8 choices put 2,048 rows on 128 experts, so every expert's
weights are read at every forward."""
from benchmarks.lib.sdar_readers import experts_touched_held as read  # noqa: F401

LAYER, UNIT, MOVES, SOURCE = "model", "%", "serve_tokens_per_s", "program_counter"
