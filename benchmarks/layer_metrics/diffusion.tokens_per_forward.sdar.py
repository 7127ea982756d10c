"""Output tokens a slot-forward gave in the SDAR diffusion cell (`tokens` /
`forwards` of the window's `serve_step` sink records): 0.8 where a block
of 4 tokens takes 5 forwards."""
from benchmarks.lib.sdar_readers import tokens_per_forward as read  # noqa: F401

LAYER, UNIT, MOVES, SOURCE = "serving_engine", "tokens", "serve_tokens_per_s", "program_counter"
