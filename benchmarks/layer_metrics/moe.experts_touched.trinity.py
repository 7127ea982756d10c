"""Share of the 128 routed experts that received at least one row, mean over
a dispatch's steps and the expert layers (`moe_touched` of the `serve_step`
sink records), mean over the window's dispatches. What sets the expert
weights a decode step must read."""
from benchmarks.lib.sink_readers import mean_field

LAYER, UNIT, MOVES, SOURCE = "model", "%", "serve_tokens_per_s", "program_counter"


def read(run):
    experts = run.get("config", {}).get("num_experts")
    if not experts:
        return None
    return mean_field(run, "moe_touched", 100.0 / experts)
