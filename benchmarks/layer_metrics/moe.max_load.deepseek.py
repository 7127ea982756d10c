"""The most rows one expert of all 160 received in one layer in one step of
a dispatch (`moe_max_load` of the `serve_step` sink records), mean over the
window's dispatches: 64 slots x 6 choices spread over 160 experts would be
2.4."""
from benchmarks.lib.sink_readers import mean_field

LAYER, UNIT, MOVES, SOURCE = "model", "rows", "serve_tokens_per_s", "program_counter"


def read(run):
    if "kv_lora_rank" not in run.get("config", {}):
        return None
    return mean_field(run, "moe_max_load")
