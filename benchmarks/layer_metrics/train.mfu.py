"""Model FLOP/s utilization: tokens/s/chip times the operations a token
REQUIRES (6*N + 6*L*h*s, causal half of the attention matrix; bench.py
counts 12*L*h*s) over the chip's bf16 peak."""
from benchmarks.lib import peaks

LAYER, UNIT, MOVES, SOURCE = "model", "%", "train_tokens_per_s", "host_clock"


def read(run):
    if "tokens_per_s_per_chip" not in run:
        return None
    flops = peaks.train_flops_per_token(run["n_params"], run["num_layers"],
                                        run["hidden"], run["seq"])
    peak = peaks.peak(run["device_kind"])["bf16_flops_per_s"]
    return 100.0 * run["tokens_per_s_per_chip"] * flops / peak
