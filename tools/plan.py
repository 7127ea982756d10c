"""Auto-parallel topology planner CLI.

    python tools/plan.py --model gpt --n-devices 8 --batch 8 --seq 128
    python tools/plan.py --model mlp --hidden 2048 --n-devices 8

AOT-compiles the fused train step for every legal hybrid topology on a
virtual CPU mesh of --n-devices (nothing executes; works without a TPU) and
prints a ranked JSON table of the planner's cost-model readout
(auto_parallel/planner.py — reference planner.py + cost_model.py analogue).
"""
from __future__ import annotations

import _bootstrap  # noqa: F401  (checkout-hermetic sys.path, tools/_bootstrap.py)

import argparse
import json
import os


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--model", choices=["gpt", "mlp"], default="gpt")
    ap.add_argument("--n-devices", type=int, default=8)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--hidden", type=int, default=1024)
    ap.add_argument("--memory-budget", type=int, default=None,
                    help="per-device bytes; infeasible topologies rejected")
    args = ap.parse_args()

    # CPU planning is the norm (AOT compile only, nothing executes).
    # PADDLE_TPU_PLAN_DEVICE=native skips the forcing to plan on real chips.
    if os.environ.get("PADDLE_TPU_PLAN_DEVICE") != "native":
        from paddle_tpu.device.probe import force_cpu_platform

        force_cpu_platform(virtual_devices=args.n_devices)
    import jax  # noqa: F401  (backend initialized above)
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.distributed.auto_parallel.planner import plan

    paddle.seed(0)
    rng = np.random.RandomState(0)
    if args.model == "gpt":
        from paddle_tpu.models import GPTConfig, GPTForPretraining

        cfg = GPTConfig(vocab_size=1024, hidden_size=args.hidden // 4,
                        num_layers=2, num_heads=4, max_seq_len=args.seq)

        def mf():
            paddle.seed(0)
            return GPTForPretraining(cfg)

        ids = rng.randint(0, cfg.vocab_size,
                          (args.batch, args.seq)).astype(np.int64)
        batch = [paddle.to_tensor(ids),
                 paddle.to_tensor(np.roll(ids, -1, 1))]
        loss_fn = None
    else:
        from paddle_tpu.distributed.meta_parallel import (
            ColumnParallelLinear, RowParallelLinear)
        import paddle_tpu.nn as nn

        class TPNet(nn.Layer):
            def __init__(self):
                super().__init__()
                self.up = ColumnParallelLinear(args.hidden, 4 * args.hidden,
                                               gather_output=False)
                self.down = RowParallelLinear(4 * args.hidden, args.hidden,
                                              input_is_parallel=True)

            def forward(self, x):
                return self.down(self.up(x))

        def mf():
            paddle.seed(0)
            return TPNet()

        x = rng.randn(args.batch, args.hidden).astype(np.float32)
        batch = [paddle.to_tensor(x), paddle.to_tensor(x)]
        loss_fn = paddle.nn.MSELoss()

    def of(m):
        return paddle.optimizer.AdamW(learning_rate=1e-3,
                                      parameters=m.parameters())

    best, results = plan(mf, of, batch, n_devices=args.n_devices,
                         loss_fn=loss_fn, memory_budget=args.memory_budget)
    print(json.dumps({
        "best": best,
        "table": [{
            "config": r.config, "feasible": r.feasible,
            "score": r.score if r.score != float("inf") else None,
            "hbm_bytes": r.hbm_bytes, "ici_bytes": r.ici_bytes,
            "peak_bytes": r.peak_bytes,
            **({"reason": r.detail["reason"]} if "reason" in r.detail else {}),
        } for r in results],
    }, indent=2))


if __name__ == "__main__":
    main()
