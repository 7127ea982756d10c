"""Flagship benchmark: GPT-2 124M causal-LM training throughput on the TPU.

One configuration, one process, the TPU or a non-zero exit. Prints ONE JSON
line: {"metric": ..., "value": N, "unit": ..., "vs_baseline": null, "extra":
{...}} whose extra names the device the number came from (platform,
device_kind, device count). A failure of any kind — no TPU, a kernel that
does not compile, an exception mid-run — ends the process with a traceback
and a non-zero exit code; nothing is retried on another path and nothing is
recorded here (the driver keeps the ledger).
"""
from __future__ import annotations

import json
import sys
import time

import numpy as np


def bench_config():
    """The benchmark model: GPT-2 124M at its published width and depth.
    Single source of truth — main() and chip_smoke.py run it, and
    tests/test_bench_compile_gate.py AOT-lowers the same config for the TPU
    target on every chip-less run, so they cannot drift. Returns (cfg,
    per-chip batch, seq, steps, warmup)."""
    from paddle_tpu.models import GPTConfig

    return (GPTConfig(vocab_size=50304, hidden_size=768, num_layers=12,
                      num_heads=12, max_seq_len=1024), 8, 1024, 20, 3)


def _window_plan(steps, n_windows):
    """Split the timed region into n window lengths (first windows take the
    remainder) so per-window throughput exposes run variance."""
    n = max(1, min(n_windows, steps))
    base, rem = divmod(steps, n)
    return [base + (1 if i < rem else 0) for i in range(n)]


def _window_stats(window_dts, batch, seq):
    """Per-window tokens/s + median + relative spread: the variance field
    that makes 1-2%-margin pair comparisons decidable.
    window_dts: list of (wall_seconds, steps_in_window)."""
    import statistics

    rates = [n * batch * seq / d for d, n in window_dts if n and d > 0]
    if not rates:
        return None
    med = statistics.median(rates)
    return {
        "windows": len(rates),
        "window_tokens_per_sec": [round(r, 1) for r in rates],
        "median_tokens_per_sec": round(med, 1),
        # (max-min)/median across windows; None needs >= 2 windows. A pair
        # of configs closer than each other's rel_spread is NOT decidable
        # from single runs — tools/plan_validate.py applies the same rule
        "rel_spread": (round((max(rates) - min(rates)) / med, 4)
                       if len(rates) > 1 else None),
    }


def main() -> int:
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"bench: JAX found no TPU (platform={devs[0].platform}); a "
              f"device metric is only measured on the device",
              file=sys.stderr)
        return 1

    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed import fleet
    from paddle_tpu.models import GPTForPretraining
    from paddle_tpu.observability import (
        peak_flops_per_sec, transformer_flops_per_token)

    n_dev = len(devs)
    peak = peak_flops_per_sec(devs[0].device_kind)  # unknown kind: raises
    cfg, batch, seq, steps, warmup = bench_config()
    batch *= n_dev  # per-chip batch; the batch dim shards over dp = n_dev

    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int64)
    paddle.seed(0)
    strategy = dist.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": n_dev, "mp_degree": 1}
    fleet.init(is_collective=True, strategy=strategy)
    model = GPTForPretraining(cfg)
    n_params = sum(p.size for p in model.parameters())
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters(),
                                 weight_decay=0.01)
    engine = fleet.distributed_engine(model, opt)
    t_ids = paddle.to_tensor(ids)
    t_labels = paddle.to_tensor(np.roll(ids, -1, 1))

    # the timed region runs as 3 windows, each ended by a D2H fetch of the
    # loss, so the row carries a median and a spread instead of one sample
    window_dts = []
    # bf16 matmuls on the MXU (params stay f32, optimizer math f32)
    with paddle.amp.auto_cast(dtype="bfloat16"):
        for _ in range(warmup):
            loss = engine.step(t_ids, t_labels)
        float(loss.item())  # drains the dispatch queue before timing
        t0 = time.perf_counter()
        for wn in _window_plan(steps, 3):
            tw = time.perf_counter()
            for _ in range(wn):
                loss = engine.step(t_ids, t_labels)
            final_loss = float(loss.item())  # sync ends the window
            window_dts.append((time.perf_counter() - tw, wn))
        dt = time.perf_counter() - t0

    tokens_per_sec_chip = steps * batch * seq / dt / n_dev
    # model-FLOPs accounting (PaLM appendix B): 6*N parameter FLOPs + 12*L*h*s
    # attention-matmul FLOPs per token, counting FULL attention matmuls (the
    # causal flash kernel skips about half those blocks)
    flops_per_tok = transformer_flops_per_token(
        n_params, cfg.num_layers, cfg.hidden_size, seq)
    print(json.dumps({
        "metric": "gpt_pretrain_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec_chip, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": None,
        "extra": {
            "platform": devs[0].platform,
            "device_kind": devs[0].device_kind, "devices": n_dev,
            "model_params": int(n_params),
            "hidden": cfg.hidden_size, "layers": cfg.num_layers,
            "batch": batch, "seq": seq, "steps": steps,
            "final_loss": round(final_loss, 4),
            "timing": _window_stats(window_dts, batch, seq),
            "mfu": round(flops_per_tok * tokens_per_sec_chip / peak, 4),
            "peak_bytes_in_use": [
                (d.memory_stats() or {}).get("peak_bytes_in_use")
                for d in devs],
        },
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
