"""Chip-free triage of the decode-loop slowness via compiled-HLO inspection.

Round-3 on-chip datum: generate(batch 16, prompt 128, 64 new
tokens) = 179.8 tok/s total — ~89 ms per decode step for a model whose
per-step roofline (weights + KV cache, one HBM pass) is ~1 ms. The two
structural suspects visible WITHOUT a chip, in the compiled while-loop body:

  1. loop-invariant f32->bf16 weight converts NOT hoisted out of the loop
     (the amp scope casts every matmul input; if XLA fails to LICM them the
     loop re-materializes bf16 copies of all weights every token);
  2. full-size KV-cache copies inside the body (dynamic-update-slice not
     done in place -> each token pays a cache-sized memcpy per layer).

This tool jits the same `generate` the bench calls (tiny config by default so
CPU compile stays fast), grabs the optimized HLO, finds the biggest while
body, and reports: convert ops at weight shapes, copy/DUS ops at cache
shapes, and the body's total op count. Counts > layer-count signal suspect 2;
any weight-shaped convert signals suspect 1.

Usage: python tools/decode_hlo_probe.py [--model tiny|base] [--device cpu]
"""
from __future__ import annotations

import _bootstrap  # noqa: F401

import argparse
import json


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="tiny", choices=("tiny", "base"))
    ap.add_argument("--device", default="cpu", choices=("cpu", "tpu"))
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt", type=int, default=16)
    ap.add_argument("--new", type=int, default=8)
    args = ap.parse_args()

    if args.device == "cpu":
        from paddle_tpu.device.probe import force_cpu_platform

        force_cpu_platform()

    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.models import GPTConfig, GPTForPretraining, gpt_tiny

    cfg = gpt_tiny() if args.model == "tiny" else GPTConfig(
        vocab_size=50304, hidden_size=768, num_layers=12, num_heads=12,
        max_seq_len=1024)
    paddle.seed(0)
    model = GPTForPretraining(cfg)
    model.eval()
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size,
                      (args.batch, args.prompt)).astype(np.int64)

    import jax
    import jax.numpy as jnp

    with paddle.amp.auto_cast(enable=True, dtype="bfloat16"):
        # reach the same cached executable generate() builds internally
        model.generate(paddle.to_tensor(ids), max_new_tokens=args.new,
                       temperature=0)
        jitted = next(iter(model.decode_exec_registry().values()))
        lowered_params = {k: v._data for k, v in model.state_dict(
            include_non_persistable_buffer=True).items()}
        key = jax.random.key(0)
        # run(params, ids, plen, key) — plen traced since the bucket round
        hlo = jitted.lower(lowered_params, ids, jnp.int32(args.prompt),
                           key).compile()
    text = hlo.as_text()

    nh, hd = cfg.num_heads, cfg.hidden_size // cfg.num_heads
    total = args.prompt + args.new
    cache_shape = f"{args.batch},{total},{nh},{hd}"
    # any tensor with >= hidden*hidden elements counts as "weight-sized"
    wmin = cfg.hidden_size * cfg.hidden_size

    from paddle_tpu.utils import hlo_inspect as hi

    body_lines = hi.while_body_lines(text)
    bpe = {"bf16": 2, "f16": 2, "f32": 4}
    weight_converts, cache_converts = [], []
    convert_bytes = 0
    for line in body_lines:
        if "convert(" in line:
            dt, n = hi.shape_elems(line)
            if n >= wmin:
                convert_bytes += n * bpe.get(dt, 4)
                (cache_converts if cache_shape in line
                 else weight_converts).append(line.strip()[:120])
    cache_copies = hi.copies_of_shape(body_lines, cache_shape)

    print(json.dumps({
        "body_tagged_ops": len(body_lines),
        "weight_sized_converts_per_step": len(weight_converts),
        "cache_shaped_converts_per_step": len(cache_converts),
        "cache_shaped_copies_per_step": len(cache_copies),
        "dynamic_update_slices_per_step":
            hi.count_dynamic_update_slices(body_lines),
        "big_convert_mb_per_step": round(convert_bytes / 1e6, 1),
        "examples": (weight_converts + cache_converts
                     + [c[:120] for c in cache_copies])[:6],
    }))


if __name__ == "__main__":
    main()
