"""Decompose KV-cache decode time: prefill+dispatch vs per-token scan cost.

Round-3 on-chip datum: generate(batch 16, prompt 128, 64 new, greedy) ran at
179.8 total tokens/s — ~89 ms per decode step for a 124M-param model whose
weights fit one HBM pass in <1 ms. This probe times max_new_tokens in
{1, 8, 64, 128} at the bench config; the slope of time vs K is the true
per-token cost, the intercept is prefill + dispatch + D2H. A big intercept
says dispatch; a big slope says the scan step itself is slow (e.g.
cache update not in-place, or the per-step LM head dominating).

Usage (live TPU): python tools/decode_probe.py [--batch 16] [--prompt 128]
One JSON line per K: {"k", "total_s", "tokens_per_s"}; then a summary line
{"per_token_ms", "intercept_s"} from a least-squares fit.

--engine runs the same decomposition against the serving engine's
single-token decode step (paddle_tpu/serving): batch requests fill batch
slots, the slope is the per-decode-step cost of the slot-cache program, the
intercept is bucketed prefill + dispatch. Comparable to the round-3 legacy
datum (179.8 tok/s at batch 16 / prompt 128 / 64 new, greedy on-chip).
--steps-per-dispatch defaults to 1 here so the fit measures the raw step;
raise it to measure the fused dispatch the engine uses in production.
"""
from __future__ import annotations

import _bootstrap  # noqa: F401  (checkout-hermetic sys.path, tools/_bootstrap.py)

import argparse
import json
import time

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--prompt", type=int, default=128)
    ap.add_argument("--ks", default="1,8,64,128")
    ap.add_argument("--engine", action="store_true",
                    help="probe the serving engine's decode step instead of "
                         "legacy generate()")
    ap.add_argument("--steps-per-dispatch", type=int, default=1)
    ap.add_argument("--device", default="auto", choices=("auto", "cpu"),
                    help="cpu forces the host platform BEFORE jax backend "
                         "init")
    args = ap.parse_args()

    if args.device == "cpu":
        from paddle_tpu.device.probe import force_cpu_platform

        force_cpu_platform()

    import paddle_tpu as paddle
    from paddle_tpu.models import GPTConfig, GPTForPretraining

    import jax

    on_tpu = jax.default_backend() != "cpu"
    cfg = (GPTConfig(vocab_size=50304, hidden_size=768, num_layers=12,
                     num_heads=12, max_seq_len=1024) if on_tpu else
           __import__("paddle_tpu.models", fromlist=["gpt_tiny"]).gpt_tiny())
    paddle.seed(0)
    model = GPTForPretraining(cfg)
    model.eval()
    rng = np.random.RandomState(0)
    prompt = min(args.prompt, cfg.max_seq_len // 2)
    ids = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (args.batch, prompt)).astype(np.int64))

    ks, xs, ys = [int(k) for k in args.ks.split(",")], [], []
    prompt_np = ids.numpy()
    with paddle.amp.auto_cast(enable=on_tpu, dtype="bfloat16"):  # match bench
        eng = None
        if args.engine:
            from paddle_tpu.serving import ServingEngine

            feasible = [k for k in ks if prompt + k <= cfg.max_seq_len]
            eng = ServingEngine(
                model, slot_count=args.batch, ladder=(prompt,),
                max_new_cap=max(feasible), max_seq_len=cfg.max_seq_len,
                steps_per_dispatch=args.steps_per_dispatch)

        def run_engine(k):
            reqs = [eng.submit(prompt_np[i], max_new_tokens=k,
                               temperature=0.0) for i in range(args.batch)]
            eng.run()
            assert all(r.done for r in reqs)

        for k in ks:
            if prompt + k > cfg.max_seq_len:
                continue
            if args.engine:
                run_engine(k)                                     # warm
                t0 = time.perf_counter()
                run_engine(k)
                dt = time.perf_counter() - t0
            else:
                warm = model.generate(ids, max_new_tokens=k, temperature=0)
                int(warm.numpy()[0, -1])  # sync: jit dispatch is async —
                t0 = time.perf_counter()  # else the warmup lands in the fit
                out = model.generate(ids, max_new_tokens=k, temperature=0)
                int(out.numpy()[0, -1])                           # D2H sync
                dt = time.perf_counter() - t0
            xs.append(k)
            ys.append(dt)
            print(json.dumps({"k": k, "total_s": round(dt, 4),
                              "tokens_per_s": round(args.batch * k / dt, 1)}),
                  flush=True)
    if len(xs) >= 2:
        slope, intercept = np.polyfit(xs, ys, 1)
        print(json.dumps({"per_token_ms": round(slope * 1e3, 3),
                          "intercept_s": round(float(intercept), 4),
                          "batch": args.batch, "prompt": prompt,
                          "mode": "engine" if args.engine else "legacy",
                          "steps_per_dispatch": (args.steps_per_dispatch
                                                 if args.engine else None)}),
                  flush=True)


if __name__ == "__main__":
    main()
