"""Measure THIS chip's achievable matmul peak — the MFU denominator check.

Round-5 question: the bench headline sits at MFU ~0.41 against the v5e
datasheet peak (197 TFLOP/s bf16), and every matmul-heavy region micro-times
at 76-107 TFLOP/s. Is the program leaving half the MXU idle, or does this
chip simply not deliver datasheet peak?
Square bf16 matmuls at growing sizes are the least-confounded probe: no
reshapes, no fusion decisions, one dot per launch, compute intensity far
past the roofline knee. Whatever the 8k x 8k point achieves IS the
practical ceiling a whole-model step could ever approach here.

Usage: python tools/mxu_roofline.py [--sizes 2048,4096,8192] [--iters 30]
One JSON line per size; the final line is the achieved ceiling.
"""
from __future__ import annotations

import _bootstrap  # noqa: F401

import argparse
import json

from _timing import timeit


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes", default="1024,2048,4096,8192,16384")
    ap.add_argument("--iters", type=int, default=30)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--device", default="auto", choices=("auto", "cpu"),
                    help="cpu forces the host platform BEFORE jax backend "
                         "init")
    args = ap.parse_args()

    if args.device == "cpu":
        from paddle_tpu.device.probe import force_cpu_platform

        force_cpu_platform()

    import jax
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.RandomState(0)
    best = 0.0
    f = jax.jit(lambda a, b: a @ b)
    for n in [int(s) for s in args.sizes.split(",")]:
        a = jnp.asarray(rng.randn(n, n), args.dtype)
        b = jnp.asarray(rng.randn(n, n), args.dtype)
        dt = timeit(f, (a, b), iters=args.iters, warmup=3)
        tf = 2 * n * n * n / dt / 1e12
        best = max(best, tf)
        print(json.dumps({"n": n, "ms": round(dt * 1e3, 3),
                          "tflops_per_sec": round(tf, 1)}), flush=True)
    print(json.dumps({"achieved_ceiling_tflops": round(best, 1),
                      "datasheet_bf16_tflops": 197.0,
                      "platform": jax.default_backend()}), flush=True)


if __name__ == "__main__":
    main()
