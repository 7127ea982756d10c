"""Shared timing helpers for the on-chip probe tools.

A timed region ends with jax.block_until_ready, which drains every shard on
every device. On the TPU v5e machine this tree runs on, a 29 ms jitted matmul
chain timed 29.0-29.2 ms with block_until_ready, 29.5 ms with a D2H fetch of
one element and 30.8 ms with both (chip run, PR 21): block_until_ready does
not return early, so it is the one sync kept.
"""
from __future__ import annotations

import time


def sync(out):
    import jax

    jax.block_until_ready(out)


def timeit(fn, args=(), iters=10, warmup=2):
    out = None
    for _ in range(warmup):
        out = fn(*args)
    if out is not None:  # warmup=0: caller accepts compile time in the timing
        sync(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    sync(out)
    return (time.perf_counter() - t0) / iters
