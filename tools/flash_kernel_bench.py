"""Time the flash-attention kernels ALONE on the chip, causal, bf16.

    chiprun --chips 1 -- python3 tools/flash_kernel_bench.py [d64 d128 ...]

One JSON line a shape and side, each with the microseconds of the forward and
of forward + backward (`jax.vjp` through the custom vjp on a cotangent that is
already on the device) and their share of the causal roofline (required
matmul work over the bf16 peak; compute bounds these shapes):

- `packed` / `head128`: the kernels of the path `_path` picks, alone, on
  `[b, s, h*d]` operands (the backward includes `delta`, an XLA reduction);
- `entry`: `flash_attention` on `[b, s, h, d]` arrays as a caller holds them:
  at d=64 the reshape to `[b, s, h*d]` is a copy on the chip (a `[.., 16, 64]`
  array is tiled with its 64 lanes padded to 128);
- `legacy`: the `[b*h, s, d]` kernels (the parent's, kept for the shapes
  the packed paths do not take), alone and with the transposes
  `[b, s, h, d] <-> [b*h, s, d]` their entry wraps them in;
- `jax`: `jax.experimental.pallas.ops.tpu.flash_attention`, the better of its
  blocks of 512 and of 1024 — a yardstick, not a dependency.

A time is the best of three means over 30 dispatches ended by
`block_until_ready`. Off a TPU the script exits 1: a CPU time is no kernel time.
"""
from __future__ import annotations

import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

PEAK_FLOPS = 197e12     # TPU v5e, bf16 (benchmarks/lib/peaks.py)

# (b, s, h, d): the train cell's 128 heads and GPT-2 large's 160 at s=1024;
# the same token count at s = 2048 and 4096; d=128 with half the heads
SHAPES = {
    "d64": [(8, 1024, 16, 64), (8, 1024, 20, 64), (4, 2048, 16, 64),
            (2, 4096, 16, 64)],
    "d128": [(8, 1024, 8, 128), (4, 2048, 8, 128), (2, 4096, 8, 128)],
}


def best_of(fn, args, iters=30, rounds=3):
    import jax

    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(rounds):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / iters)
    return best * 1e6


def required_us(b, s, h, d, backward):
    """Causal attention's required matmul work at the peak: forward two
    matmuls of 2*s*s*d, halved by causality; backward four."""
    fwd = 2 * s * s * d
    return b * h * fwd * (3 if backward else 1) / PEAK_FLOPS * 1e6


def main(argv) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu  # noqa: F401  (jax_enable_x64, as the program runs)
    import paddle_tpu.ops.pallas.flash_attention  # noqa: F401
    from jax.experimental.pallas.ops.tpu import flash_attention as jax_flash

    fa = sys.modules["paddle_tpu.ops.pallas.flash_attention"]
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"needs a TPU, found {dev.platform}", file=sys.stderr)
        return 1

    def line(**kw):
        print(json.dumps(kw), flush=True)

    line(side="device", kind=dev.device_kind, count=len(jax.devices()))
    for group in (argv or sorted(SHAPES)):
        for b, s, h, d in SHAPES[group]:
            rng = np.random.RandomState(0)
            q, k, v, g = (jnp.asarray(rng.randn(b, s, h, d), jnp.bfloat16)
                          for _ in range(4))
            scale = 1.0 / math.sqrt(d)

            def times(fn, args, cot):
                """(forward us, forward + backward us); the backward is the
                vjp on a cotangent that is already there, so nothing but the
                attention's own work is timed."""
                def both(*a):
                    return jax.vjp(fn, *a)[1](cot)
                return best_of(jax.jit(fn), args), best_of(jax.jit(both), args)

            def report(side, t_f, t_fb, **kw):
                line(side=side, shape=[b, s, h, d], fwd_us=t_f,
                     fwd_bwd_us=t_fb,
                     fwd_roofline=required_us(b, s, h, d, False) / t_f,
                     fwd_bwd_roofline=required_us(b, s, h, d, True) / t_fb,
                     **kw)

            path, heads = fa._path(h, d, s, s, q.dtype)

            def entry(q, k, v):
                return fa.flash_attention(q, k, v, causal=True)

            report("entry", *times(entry, (q, k, v), g), path=path)

            if path != "legacy":
                def flat(x):  # [b, s, h*d]; at d=64 a copy on the chip, the
                    return x.reshape(b, s, h * d)  # tiles pad 64 lanes to 128

                def alone(q, k, v):
                    return fa._flash_packed(
                        q, k, v, scale, True, heads, d,
                        fa._static_blocks(path, s, s))[0]

                report(path, *times(alone, tuple(map(flat, (q, k, v))),
                                    flat(g)))

            def to_bhsd(x):
                return jnp.swapaxes(x, 1, 2).reshape(b * h, s, d)

            def legacy(q, k, v):
                return fa._flash_bhsd(q, k, v, scale, True, (512, 512))

            def legacy_wrapped(q, k, v):
                o = legacy(to_bhsd(q), to_bhsd(k), to_bhsd(v))
                return jnp.swapaxes(o.reshape(b, h, s, d), 1, 2)

            report("legacy", *times(legacy, tuple(map(to_bhsd, (q, k, v))),
                                    to_bhsd(g)))
            report("legacy_with_transposes",
                   *times(legacy_wrapped, (q, k, v), g))

            # jax's kernel takes [b, h, s, d] and no 64-bit index types; the
            # better of its 512 and 1024 blocks, forward and both apart
            with jax.enable_x64(False):
                bhsd = tuple(jnp.swapaxes(x, 1, 2) for x in (q, k, v))
                both = []
                for n in (512, 1024):
                    sizes = jax_flash.BlockSizes(
                        block_q=n, block_k_major=n, block_k=n, block_b=1,
                        block_q_major_dkv=n, block_k_major_dkv=n,
                        block_k_dkv=n, block_q_dkv=n,
                        block_k_major_dq=n, block_k_dq=n, block_q_dq=n)

                    def theirs(q, k, v):
                        return jax_flash.flash_attention(
                            q, k, v, causal=True, sm_scale=scale,
                            block_sizes=sizes)

                    both.append(times(theirs, bhsd, jnp.swapaxes(g, 1, 2)))
                report("jax", *map(min, zip(*both)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
