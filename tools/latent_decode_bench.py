"""Time the absorbed core of a latent-attention decode step ALONE on the chip:
the Pallas kernel (ops/pallas/latent_decode.py) at a few block sizes, and the
plain `latent_attention.absorbed` it replaces.

    chiprun --chips 1 -- python3 tools/latent_decode_bench.py [256 512 ...]

One layer of the `serve-deepseek-v2-decode` cell: rows `[64, 6144, 640]`
bf16, 128 heads, latent 512 + 64, one query a slot; the slots' lengths drawn
as `decode-backlog-deep` fills them (a prompt of its lognormal, 512 to 3,584,
plus a uniform share of 2,048 tokens out: 512 to 5,632, about 3,200 on
average), from `--seed N` (default 0). One JSON line a side:

- `ms`: a call, the best of three means over 50 dispatches ended by
  `block_until_ready`;
- `needed_gb_s`: the bytes of the rows HELD (1,152 B a position: the function
  the benchmark keeps, `decode_bytes_mla.decode_step_bytes(...)["latent_rows"]`
  a layer) over that time; `fetched_gb_s` counts what the side reads (the
  kernel: 1,280 B a stored row, to the block; the plain core: every row
  twice);
- `roofline`: the larger of those bytes over 819 GB/s and
  `decode_step_flops(...)["core"]` a layer over 197 TFLOP/s, over that time,
  and `bound`, which of the two it was;
- `max_gap`: the largest difference from the plain core's result, and
  `mla.calls.absorbed_kernel` as the registry counts it.

Off a TPU the script exits 1: a CPU time is no kernel time.
"""
from __future__ import annotations

import _bootstrap  # noqa: F401

import json
import os
import sys

from _timing import timeit

SLOTS, ROWS, HEADS = 64, 6144, 128
BLOCKS = (256, 512, 1024, 2048)


def contexts(traffic: dict, slots: int, seed: int, rows: int):
    """Positions held by `slots` slots somewhere in a long run of the
    traffic: a prompt of its distribution and a uniform share of the
    tokens it asks for (tools/slot_decode_bench.py draws its own so)."""
    import numpy as np

    rng = np.random.default_rng(seed)

    def draw(d):
        if d["dist"] == "fixed":
            return np.full(slots, float(d["value"]))
        return np.clip(np.exp(rng.normal(np.log(d["median"]), d["sigma"],
                                         slots)), d["min"], d["max"])

    prompt = draw(traffic["prompt_len"])
    out = rng.uniform(0, draw(traffic["max_new"]), slots)
    return np.clip((prompt + out).astype(np.int64), 1, rows)


def main(argv) -> int:
    import jax
    import jax.numpy as jnp

    import paddle_tpu  # noqa: F401  (jax_enable_x64, as the program runs)
    from benchmarks.lib import decode_bytes_mla as need
    from benchmarks.lib.peaks import peak as chip_peak
    from paddle_tpu.observability import metrics
    from paddle_tpu.ops import latent_attention
    from paddle_tpu.ops.pallas import latent_decode

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"needs a TPU, found {dev.platform}", file=sys.stderr)
        return 1
    seed = 0
    if "--seed" in argv:
        at = argv.index("--seed")
        seed = int(argv[at + 1])
        argv = argv[:at] + argv[at + 2:]
    blocks = [int(a) for a in argv] or BLOCKS
    bench = os.path.join(_bootstrap._REPO, "benchmarks")
    with open(os.path.join(bench, "configs", "deepseek-v2.json")) as f:
        config = json.load(f)
    with open(os.path.join(bench, "traffic", "decode-backlog-deep.json")) as f:
        traffic = json.load(f)
    peak = chip_peak(dev.device_kind)
    r, d_r = config["kv_lora_rank"], config["qk_rope_head_dim"]
    width = -(-(r + d_r) // 128) * 128      # as nn/kv_cache.py stores a row
    held = contexts(traffic, SLOTS, seed, ROWS)
    layers = sum(need.layer_counts(config))
    need_bytes = need.decode_step_bytes(config, held, 0)["latent_rows"] / layers
    need_flops = need.decode_step_flops(config, held)["core"] / layers
    by_bytes = need_bytes / peak["hbm_bytes_per_s"]
    by_flops = need_flops / peak["bf16_flops_per_s"]
    bound_s, bound = max((by_bytes, "bytes"), (by_flops, "flops"))

    def line(**kw):
        print(json.dumps(kw), flush=True)

    line(side="device", kind=dev.device_kind, seed=seed,
         contexts={"mean": float(held.mean()), "min": int(held.min()),
                   "max": int(held.max())},
         bound_ms=bound_s * 1e3, bound=bound, by_bytes_ms=by_bytes * 1e3,
         by_flops_ms=by_flops * 1e3)

    keys = jax.random.split(jax.random.key(seed), 3)
    q_l = jax.random.normal(keys[0], (SLOTS, 1, HEADS, r), jnp.bfloat16)
    q_r = jax.random.normal(keys[1], (SLOTS, 1, HEADS, d_r), jnp.bfloat16)
    rows = jnp.pad(jax.random.normal(keys[2], (SLOTS, ROWS, r + d_r),
                                     jnp.bfloat16),
                   [(0, 0), (0, 0), (0, width - r - d_r)])
    lengths = jnp.asarray(held, jnp.int32)
    mask = jnp.arange(ROWS)[None, None, :] < lengths[:, None, None]
    scale = 0.1147
    counter = metrics.default_registry().counter("mla.calls.absorbed_kernel")

    def report(side, fn, fetched, **kw):
        before = counter.value
        jitted = jax.jit(fn)
        got = jitted(q_l, q_r, rows).astype(jnp.float32)
        t = min(timeit(jitted, (q_l, q_r, rows), iters=50) for _ in range(3))
        line(side=side, ms=t * 1e3, needed_gb_s=need_bytes / t / 1e9,
             fetched_gb_s=fetched / t / 1e9, roofline=bound_s / t,
             bound=bound, kernel_calls=counter.value - before, **kw)
        return got

    def plain(q_l, q_r, rows):
        return latent_attention.absorbed(q_l, q_r, rows, mask, scale)

    row_bytes = width * rows.dtype.itemsize
    want = report("plain", plain, 2 * SLOTS * ROWS * row_bytes)
    for block in blocks:
        if ROWS % block:
            line(side=f"kernel_{block}", refused="the rows do not divide")
            continue
        latent_decode.BLOCK_ROWS = block

        def kernel(q_l, q_r, rows):
            return latent_attention.absorbed(q_l, q_r, rows, mask, scale,
                                             lengths=lengths)

        fetched = int((-(-held // block) * block).sum()) * row_bytes
        got = report(f"kernel_{block}", kernel, fetched, block=block)
        # relative to the largest value: bf16 results, two roundings apart
        line(side=f"kernel_{block}",
             max_gap=float(jnp.abs(got - want).max()
                           / jnp.abs(want).max()))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
