"""Time the attention core of a decode step, or of a block step, over a
`full` layer of the slot cache ALONE on the chip: the Pallas kernel
(ops/pallas/slot_decode.py; block_decode.py for `sdar`) by block size, and
the plain core it replaces.

    chiprun --chips 1 -- python3 tools/slot_decode_bench.py [64 128 ...]
        [--cell olmo,decode,chat,sdar] [--seed N]

One layer of each cell that takes the kernel, the arrays as `SlotKV` stores
them (bf16), one query a slot, every slot live at a length drawn as the
cell's traffic fills it (a prompt of its distribution plus a uniform share
of the tokens it asks for; `latent_decode_bench.contexts`):

- `olmo`: `serve-olmo-hybrid-decode`, `[16, 4096, 32, 128]` (30 heads of 128
  in 32), `decode-backlog-long`; the plain core is `afmoe._attend`;
- `decode`, `chat`: `serve-gpt2-large-decode` / `-chat`, `[16, 1024, 24,
  128]` (20 heads of 64 in 24 x 128), `decode-backlog` / `chat-poisson`; the
  plain core is `F.scaled_dot_product_attention` under the mask;
- `sdar`: `serve-sdar-30b-a3b-diffusion`'s block step, `[64, 3072, 4, 128]`,
  a block of 4 queries a slot and 8 query heads a key head (32 query rows a
  key head), `diffusion-backlog`, each length rounded up to the end of its
  block; the plain core is `afmoe._attend` under the mask (the
  block-causal mask, at offsets on a block's edge). Its blocks default to
  64 to 512.

One JSON line a side:

- `ms`: a call (one layer): 36 calls in one jitted scan, each query a
  little of the last result, the best of three means over 10 dispatches
  ended by `block_until_ready`, over 36;
- `needed_gb_s`: the bytes of the key and value rows HELD, unpadded, over
  that time; `fetched_gb_s`: what the side reads (the kernel: the stored
  rows, pad included, to the block; the plain core: every stored row);
- `max_gap`: the largest difference from the plain core's result over the
  largest value, and `attn.calls.slot_kernel` as the registry counts it.

Off a TPU the script exits 1: a CPU time is no kernel time.
"""
from __future__ import annotations

import _bootstrap  # noqa: F401

import json
import os
import sys

from _timing import timeit
from latent_decode_bench import contexts

CELLS = {   # cell -> (slots, rows, kv heads, head size, its traffic,
    #                queries a slot, query heads a key head)
    "olmo": (16, 4096, 30, 128, "decode-backlog-long", 1, 1),
    "decode": (16, 1024, 20, 64, "decode-backlog", 1, 1),
    "chat": (16, 1024, 20, 64, "chat-poisson", 1, 1),
    "sdar": (64, 3072, 4, 128, "diffusion-backlog", 4, 8),
}
BLOCKS = (32, 64, 128, 256, 512)
BLOCK_STEP_BLOCKS = (64, 128, 256, 512)
CALLS = 36          # layers a timed program holds


def _option(argv, name, default):
    if name not in argv:
        return default, argv
    at = argv.index(name)
    return argv[at + 1], argv[:at] + argv[at + 2:]


def main(argv) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu  # noqa: F401  (jax_enable_x64, as the program runs)
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.models.afmoe import _attend
    from paddle_tpu.nn import functional as F
    from paddle_tpu.nn.kv_cache import SlotKV, logical_rows, stored_dims
    from paddle_tpu.observability import metrics
    from paddle_tpu.ops import slot_attention
    from paddle_tpu.ops.pallas import block_decode, slot_decode

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"needs a TPU, found {dev.platform}", file=sys.stderr)
        return 1
    seed, argv = _option(argv, "--seed", "0")
    cells, argv = _option(argv, "--cell", ",".join(CELLS))
    seed = int(seed)
    blocks = [int(a) for a in argv]

    def line(**kw):
        print(json.dumps(kw), flush=True)

    for cell in cells.split(","):
        slots, rows, kv_heads, d, traffic_name, s, groups = CELLS[cell]
        # a block step (s > 1) goes to the grouped body, whose every query
        # sees the rows to the end of its slot's block
        block_step = s > 1
        kernel_module = block_decode if block_step else slot_decode
        counter = metrics.default_registry().counter(
            "attn.calls." + ("block_kernel" if block_step else "slot_kernel"))
        with open(os.path.join(_bootstrap._REPO, "benchmarks", "traffic",
                               traffic_name + ".json")) as f:
            held = contexts(json.load(f), slots, seed, rows)
        if block_step:
            held = np.minimum(-(-held // s) * s, rows)
        heads, width = stored_dims(kv_heads, d)
        keys = jax.random.split(jax.random.key(seed), 3)
        pad = [(0, 0), (0, 0), (0, heads - kv_heads), (0, width - d)]
        q = jax.random.normal(keys[0], (slots, s, kv_heads, groups, d),
                              jnp.bfloat16)
        k = jnp.pad(jax.random.normal(keys[1], (slots, rows, kv_heads, d),
                                      jnp.bfloat16), pad)
        v = jnp.pad(jax.random.normal(keys[2], (slots, rows, kv_heads, d),
                                      jnp.bfloat16), pad)
        # the handle `update` returns: its offset counts the step's own row
        offset = jnp.asarray(held, jnp.int32)
        mask = jnp.arange(rows)[None, None, :] < offset[:, None, None]
        needed = 2 * int(held.sum()) * kv_heads * d * 2
        stored_row = 2 * heads * width * 2            # both arrays
        line(side="device", cell=cell, kind=dev.device_kind, seed=seed,
             stored=list(k.shape), queries=[s, groups], contexts={
                 "mean": float(held.mean()), "min": int(held.min()),
                 "max": int(held.max())},
             needed_mb=needed / 1e6, bound_ms=needed / 819e9 * 1e3)

        def report(side, fn, fetched, **kw):
            before = counter.value
            got = jax.jit(fn)(q, k, v, offset).astype(jnp.float32)

            # CALLS layers in ONE program, each query a little of the last
            # result: a dispatch a call would read the host's floor of 0.19
            # ms (PERF.md, PR 29) where a layer takes less
            @jax.jit
            def layers(q, k, v, offset):
                def layer(q, _):
                    o = fn(q, k, v, offset)
                    return q + (o * 1e-3).astype(q.dtype), None

                return jax.lax.scan(layer, q, None, length=CALLS)[0]

            t = min(timeit(layers, (q, k, v, offset), iters=10)
                    for _ in range(3)) / CALLS
            line(side=side, cell=cell, ms=t * 1e3,
                 needed_gb_s=needed / t / 1e9, fetched_gb_s=fetched / t / 1e9,
                 kernel_calls=counter.value - before, **kw)
            return got

        def plain(q, k, v, offset):
            kc, vc = (logical_rows(a, kv_heads, d) for a in (k, v))
            if cell in ("olmo", "sdar"):
                return _attend(q, kc, vc, mask)
            return F.scaled_dot_product_attention(
                Tensor(q[:, :, :, 0]), Tensor(kc), Tensor(vc),
                attn_mask=Tensor(mask[:, None]), dropout_p=0.0,
                training=False)._data[:, :, :, None]

        want = report("plain", plain, slots * rows * stored_row)
        for block in blocks or (BLOCK_STEP_BLOCKS if block_step
                                else BLOCKS):
            side = f"kernel_{block}"
            if rows % block:
                line(side=side, cell=cell, refused="the rows do not divide")
                continue
            kernel_module.BLOCK_ROWS = block

            def kernel(q, k, v, offset):
                o = slot_attention.decode_core(q, SlotKV(k, v, offset),
                                               block=s if block_step else None)
                assert o is not None, "supported() refuses the shape"
                return o

            fetched = int((-(-held // block) * block).sum()) * stored_row
            try:
                got = report(side, kernel, fetched, block=block)
            except Exception as e:           # the chip's compiler refuses it
                line(side=side, cell=cell,
                     refused=str(e).splitlines()[0][:300])
                continue
            line(side=side, cell=cell,
                 max_gap=float(jnp.abs(got - want).max()
                               / jnp.abs(want).max()))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
