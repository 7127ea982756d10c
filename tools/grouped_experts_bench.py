"""Time the routed experts' SwiGLU over rows sorted by expert ALONE on the
chip: the Pallas kernel (ops/pallas/grouped_experts.py), the three
`ragged_dot` calls it replaces, and jax's own megablox `gmm` (three calls
and the SiLU between them), at the three routed-expert configurations'
widths and a range of mean rows an expert. Its table sets the band in which
the kernel is taken (`grouped_experts.ROWS_AN_EXPERT`).

    chiprun --chips 1 -- python3 tools/grouped_experts_bench.py
        [--config sdar,trinity,deepseek] [--rows 1,2.4,8,16,32,64,128,224]
        [--chunks 64] [--tn 0] [--mb-tm 128] [--seed N]

`--chunks` and `--tn` try the kernel at other rows a product and width
tiles (0: `tile_width()`'s, the whole expert where it fits `STEP_BYTES`).

- `sdar`: 2048 x 768, 128 experts held of 128, top-8 (SDAR-30B-A3B);
- `trinity`: 2048 x 1024, 128 of 128, top-8 (Trinity-Mini);
- `deepseek`: 5120 x 1536, 40 held of 160, top-6 (DeepSeek-V2's cell: one
  chip's share; the rows of the other 120 experts sort past the last group).

Mean rows an expert r is rows / PUBLISHED experts, the rule's measure: the
rows are `ceil(r x experts / 16) x 16`, each routed to an expert drawn
uniformly from `--seed` (a token's k experts apart, as top-k draws them).

One JSON line a side (also written to chiprun_out/grouped_experts_bench.jsonl):

- `ms`: a call (one layer): 8 calls in one jitted scan, each call's rows a
  little of the last result, the best of three means over 5 dispatches
  ended by `block_until_ready`, over 8;
- `gb_s`: the weights of the held experts that received a row, three
  matrices each, over that time; `share`: of 819 GB/s;
- `max_gap`: the largest difference from `ragged_dot`'s result over its
  largest value.

Off a TPU the script exits 1: a CPU time is no kernel time.
"""
from __future__ import annotations

import _bootstrap  # noqa: F401

import json
import math
import os
import sys

from _timing import timeit

CONFIGS = {   # name -> (hidden, width, held, published, top_k)
    "sdar": (2048, 768, 128, 128, 8),
    "trinity": (2048, 1024, 128, 128, 8),
    "deepseek": (5120, 1536, 40, 160, 6),
}
ROWS = (1, 2.4, 8, 16, 32, 64, 128, 224)
CALLS = 8           # layers a timed program holds
HBM_GB_S = 819.0    # TPU v5e (PERF.md §3)


def _option(argv, name, default):
    if name not in argv:
        return default, argv
    at = argv.index(name)
    return argv[at + 1], argv[:at] + argv[at + 2:]


def _sizes(jax, jnp, key, rows, held, published, top_k):
    """Rows an expert held, as a router over `published` experts hands them
    out: a token's k choices apart; the rows of the experts past `held`
    sort after the last group."""
    tokens = rows // top_k
    scores = jax.random.uniform(key, (tokens, published))
    _, chosen = jax.lax.top_k(scores, top_k)
    flat = chosen.reshape(-1)
    extra = rows - flat.shape[0]       # rows not a multiple of k: one more
    if extra:
        flat = jnp.concatenate([flat, jax.random.randint(
            key, (extra,), 0, published)])
    return jnp.bincount(flat, length=published)[:held].astype(jnp.int32)


def main(argv) -> int:
    import jax
    import jax.numpy as jnp

    import paddle_tpu  # noqa: F401  (jax_enable_x64, as the program runs)
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    from paddle_tpu.ops.pallas import grouped_experts as ge

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"needs a TPU, found {dev.platform}", file=sys.stderr)
        return 1
    seed, argv = _option(argv, "--seed", "0")
    names, argv = _option(argv, "--config", ",".join(CONFIGS))
    rows_an, argv = _option(argv, "--rows", ",".join(map(str, ROWS)))
    chunks, argv = _option(argv, "--chunks", str(ge.CHUNK_ROWS))
    tns, argv = _option(argv, "--tn", "0")
    mb_tms, argv = _option(argv, "--mb-tm", "128")
    seed = int(seed)
    out_dir = os.path.join(_bootstrap._REPO, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    log = open(os.path.join(out_dir, "grouped_experts_bench.jsonl"), "a")

    def line(**kw):
        text = json.dumps(kw)
        print(text, flush=True)
        log.write(text + "\n")
        log.flush()

    for name in names.split(","):
        hidden, width, held, published, top_k = CONFIGS[name]
        keys = jax.random.split(jax.random.key(seed), 5)

        def draw(key, shape):
            return (jax.random.normal(key, shape, jnp.float32)
                    * 0.02).astype(jnp.bfloat16)

        wg = draw(keys[0], (held, hidden, width))
        wu = draw(keys[1], (held, hidden, width))
        wd = draw(keys[2], (held, width, hidden))
        expert_bytes = 3 * hidden * width * 2
        for r in (float(a) for a in rows_an.split(",")):
            rows = math.ceil(r * published / 16) * 16
            sizes = _sizes(jax, jnp, jax.random.fold_in(keys[3], rows),
                           rows, held, published, top_k)
            xs = jax.random.normal(jax.random.fold_in(keys[4], rows),
                                   (rows, hidden), jnp.bfloat16)
            touched = int((sizes > 0).sum())
            mine = int(sizes.sum())
            needed = touched * expert_bytes
            line(side="device", config=name, kind=dev.device_kind, seed=seed,
                 rows_an_expert=r, rows=rows, rows_held=mine,
                 touched=touched, max_load=int(sizes.max()),
                 weights_mb=needed / 1e6,
                 bound_ms=needed / (HBM_GB_S * 1e9) * 1e3)

            def report(side, fn, want=None, **kw):
                try:
                    got = jax.jit(fn)(xs, wg, wu, wd, sizes)

                    # CALLS layers in ONE program, each call's rows a
                    # little of the last result
                    @jax.jit
                    def layers(xs, wg, wu, wd, sizes):
                        def layer(x, _):
                            y = fn(x, wg, wu, wd, sizes)
                            return x + (y * 1e-3).astype(x.dtype), None

                        return jax.lax.scan(layer, xs, None, length=CALLS)[0]

                    t = min(timeit(layers, (xs, wg, wu, wd, sizes), iters=5)
                            for _ in range(3)) / CALLS
                except Exception as e:     # the chip's compiler refuses it
                    line(side=side, config=name, rows_an_expert=r,
                         refused=str(e).splitlines()[0][:300], **kw)
                    return None
                gap = None
                if want is not None:   # over the held experts' rows: on
                    # the chip `ragged_dot` leaves the rows past them as
                    # it found them
                    d = got[:mine].astype(jnp.float32) - want[:mine]
                    gap = float(jnp.abs(d).max()
                                / jnp.abs(want[:mine]).max())
                line(side=side, config=name, rows_an_expert=r, ms=t * 1e3,
                     gb_s=needed / t / 1e9,
                     share=needed / t / 1e9 / HBM_GB_S, max_gap=gap, **kw)
                return got

            want = report("ragged_dot", ge.plain).astype(jnp.float32)
            for chunk in (int(c) for c in chunks.split(",")):
                for tn in (int(c) for c in tns.split(",")):
                    tn = tn or ge.tile_width(hidden, width, 2)
                    if width % tn:
                        continue
                    report(f"kernel_{chunk}_{tn}",
                           lambda *a, chunk=chunk, tn=tn: ge._call(
                               *a, tile=ge.ROW_TILE, chunk=chunk, tn=tn,
                               interpret=False),
                           want, chunk=chunk, tn=tn)
            for tm in (int(c) for c in mb_tms.split(",")):
                while rows % tm:       # gmm wants whole m-tiles
                    tm //= 2
                # tiles of the whole reduction, the widest output tile
                # whose weight blocks fit 12 MB twice over
                def fit(k, n):
                    return max(t for t in range(128, n + 1, 128)
                               if n % t == 0 and 2 * k * t * 2 <= 12 << 20)

                up_t = (tm, hidden, fit(hidden, width))
                down_t = (tm, width, fit(width, hidden))

                def megablox(x, wg, wu, wd, sizes, up_t=up_t, down_t=down_t):
                    # its kernel does not lower under jax_enable_x64
                    with jax.enable_x64(False):
                        g = gmm(x, wg, sizes, jnp.float32, up_t)
                        u = gmm(x, wu, sizes, jnp.float32, up_t)
                        a = (jax.nn.silu(g) * u).astype(x.dtype)
                        return gmm(a, wd, sizes, x.dtype, down_t)

                report(f"megablox_{tm}", megablox, want, tiling=[up_t, down_t])
        del wg, wu, wd
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
