"""Time the sampler ALONE on the chip: `sample_tokens_with_prob` (the block
step's draw and its probability) and `sample_tokens` (every other decode
program's draw), with the top-k / top-p filter in its full form (both
threshold searches over the whole row) and in the narrowed form (over the
candidates, `serving/sampling.py` `CAP`), at every serving cell's shape.
Its table, beside `CAP`, is what `sampling.takes_narrowed` rests on.

    python3 tools/sampler_bench.py  # on one TPU chip
        [--shapes 256x151936,16x200192,...] [--seed N]

The shapes (rows x vocabulary): SDAR's block step (64 slots x 4 positions,
151,936), Trinity (16 x 200,192), Olmo-Hybrid (16 x 100,352), GPT-2 (16 x
50,304) and DeepSeek-V2's sliced head (64 x 25,600). Every row samples at
the traffic's parameters (temperature 0.8, top_k 50, top_p 0.9) and a
quarter are greedy (temperature 0), as retired slots and greedy requests
are; `mix` `full_row` sets one row to top_k 0, top_p 0.9, which sends the
narrowed form's `lax.cond` down the full search.

One JSON line a side (also written to chiprun_out/sampler_bench.jsonl):

- `ms`: a call: 8 calls in one jitted scan (the logits shifted by the loop
  index, the results summed into the carry), the best of three means over
  5 dispatches ended by `block_until_ready`, over 8;
- `gb_s`: the logits' bytes (read once) over that time;
- `same_tokens`, `max_prob_gap`: the narrowed form's draws against the full
  form's under the same keys, and the largest gap of their probabilities.

Off a TPU the script exits 1: a CPU time is no device time.
"""
from __future__ import annotations

import _bootstrap  # noqa: F401

import json
import os
import sys

from _timing import timeit

SHAPES = ((256, 151_936), (16, 200_192), (16, 100_352), (16, 50_304),
          (64, 25_600))
CALLS = 8


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
    from paddle_tpu.serving import sampling

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"needs a TPU, found {dev.platform}", file=sys.stderr)
        return 1
    seed, argv = _option(argv, "--seed", "0")
    shapes, argv = _option(argv, "--shapes", ",".join(
        f"{n}x{v}" for n, v in SHAPES))
    seed = int(seed)
    out_dir = os.path.join(_bootstrap._REPO, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    log = open(os.path.join(out_dir, "sampler_bench.jsonl"), "a")

    def line(**kw):
        text = json.dumps(kw)
        print(text, flush=True)
        log.write(text + "\n")
        log.flush()

    wide = sampling.takes_narrowed
    for shape in shapes.split(","):
        n, vocab = (int(a) for a in shape.split("x"))
        key = jax.random.fold_in(jax.random.key(seed), vocab)
        logits = jax.random.normal(key, (n, vocab), jnp.float32) * 3
        keys = jax.random.split(jax.random.fold_in(key, 1), n)
        temps = jnp.where(jnp.arange(n) % 4 == 3, 0.0, 0.8).astype(
            jnp.float32)
        top_k = jnp.full((n,), 50, jnp.int32)
        top_p = jnp.full((n,), 0.9, jnp.float32)
        mixes = {"traffic": (top_k, top_p),
                 "full_row": (top_k.at[0].set(0), top_p)}
        line(side="device", kind=dev.device_kind, seed=seed, rows=n,
             vocab=vocab, logits_mb=n * vocab * 4 / 1e6,
             narrowed_by_width=wide(vocab))
        for fn_name in ("sample_tokens_with_prob", "sample_tokens"):
            fn = getattr(sampling, fn_name)
            for mix, (k, p) in mixes.items():
                got = {}
                for form in ("full", "narrowed"):
                    if form == "narrowed" and not wide(vocab):
                        continue
                    sampling.takes_narrowed = (lambda v, form=form:
                                               form == "narrowed")
                    try:
                        def call(x, fn=fn, k=k, p=p):
                            out = fn(x, keys, temps, k, p)
                            return out if isinstance(out, tuple) else (
                                out, jnp.zeros((n,), jnp.float32))

                        once = jax.jit(call)(logits)

                        @jax.jit
                        def calls(x, call=call):
                            def body(c, i):
                                tok, prob = call(x + i.astype(jnp.float32)
                                                 * 1e-3)
                                return c + tok.sum() + prob.sum(), None

                            return jax.lax.scan(
                                body, jnp.float32(0), jnp.arange(CALLS))[0]

                        t = min(timeit(calls, (logits,), iters=5)
                                for _ in range(3)) / CALLS
                    finally:
                        sampling.takes_narrowed = wide
                    got[form] = [np.asarray(a) for a in once]
                    kw = {}
                    if form == "narrowed":
                        kw = dict(
                            same_tokens=bool((got["full"][0]
                                              == got[form][0]).all()),
                            max_prob_gap=float(np.abs(
                                got["full"][1] - got[form][1]).max()))
                    line(side=form, fn=fn_name, mix=mix, rows=n,
                         vocab=vocab, ms=t * 1e3,
                         gb_s=n * vocab * 4 / t / 1e9, **kw)
        del logits
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
