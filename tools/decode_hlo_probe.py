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

`--serving CONFIG` (gpt2-large, trinity-mini, olmo-hybrid-7b, deepseek-v2,
sdar-30b-a3b) reads another program instead: `ServingEngine`'s decode chunk
(`serve.decode_sample`; for sdar-30b-a3b the block-step program) of a
benchmark configuration at its cell's settings,
compiled for a DESCRIBED TPU
v5e as benchmarks/rehearse_*.py compile it. It prints where the slot cache
crosses the program's boundary: each kind of cache argument with its entry
layout and the layout the same array has inside the `while`, the cache-sized
`copy` / `copy-start` instructions outside and inside the loop (count and
bytes), the Mosaic kernels the program holds (`mosaic_calls`: the grouped
matmuls', for deepseek-v2 the absorbed core's, for gpt2-large and
olmo-hybrid-7b the full layers' core, ops/pallas/slot_decode.py) and
`memory_analysis()`.
It loads the TPU's compiler library, which one process holds at a time: run
it by hand, one configuration a process, never from a test. `--slots N` compiles for another slot count than the
cell's (a compile the chip's memory refuses is reported, not raised);
`--rung N` reads the prefill program of that rung instead (no loop there:
every cache-sized copy counts as outside).

    JAX_PLATFORMS=cpu python tools/decode_hlo_probe.py --serving gpt2-large
"""
from __future__ import annotations

import _bootstrap  # noqa: F401

import argparse
import json
import os
import sys

_SERVING = {   # configuration -> (its decode cell, the runner that builds it)
    "gpt2-large": ("serve-gpt2-large-decode", "common"),
    "trinity-mini": ("serve-trinity-mini-decode", "serve_afmoe"),
    "olmo-hybrid-7b": ("serve-olmo-hybrid-decode", "serve_hybrid"),
    "deepseek-v2": ("serve-deepseek-v2-decode", "serve_deepseek"),
    "sdar-30b-a3b": ("serve-sdar-30b-a3b-diffusion", "serve_sdar"),
}
def serving(config, slots, dump=None, rung=0):
    """Compile `serve.decode_sample` of a benchmark configuration (or, with
    `rung`, that prefill program) for a described v5e and print its
    boundary report."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("FLAGS_compile_cache_dir", "")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import importlib

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import paddle_tpu as paddle
    from paddle_tpu.nn.layers import routed_experts
    from paddle_tpu.serving import ServingEngine
    from paddle_tpu.utils import hlo_inspect as hi

    bench = os.path.join(_bootstrap._REPO, "benchmarks")

    def load(folder, name):
        with open(os.path.join(bench, folder, name + ".json")) as f:
            return json.load(f)

    cell_name, runner = _SERVING[config]
    cell = load("workloads", cell_name)
    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    # zeros, not billions of normal draws on the host
    routed_experts._draw = lambda key, shape, std, dtype: jnp.zeros(shape, dtype)
    # the program is compiled for the chip, so it holds the chip's kernels:
    # a trace here sees the CPU backend and would take the plain forms
    from paddle_tpu.ops.pallas import latent_decode, slot_decode
    latent_decode._target = slot_decode._target = lambda: "mosaic"
    build = importlib.import_module(f"benchmarks.runners.{runner}").build_model
    model = build(load("configs", cell["config"]), 0)
    model.eval()
    kw = dict(cell["engine"], ladder=tuple(cell["engine"]["ladder"]))
    slots = slots or kw["slot_count"]
    kw["slot_count"] = 1        # one slot on the host; the program is
    #                             compiled for `slots` through its shapes

    def on_chip(tree, lead=None):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(
                a.shape if lead is None else (lead,) + a.shape[1:], a.dtype,
                sharding=chip), tree)

    def vec(dtype):
        return jax.ShapeDtypeStruct((slots,), dtype, sharding=chip)

    # the GPT-2 runner serves inside the autocast scope; the other two
    # models hold bf16 weights and the scope changes nothing for them
    with paddle.amp.auto_cast(dtype="bfloat16"):
        eng = ServingEngine(model, **kw)
        cache = on_chip(eng.slot_cache.args(), lead=slots)
        def scalar(dtype):
            return jax.ShapeDtypeStruct((), dtype, sharding=chip)

        if getattr(model, "generation", None) is not None:
            # a model that generates by diffusion over blocks: its own
            # prefill and its block-step decode program
            if rung:
                lowered = eng._build_block_prefill(rung).lower(
                    on_chip(eng._params), *cache,
                    jax.ShapeDtypeStruct((1, rung), jnp.int64, sharding=chip),
                    scalar(jnp.int32), scalar(jnp.int32))
            else:
                lowered = eng._build_block_decode("sample").lower(
                    on_chip(eng._params), *cache, *on_chip(tuple(
                        jnp.asarray(a) for a in (*eng._host_carry(),
                                                 *eng._host_consts())),
                        lead=slots))
        elif rung:
            lowered = eng._build_prefill(rung).lower(
                on_chip(eng._params), *cache,
                jax.ShapeDtypeStruct((1, rung), jnp.int64, sharding=chip),
                scalar(jnp.int32), scalar(jnp.int32), scalar(jnp.float32),
                scalar(jnp.int32), scalar(jnp.float32), scalar(jnp.int32))
        else:
            lowered = eng._build_decode("sample").lower(
                on_chip(eng._params), *cache, vec(jnp.int32),
                vec(jnp.int32), vec(jnp.bool_), vec(jnp.float32),
                vec(jnp.int32), vec(jnp.float32), vec(jnp.int32),
                vec(jnp.int32), vec(jnp.int32))
    out = {"config": config, "slots": slots,
           "program": (f"serve.prefill_b{rung}" if rung
                       else "serve.decode_sample"),
           "steps_per_dispatch": eng.steps_per_dispatch}
    try:
        compiled = lowered.compile()
    except Exception as e:     # the chip's memory refuses the program
        print(json.dumps(dict(out, refused=str(e).splitlines()[0][:400])))
        return 1
    hlo_name = {"bfloat16": "bf16", "float32": "f32"}
    shapes = {(hlo_name[str(a.dtype)], tuple(a.shape))
              for a in jax.tree_util.tree_leaves(cache)}
    ma = compiled.memory_analysis()
    gib = 2 ** 30
    text = compiled.as_text()
    if dump:
        with open(dump, "w") as f:
            f.write(text)
    out.update(hi.boundary_report(text, shapes))
    out["mosaic_calls"] = text.count('custom_call_target="tpu_custom_call"')
    out["GiB"] = {
        "arguments": round(ma.argument_size_in_bytes / gib, 3),
        "aliased": round(ma.alias_size_in_bytes / gib, 3),
        "temporaries": round(ma.temp_size_in_bytes / gib, 3),
        "peak": round((ma.argument_size_in_bytes + ma.output_size_in_bytes
                       - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
                      / gib, 3)}
    print(json.dumps(out, indent=1))
    return 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--serving", choices=tuple(_SERVING))
    ap.add_argument("--slots", type=int, default=0)
    ap.add_argument("--dump", help="with --serving: write the optimized HLO "
                    "text to this file")
    ap.add_argument("--rung", type=int, default=0, help="with --serving: "
                    "the prefill program of this rung, not the decode one")
    ap.add_argument("--model", default="tiny", choices=("tiny", "base"))
    ap.add_argument("--device", default="cpu", choices=("cpu", "tpu"))
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt", type=int, default=16)
    ap.add_argument("--new", type=int, default=8)
    args = ap.parse_args()
    if args.serving:
        return serving(args.serving, args.slots, args.dump, args.rung)

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
    sys.exit(main())
