"""The third rehearsal of the on-chip-measurement guide for the cell
`serve-deepseek-v2-decode`, run by hand and never sent to the chip: compile
the engine's decode program and its largest prefill programs at the
published widths for a DESCRIBED TPU v5e chip and print `memory_analysis()`
(arguments, temporaries, peak), so that the choice between 64, 48 and 32
slots is made before chip time is spent: 5.16B parameters (10.33 GB), the
latent rows of the slots (2.52 GB at 64 x 6,144) and a prefill of 3,584
tokens at 128 heads have to fit 15.75 GiB together.

    JAX_PLATFORMS=cpu python3 benchmarks/rehearse_deepseek_v2.py [--slots N] [rung ...]
    JAX_PLATFORMS=cpu python3 benchmarks/rehearse_deepseek_v2.py --reference

`--reference` compiles the check's float32 reference instead, an expert
layer over the longest request (3,600 positions), plain and as the float8
control rounds it, from shapes alone (no model is built): what the check
needs BESIDE the 11.97 GiB of weights and rows, of 15.75.

It loads the TPU's compiler library, which only one process may hold: a
script, not a test. Nothing runs. The model is built here on the host with
zeros for its matrices (10.3 GB of host memory); the programs are compiled
for the slot count through their argument shapes.
"""
from __future__ import annotations

import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("FLAGS_compile_cache_dir", "")   # unreadable here anyway

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))


def reference_programs(chip) -> int:
    """The reference's expert-layer program at the check's longest request,
    plain and with every matrix and the stream rounded inside it."""
    import jax
    import jax.numpy as jnp

    from benchmarks.rehearse_compile import report
    from benchmarks.lib import reference_deepseek_v2 as reference
    from benchmarks.runners import serve_deepseek
    from benchmarks.runners.common import state_arrays
    from benchmarks.tests.controls_deepseek_v2 import _e4m3

    with open(os.path.join(HERE, "configs", "deepseek-v2.json")) as f:
        config = json.load(f)
    cfg, first = serve_deepseek.reference_config(config)
    shapes = jax.eval_shape(
        lambda: state_arrays(serve_deepseek.build_model(config, 0)))
    layer = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
        reference.layer_state(shapes, 1))
    h = jax.ShapeDtypeStruct((3600, cfg["hidden_size"]), jnp.float32,
                             sharding=chip)
    for name, low in (("plain", lambda x: x), ("float8", _e4m3)):
        t0 = time.perf_counter()
        compiled = jax.jit(lambda p, x: reference.layer(
            {k: (low(v) if v.ndim >= 2 else v) for k, v in p.items()},
            low(x), 1, cfg, base=first)).lower(layer, h).compile()
        report(f"reference expert layer, 3,600 positions, {name}", compiled,
               t0)
    return 0


def main(argv) -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmarks.rehearse_compile import report
    from benchmarks.runners import serve_deepseek
    from paddle_tpu.nn.layers import routed_experts
    from paddle_tpu.serving import ServingEngine

    def load(folder, name):
        with open(os.path.join(HERE, folder, name + ".json")) as f:
            return json.load(f)

    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    if argv[:1] == ["--reference"]:
        return reference_programs(chip)
    cell = load("workloads", "serve-deepseek-v2-decode")
    # zeros, not 3.3 billion normal draws on the host
    routed_experts._draw = lambda key, shape, std, dtype: jnp.zeros(shape, dtype)
    model = serve_deepseek.build_model(load("configs", cell["config"]), 0)
    kw = dict(cell["engine"], ladder=tuple(cell["engine"]["ladder"]))
    slots = kw.pop("slot_count")
    if argv[:1] == ["--slots"]:
        slots, argv = int(argv[1]), argv[2:]
    eng = ServingEngine(model, slot_count=1, **kw)

    def on_chip(tree, lead=None):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(
                a.shape if lead is None else (lead,) + a.shape[1:], a.dtype,
                sharding=chip), tree)

    cache = on_chip(eng.slot_cache.args(), lead=slots)
    params = on_chip(eng._params)
    gb = 1 / 2 ** 30

    def size(tree):
        return sum(a.size * a.dtype.itemsize
                   for a in jax.tree_util.tree_leaves(tree)) * gb

    print(f"weights {size(eng._params):.2f} GiB, at {slots} slots latent "
          f"rows {size(cache):.2f} GiB", flush=True)

    def vec(dtype):
        return jax.ShapeDtypeStruct((slots,), dtype, sharding=chip)

    def scalar(dtype):
        return jax.ShapeDtypeStruct((), dtype, sharding=chip)

    for family in ("sample",):      # the one the cell compiles
        t0 = time.perf_counter()
        compiled = eng._build_decode(family).lower(
            params, *cache, vec(jnp.int32), vec(jnp.int32),
            vec(jnp.bool_), vec(jnp.float32), vec(jnp.int32),
            vec(jnp.float32), vec(jnp.int32), vec(jnp.int32),
            vec(jnp.int32)).compile()
        report(f"serve-deepseek-v2 decode `{family}`, {slots} slots, "
               f"{eng.steps_per_dispatch} steps a dispatch", compiled, t0)
    for rung in [int(a) for a in argv] or [max(eng.ladder)]:
        t0 = time.perf_counter()
        compiled = eng._build_prefill(rung).lower(
            params, *cache,
            jax.ShapeDtypeStruct((1, rung), jnp.int64, sharding=chip),
            scalar(jnp.int32), scalar(jnp.int32), scalar(jnp.float32),
            scalar(jnp.int32), scalar(jnp.float32),
            scalar(jnp.int32)).compile()
        report(f"serve-deepseek-v2 prefill rung {rung}, {slots} slots",
               compiled, t0)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
