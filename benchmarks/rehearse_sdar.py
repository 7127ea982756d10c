"""The third rehearsal of the on-chip-measurement guide for the cell
`serve-sdar-30b-a3b-diffusion`, run by hand and never sent to the chip:
compile the engine's block-step decode program and its block prefill
programs at the published widths for a DESCRIBED TPU v5e chip and print
`memory_analysis()` (arguments, temporaries, peak), so that the sizes are
proved before chip time is spent: 4.36B parameters (8.72 GB), the rows of 64
slots of 3,072 positions (2.42 GB), a forward's 256 rows of 151,936 float32
logits and a prefill of 2,048 tokens have to fit 15.75 GiB together.

    JAX_PLATFORMS=cpu python3 benchmarks/rehearse_sdar.py [--slots N] [rung ...]
    JAX_PLATFORMS=cpu python3 benchmarks/rehearse_sdar.py --reference

`--reference` compiles the check's float32 reference instead, a layer over
the longest request (2,064 positions), from shapes alone (no model is
built): what the check needs BESIDE the weights and the rows.

It loads the TPU's compiler library, which only one process may hold: a
script, not a test. Nothing runs. The model is built here on the host with
zeros for its matrices (8.7 GB of host memory); the programs are compiled
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


def load(folder, name):
    with open(os.path.join(HERE, folder, name + ".json")) as f:
        return json.load(f)


def reference_program(chip) -> int:
    """The reference's layer program at the check's longest request."""
    import jax
    import jax.numpy as jnp

    from benchmarks.lib import reference_sdar as reference
    from benchmarks.rehearse_compile import report
    from benchmarks.runners import serve_sdar
    from benchmarks.runners.common import state_arrays

    config = load("configs", "sdar-30b-a3b")
    shapes = jax.eval_shape(
        lambda: state_arrays(serve_sdar.build_model(config, 0)))
    layer = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
        reference.layer_state(shapes, 1))
    h = jax.ShapeDtypeStruct((2064, config["hidden_size"]), jnp.float32,
                             sharding=chip)
    t0 = time.perf_counter()
    compiled = jax.jit(lambda p, x: reference.layer(
        p, x, config, config["block_length"])).lower(layer, h).compile()
    report("reference layer, 2,064 positions", compiled, t0)
    return 0


def main(argv) -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmarks.rehearse_compile import report
    from benchmarks.runners import serve_sdar
    from paddle_tpu.nn.layers import routed_experts
    from paddle_tpu.serving import ServingEngine

    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    if argv[:1] == ["--reference"]:
        return reference_program(chip)
    cell = load("workloads", "serve-sdar-30b-a3b-diffusion")
    # zeros, not 4.4 billion normal draws on the host
    routed_experts._draw = lambda key, shape, std, dtype: jnp.zeros(shape, dtype)
    model = serve_sdar.build_model(load("configs", cell["config"]), 0)
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

    print(f"weights {size(eng._params):.2f} GiB, at {slots} slots rows "
          f"{size(cache):.2f} GiB", flush=True)
    # the carry and the per-slot constants, a slot a row
    per_slot = on_chip(tuple(jnp.asarray(a) for a in (
        *eng._host_carry(), *eng._host_consts())), lead=slots)
    t0 = time.perf_counter()
    compiled = eng._build_block_decode("sample").lower(
        params, *cache, *per_slot).compile()
    report(f"serve-sdar block decode `sample`, {slots} slots, "
           f"{eng.steps_per_dispatch} forwards a dispatch", compiled, t0)
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=chip)
    for rung in [int(a) for a in argv] or [max(eng.ladder)]:
        t0 = time.perf_counter()
        compiled = eng._build_block_prefill(rung).lower(
            params, *cache,
            jax.ShapeDtypeStruct((1, rung), jnp.int64, sharding=chip),
            scalar, scalar).compile()
        report(f"serve-sdar block prefill rung {rung}, {slots} slots",
               compiled, t0)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
