"""The third rehearsal of the on-chip-measurement guide for the cell
`serve-olmo-hybrid-decode`, run by hand and never sent to the chip: compile
the engine's prefill and decode programs at the published widths for a
DESCRIBED TPU v5e chip and print `memory_analysis()`, so that 3.27B
parameters, 16 slots of rows and states and a prefill of 3,584 tokens are
known to fit before chip time is spent.

    JAX_PLATFORMS=cpu python3 benchmarks/rehearse_olmo_hybrid.py [rung ...]

It loads the TPU's compiler library, which only one process may hold: a
script, not a test. Nothing runs. The model is built here on the host with
zeros for its matrices (6.5 GB of host memory); the programs are compiled
for the cell's slot count through their argument shapes.
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


def main(argv) -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmarks.rehearse_compile import report
    from benchmarks.runners import serve_hybrid
    from paddle_tpu.nn.layers import routed_experts
    from paddle_tpu.serving import ServingEngine

    def load(folder, name):
        with open(os.path.join(HERE, folder, name + ".json")) as f:
            return json.load(f)

    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    cell = load("workloads", "serve-olmo-hybrid-decode")
    # zeros, not 3.3 billion normal draws on the host
    routed_experts._draw = lambda key, shape, std, dtype: jnp.zeros(shape, dtype)
    model = serve_hybrid.build_model(load("configs", cell["config"]), 0)
    kw = dict(cell["engine"], ladder=tuple(cell["engine"]["ladder"]))
    slots = kw.pop("slot_count")
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
          f"{size(cache[:2]):.2f} GiB and states {size(cache[2:]):.2f} GiB",
          flush=True)

    def vec(dtype):
        return jax.ShapeDtypeStruct((slots,), dtype, sharding=chip)

    def scalar(dtype):
        return jax.ShapeDtypeStruct((), dtype, sharding=chip)

    for family in ("sample", "greedy"):
        t0 = time.perf_counter()
        compiled = eng._build_decode(family).lower(
            params, *cache, vec(jnp.int32), vec(jnp.int32),
            vec(jnp.bool_), vec(jnp.float32), vec(jnp.int32),
            vec(jnp.float32), vec(jnp.int32), vec(jnp.int32),
            vec(jnp.int32)).compile()
        report(f"serve-olmo-hybrid decode `{family}`, {slots} slots, "
               f"{eng.steps_per_dispatch} steps a dispatch", compiled, t0)
    for rung in [int(a) for a in argv] or [max(eng.ladder)]:
        t0 = time.perf_counter()
        compiled = eng._build_prefill(rung).lower(
            params, *cache,
            jax.ShapeDtypeStruct((1, rung), jnp.int64, sharding=chip),
            scalar(jnp.int32), scalar(jnp.int32), scalar(jnp.float32),
            scalar(jnp.int32), scalar(jnp.float32),
            scalar(jnp.int32)).compile()
        report(f"serve-olmo-hybrid prefill rung {rung}, {slots} slots",
               compiled, t0)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
