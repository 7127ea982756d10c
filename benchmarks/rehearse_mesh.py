"""The third rehearsal for a cell that is one program across chips, run by
hand and never sent to the chip: lower the FSDP train step of a four-chip
cell for the four DESCRIBED devices of a `v5e:2x2` host, compile it with the
TPU's compiler, and print `memory_analysis()` for each rung of
`batch_per_chip`, so that the rung the cell runs is known to fit before four
chips are paid for.

    JAX_PLATFORMS=cpu python3 benchmarks/rehearse_mesh.py [cell] [rung ...]

`cell` is a file of benchmarks/workloads/ (default `train-gpt2-large-fsdp4`,
or its parameters from this file while the cell does not exist yet); the
rungs default to 8 4 2 and the script stops at the first that fits. Like
`rehearse_compile.py` it loads the TPU's compiler library, so it is a script
and not a test, and nothing runs: no result, no time.

What a program's `memory_analysis()` leaves out is added by hand: the Layer's
whole f32 weights stay on device 0 beside the shard (ROADMAP D16), so a rung
fits only if its peak plus those bytes is under the chip's 15.75 GiB.

The engine is built on one CPU device (its constructor places the weights)
and then handed the described mesh: every sharding of the step is made from
`engine.mesh` when the step is built, which is after the swap.
"""
from __future__ import annotations

import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("FLAGS_compile_cache_dir", "")   # unreadable here anyway

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

HBM_GIB = 15.75
CELL = {"config": "gpt2-large", "traffic": "pretrain-b8-s1024", "chips": 4,
        "learning_rate": 1e-4, "weight_decay": 0.01,
        "engine_kw": {"fsdp": True}}


def load(folder, name):
    with open(os.path.join(HERE, folder, name + ".json")) as f:
        return json.load(f)


def main(argv) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies

    import paddle_tpu as paddle
    import paddle_tpu.ops.pallas.flash_attention  # noqa: F401
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.mesh import HybridCommunicateGroup
    from benchmarks.runners import common

    names = [a for a in argv if not a.isdigit()]
    rungs = [int(a) for a in argv if a.isdigit()] or [8, 4, 2]
    name = names[0] if names else "train-gpt2-large-fsdp4"
    path = os.path.join(HERE, "workloads", name + ".json")
    cell = load("workloads", name) if os.path.exists(path) else CELL
    traf = load("traffic", cell["traffic"])
    chips = int(cell["chips"])

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    fa = sys.modules["paddle_tpu.ops.pallas.flash_attention"]
    fa._interpret = lambda: False
    paddle.set_flags({"use_flash_attention": True,
                      "pallas_interpret_ok": True})

    model = common.build_model(load("configs", cell["config"]), 0)
    layer_bytes = sum(p.size * p._data.dtype.itemsize
                      for p in model.parameters())
    import paddle_tpu.distributed as dist

    strategy = dist.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 1}
    fleet.init(is_collective=True, strategy=strategy)
    opt = paddle.optimizer.AdamW(learning_rate=float(cell["learning_rate"]),
                                 parameters=model.parameters(),
                                 weight_decay=float(cell["weight_decay"]))
    eng = fleet.distributed_engine(model, opt, **cell.get("engine_kw", {}))
    # the described mesh takes the place of the one-device mesh the
    # constructor used; nothing below touches a real device
    eng.hcg = HybridCommunicateGroup(dp_degree=chips,
                                     devices=list(topo.devices)[:chips])
    eng.mesh = eng.hcg.mesh
    eng._zero_reason = "unset"
    eng._fsdp_cache = None
    if not eng._fsdp_on():
        print("the cell's engine_kw do not engage FSDP on this mesh")
        return 1

    k, dtype, use_residual, chunk, _ = eng._grad_comm_config()
    buckets = eng._fsdp_layout()
    shard = eng._residual_sharding()
    scalar = jax.sharding.NamedSharding(eng.mesh,
                                        jax.sharding.PartitionSpec())
    flat = tuple(jax.ShapeDtypeStruct((b["pad"],), jnp.float32,
                                      sharding=shard) for b in buckets)
    slots = tuple(flat for _ in range(eng._zero_n_slots()))
    key = jax.random.key(0)
    gib = 1 / 2 ** 30
    print(f"[{name}] {len(buckets)} buckets, shard "
          f"{sum(b['shard'] for b in buckets) * 4 * gib:.2f} GiB of weights "
          f"a chip and {len(slots)}x that of optimizer state; the Layer's "
          f"f32 weights, {layer_bytes * gib:.2f} GiB, stay on device 0",
          flush=True)

    for rung in rungs:
        shape = (rung * chips, int(traf["seq_len"]))
        eng._batch_shardings = None
        ids = np.zeros(shape, np.int64)
        batch_sh = eng._shardings_for([ids, ids])
        batch = tuple(jax.ShapeDtypeStruct(shape, jnp.int64, sharding=s)
                      for s in batch_sh)
        t0 = time.perf_counter()
        try:
            with paddle.amp.auto_cast(dtype="bfloat16"):
                fn = eng._build_fsdp_accum([ids, ids], k, dtype,
                                           use_residual, chunk)
                compiled = fn.lower(
                    flat, slots,
                    jax.ShapeDtypeStruct((), jnp.float32, sharding=scalar),
                    jax.ShapeDtypeStruct((), jnp.int32, sharding=scalar),
                    jax.ShapeDtypeStruct(key.shape, key.dtype,
                                         sharding=scalar),
                    *batch).compile()
        except Exception as e:                     # the compiler's refusal
            print(f"[{name}] batch_per_chip {rung}: refused after "
                  f"{time.perf_counter() - t0:.0f} s: "
                  f"{str(e).splitlines()[0][:600]}", flush=True)
            continue
        ma = compiled.memory_analysis()
        text = compiled.as_text()
        peak = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
        total = (peak + layer_bytes) * gib
        counts = {op: text.count(f" {op}(") + text.count(f" {op}-start(")
                  for op in ("all-gather", "all-reduce", "reduce-scatter",
                             "all-to-all", "collective-permute")}
        print(f"[{name}] batch_per_chip {rung}: compiled in "
              f"{time.perf_counter() - t0:.0f} s; a chip holds arguments "
              f"{ma.argument_size_in_bytes * gib:.2f} GiB, outputs "
              f"{ma.output_size_in_bytes * gib:.2f} (aliased "
              f"{ma.alias_size_in_bytes * gib:.2f}), temporaries "
              f"{ma.temp_size_in_bytes * gib:.2f}, peak {peak * gib:.2f}; "
              f"with the Layer's weights device 0 holds {total:.2f} GiB of "
              f"{HBM_GIB}; Mosaic calls {text.count('tpu_custom_call')}, "
              f"collectives {counts}", flush=True)
        if total <= HBM_GIB:
            print(f"[{name}] batch_per_chip {rung} fits", flush=True)
            return 0
        print(f"[{name}] batch_per_chip {rung} does not leave room for the "
              f"Layer's weights", flush=True)
    print(f"[{name}] no rung of {rungs} fits")
    return 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
