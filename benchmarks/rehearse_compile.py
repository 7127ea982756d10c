"""The third rehearsal of the on-chip-measurement guide, run by hand and never
sent to the chip: compile the cells' real programs at their real sizes for a
DESCRIBED TPU v5e chip, and print `memory_analysis()`, so that 8 x 1024 tokens
of GPT-2 medium and 32 slots of GPT-2 large are known to fit before chip time
is spent.

    JAX_PLATFORMS=cpu python3 benchmarks/rehearse_compile.py [train] [serve]

It loads the TPU's compiler library, which only one process may hold: a
script, not a test. Nothing runs, so it says nothing about results or times,
and it counts one program at a time, not what else the process keeps on the
device (the Layer's f32 weights beside the served bf16 snapshot, the reference
check). The program picks its CPU branches here (`jax.default_backend()` is
the CPU), so the script steers the flash kernel to its compiled path the way
tests/test_bench_compile_gate.py does, and hands the unjitted step and the
engine's program builders shapes that live on the described chip.
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


def report(label, compiled, t0):
    ma = compiled.memory_analysis()
    text = compiled.as_text()
    gb = 1 / 2 ** 30
    print(f"[{label}] compiled in {time.perf_counter() - t0:.0f} s: "
          f"arguments {ma.argument_size_in_bytes * gb:.2f} GiB, outputs "
          f"{ma.output_size_in_bytes * gb:.2f} GiB (aliased "
          f"{ma.alias_size_in_bytes * gb:.2f}), temporaries "
          f"{ma.temp_size_in_bytes * gb:.2f} GiB, peak about "
          f"{(ma.argument_size_in_bytes + ma.output_size_in_bytes - ma.alias_size_in_bytes + ma.temp_size_in_bytes) * gb:.2f}"
          f" GiB of 15.75; Mosaic calls {text.count('tpu_custom_call')}",
          flush=True)


def main(argv) -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import paddle_tpu as paddle
    import paddle_tpu.ops.pallas.flash_attention  # noqa: F401
    from benchmarks.runners import common

    which = set(argv) or {"train", "serve"}
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    fa = sys.modules["paddle_tpu.ops.pallas.flash_attention"]
    fa._interpret = lambda: False
    paddle.set_flags({"use_flash_attention": True,
                      "pallas_interpret_ok": True})

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
            tree)

    def load(folder, name):
        with open(os.path.join(HERE, folder, name + ".json")) as f:
            return json.load(f)

    if "train" in which:
        import paddle_tpu.distributed as dist
        from paddle_tpu.distributed import fleet

        cell = load("workloads", "train-gpt2-medium")
        traf = load("traffic", cell["traffic"])
        model = common.build_model(load("configs", cell["config"]), 0)
        strategy = dist.DistributedStrategy()
        strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 1}
        fleet.init(is_collective=True, strategy=strategy)
        opt = paddle.optimizer.AdamW(learning_rate=cell["learning_rate"],
                                     parameters=model.parameters(),
                                     weight_decay=cell["weight_decay"])
        eng = fleet.distributed_engine(model, opt, **cell["engine_kw"])
        ids = jax.ShapeDtypeStruct(
            (traf["batch_per_chip"], traf["seq_len"]), jnp.int64,
            sharding=chip)
        t0 = time.perf_counter()
        with paddle.amp.auto_cast(dtype="bfloat16"):
            compiled = jax.jit(eng._raw_step(), donate_argnums=(0, 1)).lower(
                on_chip(eng.params), on_chip(eng.opt_state),
                on_chip(jnp.float32(1e-4)), on_chip(jnp.int32(1)),
                on_chip(jax.random.key(0)), ids, ids).compile()
        report("train-gpt2-medium step", compiled, t0)
        del eng, opt, model

    if "serve" in which:
        from paddle_tpu.serving import ServingEngine

        cell = load("workloads", "serve-gpt2-large-chat")
        model = common.build_model(load("configs", cell["config"]), 0)
        model.eval()
        kw = dict(cell["engine"], ladder=tuple(cell["engine"]["ladder"]))
        slots = kw.pop("slot_count")
        with paddle.amp.auto_cast(dtype="bfloat16"):
            # one slot here on the host; the programs are compiled for the
            # cell's slot count through their argument shapes
            eng = ServingEngine(model, slot_count=1, **kw)
            cache = [jax.ShapeDtypeStruct((slots,) + a.shape[1:], a.dtype,
                                          sharding=chip) for a in eng._kcs]
            params = on_chip(eng._params)

            def vec(dtype):
                return jax.ShapeDtypeStruct((slots,), dtype, sharding=chip)

            def scalar(dtype):
                return jax.ShapeDtypeStruct((), dtype, sharding=chip)

            rung = max(eng.ladder)
            t0 = time.perf_counter()
            compiled = eng._build_prefill(rung).lower(
                params, cache, cache,
                jax.ShapeDtypeStruct((1, rung), jnp.int64, sharding=chip),
                scalar(jnp.int32), scalar(jnp.int32), scalar(jnp.float32),
                scalar(jnp.int32), scalar(jnp.float32),
                scalar(jnp.int32)).compile()
            report(f"serve-gpt2-large prefill rung {rung}, {slots} slots",
                   compiled, t0)
            for family in ("sample", "greedy"):
                t0 = time.perf_counter()
                compiled = eng._build_decode(family).lower(
                    params, cache, cache, vec(jnp.int32), vec(jnp.int32),
                    vec(jnp.bool_), vec(jnp.float32), vec(jnp.int32),
                    vec(jnp.float32), vec(jnp.int32), vec(jnp.int32),
                    vec(jnp.int32)).compile()
                report(f"serve-gpt2-large decode `{family}`, {slots} slots, "
                       f"{eng.steps_per_dispatch} steps a dispatch",
                       compiled, t0)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
