"""HLO-level performance regression gates, runnable without a TPU.

VERDICT r2 #1a: perf must be *verifiable* on CPU even when the chip is away.
Each test pins a compiler-level property that the on-chip numbers depend on:

- the dp engine step emits ONE fused (variadic) gradient all-reduce, not one
  per parameter (XLA AllReduceCombiner over the bucketed layout — the
  reference's Reducer contract, `paddle/fluid/imperative/reducer.cc`);
- the Pallas kernel flags actually route (pallas_call present in the jaxpr)
  AND the kernels Mosaic-compile for the TPU target (jax.export platforms=
  ["tpu"] embeds a tpu_custom_call) — this gate caught three real on-chip
  compile bugs in round 3 that interpret-mode tests had masked;
- recompute (remat) shrinks autodiff saved-residual bytes;
- the chunked fused LM loss avoids materializing [N, V] logits (temp bytes);
- buffer donation aliases the param+opt arguments (no double buffering).

Thresholds are pinned from measured values; regressions fail loudly.
"""
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core.tensor import Tensor

# ensure the kernel SUBMODULES are importable (the package __init__ re-exports
# shadow same-named functions)
import paddle_tpu.ops.pallas.flash_attention  # noqa: F401
import paddle_tpu.ops.pallas.layer_norm  # noqa: F401
import paddle_tpu.ops.pallas.lm_loss  # noqa: F401

_FA = sys.modules["paddle_tpu.ops.pallas.flash_attention"]
_LN = sys.modules["paddle_tpu.ops.pallas.layer_norm"]
_LM = sys.modules["paddle_tpu.ops.pallas.lm_loss"]

def _collective_gate_skip_reason():
    """Backend-capability probe for the collective-shape gates — now the
    SHARED predicate in paddle_tpu/analysis/backend.py (the analyzer's
    requires_combining contracts and these gates must agree on which
    backends can pin collective shapes). Returns None when the backend
    combines (gates must run), else the skip reason; cached there."""
    from paddle_tpu.analysis.backend import collective_combining_reason

    return collective_combining_reason()


def _require_collective_combining():
    reason = _collective_gate_skip_reason()
    if reason is not None:
        pytest.skip(reason)


def _dp8_engine(n_linear=12):
    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed import fleet

    strategy = dist.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 8}
    fleet.init(is_collective=True, strategy=strategy)
    layers = []
    for _ in range(n_linear):
        layers += [paddle.nn.Linear(64, 64), paddle.nn.ReLU()]
    net = paddle.nn.Sequential(*layers[:-1])
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=net.parameters())
    eng = fleet.distributed_engine(net, opt, loss_fn=paddle.nn.MSELoss())
    x = jnp.asarray(np.random.RandomState(0).randn(16, 64).astype("float32"))
    y = jnp.asarray(np.random.RandomState(1).randn(16, 64).astype("float32"))
    return eng, [x, y]


def _compile_step(eng, arrays):
    jf = eng._build(arrays)
    return jf.lower(eng.params, eng.opt_state, jnp.float32(1e-3),
                    jnp.int32(1), jax.random.key(0), *arrays).compile()


def test_dp_allreduce_is_fused():
    """24 params -> a handful of combined all-reduces, NOT one per param.
    (Declarative since ISSUE 11: the same contract rides engine.analyze().)"""
    _require_collective_combining()
    from paddle_tpu import analysis as an

    eng, arrays = _dp8_engine(n_linear=12)
    comp = _compile_step(eng, arrays)
    assert len(eng.params) == 24
    rep = an.check_compiled("train.step", comp, an.ProgramContract(
        collectives={"all-reduce": (1, 4)},
        allow_host_calls=True, max_constant_bytes=None))
    assert rep.ok, (
        f"gradient all-reduce combining regressed (expected one variadic "
        f"fused all-reduce for 24 params):\n{rep.format()}")


def _compile_accum(eng, arrays, k, dtype="f32"):
    from paddle_tpu.distributed import grad_comm

    jf = eng._build_accum(arrays, k, dtype, False, grad_comm.chunk_size())
    return jf.lower(eng.params, eng.opt_state, jnp.float32(1e-3),
                    jnp.int32(1), jax.random.key(0), *arrays).compile()


@pytest.mark.parametrize("k", [2, 4])
def test_microbatch_accum_exactly_one_fused_allreduce(k):
    """The K-microbatch accumulation step must compile to EXACTLY ONE
    gradient all-reduce regardless of K — the deferred reduction over the
    flattened grad buffer after the scan (grad_comm), the structural form
    of the reference's fuse_all_reduce_ops + accumulate contract. The K
    microbatches must run as one scan while-loop (one dispatch), and the
    carried params+opt state must stay donation-aliased."""
    eng, _ = _dp8_engine(n_linear=12)
    eng.microbatches = k
    arrays = [jnp.asarray(np.random.RandomState(0).randn(64, 64)
                          .astype("float32")),
              jnp.asarray(np.random.RandomState(1).randn(64, 64)
                          .astype("float32"))]  # 64 rows: divisible by dp8*K
    from paddle_tpu import analysis as an

    comp = _compile_accum(eng, arrays, k)
    state_bytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                      for a in eng.params.values())
    state_bytes += sum(int(np.prod(s.shape)) * s.dtype.itemsize
                       for st in eng.opt_state.values() for s in st)
    rep = an.check_compiled(f"train.accum_k{k}_f32", comp, an.ProgramContract(
        collectives={"all-reduce": 1}, while_loops=1,
        donated_bytes=state_bytes,
        allow_host_calls=True, max_constant_bytes=None))
    assert rep.ok, (
        f"K={k} accumulation contract broken (expected ONE deferred fused "
        f"gradient all-reduce, one scan while-loop, donated params+opt "
        f"state):\n{rep.format()}")


def test_microbatch_accum_shrinks_activation_peak():
    """At EQUAL effective batch, compiled temp memory (the activation
    high-water) must drop with K: the scan body holds one microbatch's
    activations, not the global batch's. Needs a model whose activations
    dwarf the flat f32 grad accumulator (GPT, not the Linear stack — there
    grads ~= activations and the ratio washes out). Measured K=4 ratio is
    ~0.3 at the grad_comm_bench config; gate 0.75 for headroom."""
    from paddle_tpu.distributed.engine import TrainStepEngine
    from paddle_tpu.distributed.mesh import (HybridCommunicateGroup,
                                             set_hybrid_communicate_group)
    from paddle_tpu.models import GPTForPretraining, gpt_tiny

    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, 1024, (16, 128)).astype(np.int64))
    arrays = [ids, jnp.asarray(np.roll(np.asarray(ids), -1, 1))]

    def build(k):
        set_hybrid_communicate_group(None)
        hcg = HybridCommunicateGroup(dp_degree=1, devices=jax.devices()[:1])
        paddle.seed(0)
        model = GPTForPretraining(gpt_tiny())
        opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                     parameters=model.parameters())
        return TrainStepEngine(model, opt, hcg=hcg, microbatches=k)

    t1 = _compile_step(build(1), arrays).memory_analysis().temp_size_in_bytes
    t4 = _compile_accum(build(4), arrays, 4) \
        .memory_analysis().temp_size_in_bytes
    assert t4 < 0.75 * t1, (
        f"K=4 accumulation temp {t4}B !< 0.75x single-shot {t1}B — the "
        f"microbatch scan no longer bounds activation memory")


def test_engine_donation_aliases_param_and_opt_buffers():
    """donate_argnums must alias params+opt state: peak = 1x state, not 2x."""
    from paddle_tpu import analysis as an

    eng, arrays = _dp8_engine(n_linear=4)
    comp = _compile_step(eng, arrays)
    state_bytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                      for a in eng.params.values())
    state_bytes += sum(int(np.prod(s.shape)) * s.dtype.itemsize
                       for st in eng.opt_state.values() for s in st)
    # per-device view: arguments are replicated here (dp), so full size
    rep = an.check_compiled("train.step", comp, an.ProgramContract(
        donated_bytes=state_bytes,
        allow_host_calls=True, max_constant_bytes=None))
    assert rep.ok, (
        f"buffer donation regressed — training would double-buffer params "
        f"in HBM:\n{rep.format()}")


def test_train_step_flops_accounting():
    """cost_analysis flops of the fused step covers the 6*N*T analytic
    minimum the MFU claim in bench.py is computed from."""
    eng, arrays = _dp8_engine(n_linear=4)
    comp = _compile_step(eng, arrays)
    from paddle_tpu.utils.hlo_inspect import cost_analysis_dict

    flops = cost_analysis_dict(comp)["flops"]
    n_params = sum(int(np.prod(a.shape)) for a in eng.params.values())
    # cost_analysis is per-device; the batch dim is sharded over dp=8
    tokens = arrays[0].shape[0] // 8
    assert flops >= 0.5 * 6 * n_params * tokens, (
        "compiled flops below the fwd+bwd analytic bound — the step is not "
        "computing what the MFU accounting assumes")


# ---------------------------------------------------------- pallas routing ----

def _flash_jaxpr(seq=256):
    from paddle_tpu.ops import nn_functional as F

    def att(qd):
        t = Tensor(qd)
        return F.scaled_dot_product_attention(t, t, t)._data

    q = jnp.zeros((2, seq, 4, 64), jnp.float32)
    return str(jax.make_jaxpr(att)(q))


def test_flash_attention_routes_to_pallas_when_flagged():
    paddle.set_flags({"use_flash_attention": True, "pallas_interpret_ok": True})
    assert "pallas_call" in _flash_jaxpr()
    paddle.set_flags({"use_flash_attention": False})
    assert "pallas_call" not in _flash_jaxpr()


# (the layernorm / lm_loss flag-routing gates were removed in round 5 with
#  the kernels' retirement from the training path; their math
#  stays pinned by tests/test_pallas_layernorm.py / test_pallas_lm_loss.py)


# ------------------------------------------------- Mosaic TPU compilation ----

def _export_tpu(fn, *avals):
    from jax import export

    return export.export(jax.jit(fn), platforms=["tpu"])(*avals).mlir_module()


@pytest.mark.slow
@pytest.mark.parametrize("shape, dtype, kernels", [
    # the shape the gate always had: f32, 4 heads of 64 -> packed
    ((2, 256, 4, 64), jnp.float32, ("flash_fwd", "flash_bwd")),
    # the train cell's call (GPT-2 medium, 8 x 1024, 16 heads of 64)
    ((8, 1024, 16, 64), jnp.bfloat16, ("flash_fwd", "flash_bwd")),
    # one head a 128-lane block
    ((4, 1024, 8, 128), jnp.bfloat16, ("flash_fwd", "flash_bwd")),
    # an odd head count keeps the [b*h, s, d] kernels and their two backwards
    ((2, 256, 3, 64), jnp.float32,
     ("flash_fwd", "flash_bwd_dkv", "flash_bwd_dq")),
])
def test_flash_attention_mosaic_compiles_for_tpu(monkeypatch, shape, dtype,
                                                 kernels):
    """Lower fwd+bwd for the REAL TPU target (Mosaic) from the CPU host, every
    path of flash_attention._path.

    Interpret-mode tests verify numerics but not Mosaic legality; this caught
    an f64 weak-literal cast in the masked-row fix that would have failed on
    chip (flash_attention.py:_finalize), and an i64 floor-divide in the
    packed kernels' loop bounds (jax_enable_x64 promotes `//`)."""
    monkeypatch.setattr(_FA, "_interpret", lambda: False)
    paddle.set_flags({"use_flash_attention": True, "pallas_interpret_ok": True})
    from paddle_tpu.ops import nn_functional as F

    def att_loss(qd):
        t = Tensor(qd)
        out = F.scaled_dot_product_attention(t, t, t, is_causal=True)._data
        return out.astype(jnp.float32).sum()

    mod = _export_tpu(jax.grad(att_loss), jax.ShapeDtypeStruct(shape, dtype))
    assert mod.count("tpu_custom_call") >= len(kernels)
    for name in ("flash_fwd", "flash_bwd", "flash_bwd_dkv", "flash_bwd_dq"):
        assert (f'kernel_name = "{name}"' in mod) == (name in kernels), name


@pytest.mark.slow
def test_lm_loss_mosaic_compiles_for_tpu(monkeypatch):
    monkeypatch.setattr(_LM, "_interpret", lambda: False)
    lab = jnp.zeros((1024,), jnp.int32)

    def f(h, w):
        return _LM.lm_head_cross_entropy(h, w, lab).mean()

    mod = _export_tpu(jax.grad(f, argnums=(0, 1)),
                      jax.ShapeDtypeStruct((1024, 128), jnp.float32),
                      jax.ShapeDtypeStruct((8192, 128), jnp.float32))
    assert "tpu_custom_call" in mod


@pytest.mark.slow
def test_layer_norm_mosaic_compiles_for_tpu(monkeypatch):
    monkeypatch.setattr(_LN, "_interpret", lambda: False)

    def f(x, g, b):
        return _LN.layer_norm(x, g, b, eps=1e-5).sum()

    mod = _export_tpu(jax.grad(f, argnums=(0, 1, 2)),
                      jax.ShapeDtypeStruct((512, 256), jnp.float32),
                      jax.ShapeDtypeStruct((256,), jnp.float32),
                      jax.ShapeDtypeStruct((256,), jnp.float32))
    assert "tpu_custom_call" in mod


# -------------------------------------------------------- memory behavior ----

def _gpt_loss_fn(use_recompute, granularity="full"):
    from paddle_tpu.jit import functional_call
    from paddle_tpu.models import GPTConfig, GPTForPretraining

    paddle.seed(0)
    cfg = GPTConfig(vocab_size=512, hidden_size=128, num_layers=4, num_heads=4,
                    max_seq_len=256, use_recompute=use_recompute,
                    recompute_granularity=granularity)
    model = GPTForPretraining(cfg)
    model.train()
    state = model.state_dict(include_non_persistable_buffer=True)
    arrays = {k: v._data for k, v in state.items()}
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, 512, (4, 256)).astype(np.int64))
    labels = jnp.asarray(np.roll(np.asarray(ids), -1, 1))

    def f(params):
        loss = functional_call(model, params, Tensor(ids), Tensor(labels))
        return loss._data if isinstance(loss, Tensor) else loss

    return f, arrays


def _saved_residual_bytes(f, arrays):
    from jax._src.ad_checkpoint import saved_residuals

    res = saved_residuals(f, arrays)
    return sum(int(np.prod(a.shape)) * a.dtype.itemsize
               for a, _ in res if hasattr(a, "shape"))


def test_recompute_shrinks_saved_residuals():
    """use_recompute=True (jax.checkpoint per block) must cut what autodiff
    saves — the measured ratio is ~0.06; gate at 0.25 for headroom."""
    f0, a0 = _gpt_loss_fn(False)
    b_no = _saved_residual_bytes(f0, a0)
    f1, a1 = _gpt_loss_fn(True)
    b_yes = _saved_residual_bytes(f1, a1)
    assert b_yes < 0.25 * b_no, (
        f"remat saved-residuals {b_yes}B vs {b_no}B without — recompute no "
        f"longer reduces activation memory")


def test_selective_recompute_sits_between_full_and_none():
    """recompute_granularity='selective' (save matmul outputs, recompute
    elementwise — jax dots_with_no_batch_dims_saveable) must save less than
    no-remat but more than full remat, and must recompute FEWER flops than
    full remat (the matmuls are not replayed)."""
    f_none, a = _gpt_loss_fn(False)
    f_full, _ = _gpt_loss_fn(True)
    f_sel, _ = _gpt_loss_fn(True, granularity="selective")
    b_none = _saved_residual_bytes(f_none, a)
    b_full = _saved_residual_bytes(f_full, a)
    b_sel = _saved_residual_bytes(f_sel, a)
    assert b_full < b_sel < b_none, (b_full, b_sel, b_none)

    def grad_flops(f):
        from paddle_tpu.utils.hlo_inspect import cost_analysis_dict

        g = jax.jit(jax.grad(lambda p: f(p).sum()))
        return float(cost_analysis_dict(g.lower(a).compile())
                     .get("flops", 0.0))

    fl_none, fl_full, fl_sel = map(grad_flops, (f_none, f_full, f_sel))
    assert fl_none < fl_sel < fl_full, (fl_none, fl_sel, fl_full)


def test_fused_lm_loss_avoids_logits_materialization():
    """Chunked fused CE must compile to far less temp memory than the naive
    [N, V] logits path (measured 34 MB vs 134 MB at these shapes)."""
    from paddle_tpu.ops import fused as fused_mod

    rng = np.random.RandomState(0)
    h = jnp.asarray(rng.randn(2048, 128).astype(np.float32))
    w = jnp.asarray(rng.randn(8192, 128).astype(np.float32))
    lab = jnp.asarray(rng.randint(0, 8192, 2048).astype(np.int32))

    def fused(hh, ww):
        return fused_mod._fused_lce(hh, ww, lab, True, 512, -100).mean()

    def naive(hh, ww):
        logits = hh @ ww.T
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, lab[:, None], axis=-1)[:, 0]
        return (lse - picked).mean()

    def temp_bytes(f):
        comp = jax.jit(jax.value_and_grad(f, argnums=(0, 1))).lower(h, w).compile()
        return comp.memory_analysis().temp_size_in_bytes

    t_fused, t_naive = temp_bytes(fused), temp_bytes(naive)
    assert t_fused < 0.5 * t_naive, (
        f"fused CE temp {t_fused}B !< half of naive {t_naive}B — the chunked "
        f"loss is materializing logits again")


# ------------------------------------------------------ ICI-level gates ----

def _gpt_engine_compiled(conf, sharding=False, sep_impl=None):
    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed import fleet
    from paddle_tpu.models import GPTForPretraining, gpt_tiny

    paddle.seed(0)
    strategy = dist.DistributedStrategy()
    strategy.sharding = sharding
    strategy.hybrid_configs = conf
    if sep_impl is not None:
        strategy.sep_impl = sep_impl
    fleet.init(is_collective=True, strategy=strategy)
    hcg = fleet.get_hybrid_communicate_group()
    model = GPTForPretraining(gpt_tiny())
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    eng = fleet.distributed_engine(model, opt)
    rng = np.random.RandomState(0)
    batch = max(4, 2 * hcg.degrees["dp"] * hcg.degrees["sharding"])
    ids = jnp.asarray(rng.randint(0, 1024, (batch, 64)).astype(np.int64))
    arrays = [ids, jnp.asarray(np.roll(np.asarray(ids), -1, 1))]
    tr = eng._build(arrays).trace(eng.params, eng.opt_state, jnp.float32(1e-3),
                                  jnp.int32(1), jax.random.key(0), *arrays)
    return eng, tr


def test_ring_sequence_parallel_emits_collective_permute():
    """sp=2 with sep_impl='ring' (the default is ulysses) must route
    attention through the ring (ppermute over 'sp') — the KV blocks rotate
    on ICI instead of an all-gather of the sequence."""
    eng, tr = _gpt_engine_compiled({"dp_degree": 2, "mp_degree": 2,
                                    "sep_degree": 2}, sep_impl="ring")
    assert "ppermute" in str(tr.jaxpr), "ring attention not engaged under sp=2"
    txt = tr.lower().compile().as_text()
    assert txt.count("collective-permute") >= 2, (
        "no collective-permute in the compiled sp step — the ring rotation "
        "was optimized out or replaced by sequence all-gather")


def test_default_sequence_parallel_is_ulysses_all_to_all():
    """The DEFAULT sp flavor is Ulysses (cost-model-backed):
    sp=2 with no explicit sep_impl must emit all-to-alls, not ppermutes."""
    # non-combining backends also reshard across the dp2/mp2/sp2 mesh with
    # device-order collective-permutes (identity-shuffle source_target_pairs),
    # tripping the no-ppermute assertion for reasons unrelated to the ulysses
    # routing — same reduced pipeline the probe detects
    _require_collective_combining()
    from paddle_tpu import analysis as an

    eng, tr = _gpt_engine_compiled({"dp_degree": 2, "mp_degree": 2,
                                    "sep_degree": 2})
    rep = an.check_compiled("train.step", tr.lower().compile(),
                            an.ProgramContract(
        collectives={"all-to-all": (1, None), "collective-permute": 0},
        allow_host_calls=True, max_constant_bytes=None))
    assert rep.ok, (
        f"ulysses default regressed (expected all-to-alls, no ppermute in "
        f"the default sp step):\n{rep.format()}")


def test_zero_sharding_gathers_params_and_keeps_fused_grad_reduce():
    """ZeRO-1 signature: sharded opt update + param all-gather, with the
    gradient reduction still COMBINED (a fused handful, not per-param)."""
    _require_collective_combining()
    eng, tr = _gpt_engine_compiled({"dp_degree": 2, "sharding_degree": 4},
                                   sharding=True)
    from paddle_tpu import analysis as an

    sharded = sum(1 for s in eng.opt_specs.values()
                  if "sharding" in str(s))
    assert sharded >= 10, f"only {sharded} opt-state specs ZeRO-sharded"
    rep = an.check_compiled("train.step", tr.lower().compile(),
                            an.ProgramContract(
        collectives={"all-gather": (5, None), "all-reduce": (1, 8)},
        allow_host_calls=True, max_constant_bytes=None))
    assert rep.ok, (
        f"ZeRO-1 signature broken (expected param all-gathers plus a "
        f"COMBINED gradient reduction):\n{rep.format()}")


def test_run_steps_scan_is_one_program_one_loop():
    """The fused K-step trainer must compile to ONE program whose steps run
    inside a single while-loop (lax.scan), with the same fused gradient
    all-reduce as the single step — not K unrolled bodies and not K
    dispatches. Donation must still alias the carried params+opt state."""
    _require_collective_combining()
    eng, arrays = _dp8_engine(n_linear=12)
    k = 5
    jf = eng._build_scan(arrays, True)
    keys = jnp.stack([jax.random.key(i) for i in range(k)])
    from paddle_tpu import analysis as an

    comp = jf.lower(eng.params, eng.opt_state, jnp.full((k,), 1e-3, jnp.float32),
                    jnp.int32(1), keys, *arrays).compile()
    state_bytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                      for a in eng.params.values())
    rep = an.check_compiled("train.run_steps", comp, an.ProgramContract(
        collectives={"all-reduce": (1, 4)}, while_loops=1,
        donated_bytes=state_bytes,
        allow_host_calls=True, max_constant_bytes=None))
    assert rep.ok, (
        f"run_steps contract broken (expected ONE scan while-loop, the "
        f"fused gradient all-reduce, donated carried params):\n"
        f"{rep.format()}")


def test_decode_loop_cache_in_place_no_weight_casts():
    """The KV-cache decode loop (GPTForPretraining.generate) must compile to a
    while loop whose body (a) updates the cache via dynamic-update-slice with
    NO cache-sized copy ops (in-place carry), and (b) contains no
    weight-sized f32->bf16 converts — under bf16 amp the weights are cast
    ONCE outside the loop and the cache is STORED in the compute dtype
    (round-3 fix: an f32 cache cost 2 cache-sized casts per layer per token,
    ~0.7 GB/step of HBM traffic at the bench config; tools/decode_hlo_probe.py).
    """
    from paddle_tpu.models import GPTForPretraining, gpt_tiny

    cfg = gpt_tiny()
    paddle.seed(0)
    model = GPTForPretraining(cfg)
    model.eval()
    b, prompt, new = 2, 16, 48
    ids = np.random.RandomState(0).randint(
        0, cfg.vocab_size, (b, prompt)).astype(np.int64)
    with paddle.amp.auto_cast(enable=True, dtype="bfloat16"):
        model.generate(paddle.to_tensor(ids), max_new_tokens=new,
                       temperature=0)
        jf = next(iter(model.decode_exec_registry().values()))
        params = {k: v._data for k, v in model.state_dict(
            include_non_persistable_buffer=True).items()}
        # run(params, ids, plen, key): plen traced since the prompt-bucket
        # round (round 6) — exact-shape calls simply pass plen == prompt
        txt = jf.lower(params, ids, jnp.int32(prompt),
                       jax.random.key(0)).compile().as_text()

    from paddle_tpu.utils import hlo_inspect as hi

    assert re.search(r"\) while\(", txt), \
        "decode scan unrolled or missing — expected one while loop"
    body = hi.while_body_lines(txt)
    assert body, "no while/body-tagged ops in compiled decode program"

    nh, hd = cfg.num_heads, cfg.hidden_size // cfg.num_heads
    cache_shape = f"{b},{prompt + new},{nh},{hd}"
    copies = hi.copies_of_shape(body, cache_shape)
    assert not copies, (
        f"cache-sized copies inside the decode loop (in-place DUS regressed): "
        f"{copies[:2]}")
    dus = hi.count_dynamic_update_slices(body)
    assert dus >= 2 * cfg.num_layers, (
        f"{dus} dynamic-update-slices in decode body for "
        f"{cfg.num_layers} layers — KV append path changed shape")
    # cache-shaped bf16 converts on CPU are f32-legalization noise (CPU dots
    # have no native bf16); weight-sized ones are real
    wcasts = hi.bf16_converts_of_min_size(
        body, cfg.hidden_size * cfg.hidden_size, exclude_shape_csv=cache_shape)
    assert not wcasts, (
        f"weight-sized f32->bf16 converts INSIDE the decode loop — amp cast "
        f"hoisting regressed: {wcasts[:2]}")


def test_zero_step_compiles_without_involuntary_rematerialization(capfd):
    """VERDICT r3 #4: the dp x mp x sharding (ZeRO) step must compile WITHOUT
    XLA's '[SPMD] Involuntary full rematerialization' warning. The round-3
    artifact carried two: the embedding optimizer-state spec ("mp","sharding")
    propagated backward onto the wte-grad scatter-add, demanding the [b,s,h]
    residual grad hidden-sharded — a batch->hidden reshard GSPMD can only do
    by replicate-and-repartition. The engine now pins grads to the param spec
    then the opt spec (distributed/engine.py); this gate captures the C++
    stderr via capfd during a fresh compile. (reduce-scatter counting is not
    assertable here: XLA CPU never forms reduce-scatter from all-reduce +
    dynamic-slice — that rewrite is TPU/GPU-only.)"""
    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed import fleet
    from paddle_tpu.models import GPTForPretraining, gpt_tiny

    if os.environ.get("TF_CPP_MIN_LOG_LEVEL", "0") not in ("0", "1"):
        # XLA emits the remat diagnostic at WARNING; with C++ logging forced
        # quieter this gate would pass vacuously
        pytest.skip("TF_CPP_MIN_LOG_LEVEL suppresses XLA warnings")

    strategy = dist.DistributedStrategy()
    strategy.sharding = True
    strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": 2,
                               "sharding_degree": 2}
    fleet.init(is_collective=True, strategy=strategy)
    paddle.seed(0)
    model = GPTForPretraining(gpt_tiny())
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    eng = fleet.distributed_engine(model, opt)
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, 1024, (4, 64)).astype(np.int64))
    labels = jnp.asarray(np.roll(np.asarray(ids), -1, 1))
    capfd.readouterr()  # drain anything queued before the compile
    compiled = _compile_step(eng, [ids, labels])
    err = capfd.readouterr().err
    assert "Involuntary full rematerialization" not in err, (
        "ZeRO step reintroduced a replicate-and-repartition reshard:\n"
        + "\n".join(ln for ln in err.splitlines()
                    if "rematerialization" in ln)[:500])
    # the partitioned step must still carry real collectives (the psums /
    # gathers of dp+mp+zero), or the topology silently degenerated
    txt = compiled.as_text()
    assert re.search(r"all-reduce", txt) and re.search(r"all-gather", txt)


def test_decode_loop_weights_precast_to_bf16():
    """Backend-independent decode-loop gate at the JAXPR level: under bf16
    amp, every weight-sized input to the decode scan must already be bf16
    (generate() pre-casts matmul weights ONCE outside the loop —
    weights-in-compute-dtype), and the scan body must contain ZERO
    weight-sized convert_element_type ops. Compiled-HLO carry checks can't
    pin this: XLA CPU upcasts bf16 dots to f32 and hoists the upcasts into
    the while carry, which on TPU would instead read f32 masters every token
    (~2x the weight traffic of the HBM-bound loop)."""
    from paddle_tpu.models import GPTForPretraining, gpt_tiny
    from paddle_tpu.utils import hlo_inspect as hi

    cfg = gpt_tiny()
    paddle.seed(0)
    model = GPTForPretraining(cfg)
    model.eval()
    ids = np.random.RandomState(0).randint(
        0, cfg.vocab_size, (2, 16)).astype(np.int64)
    with paddle.amp.auto_cast(enable=True, dtype="bfloat16"):
        model.generate(paddle.to_tensor(ids), max_new_tokens=48,
                       temperature=0)
        jf = next(iter(model.decode_exec_registry().values()))
        params = {k: v._data for k, v in model.state_dict(
            include_non_persistable_buffer=True).items()}
        # run(params, ids, plen, key) — see the cache-in-place gate above
        jaxpr = jax.make_jaxpr(jf)(params, ids, jnp.int32(16),
                                   jax.random.key(0))

    wmin = cfg.hidden_size * cfg.hidden_size
    big_inputs, n_converts = hi.jaxpr_loop_report(jaxpr, wmin)
    assert big_inputs, "decode scan not found in jaxpr"
    non_bf16 = [s for s in big_inputs if not s.startswith("bfloat16")]
    assert not non_bf16, (
        f"weight/cache-sized decode-loop inputs not pre-cast to bf16: "
        f"{non_bf16[:4]}")
    assert n_converts == 0, (
        f"{n_converts} weight-sized converts inside the decode scan body — "
        f"per-token weight casts regressed")


def test_flash_attention_memory_scales_linearly_with_seq():
    """Long-context gate: flash attention's compiled fwd+bwd temp memory
    must scale ~O(seq), not O(seq^2) — the property that makes seq 16k+
    single-chip configs (PADDLE_TPU_BENCH_SEQ) feasible at all. Measured
    ratio for 4x seq is ~3.9; a dense [.., s, s] materialization would be
    16x. Gate at 6x for headroom."""
    paddle.set_flags({"use_flash_attention": True, "pallas_interpret_ok": True})
    from paddle_tpu.ops import nn_functional as F

    def temp_bytes(seq):
        def att(qd):
            t = Tensor(qd)
            return F.scaled_dot_product_attention(t, t, t, is_causal=True)._data

        q = jnp.zeros((1, seq, 4, 64), jnp.float32)
        g = jax.jit(lambda x: jax.grad(lambda y: att(y).sum())(x))
        return g.lower(q).compile().memory_analysis().temp_size_in_bytes

    b1, b4 = temp_bytes(1024), temp_bytes(4096)
    assert b4 < 6 * b1, (
        f"flash temp memory grew {b4 / max(b1, 1):.1f}x for 4x seq — "
        f"attention is materializing O(s^2) state again")


def _sort_operand_shapes(stablehlo_text):
    """The operand shapes of every `stablehlo.sort` in a lowered program:
    a list of lists of dimension tuples."""
    found = []
    for m in re.finditer(r'"?stablehlo\.sort"?\(', stablehlo_text):
        sig = re.search(r"\}\)\s*:\s*\(([^)]*)\)\s*->",
                        stablehlo_text[m.end():])
        assert sig, "a stablehlo.sort whose signature this gate cannot read"
        found.append([tuple(int(d) for d in t.split("x")[:-1])
                      for t in re.findall(r"tensor<([^>]*)>", sig.group(1))])
    return found


@pytest.mark.parametrize("family", ["gpt", "afmoe"])
def test_serving_programs_sort_nothing_as_wide_as_the_vocabulary(family):
    """The sampler finds its top-k and nucleus thresholds by selection
    (serving/sampling.py): neither the `sample` decode program nor a prefill
    program sorts an array as wide as the vocabulary (two such sorts were
    44% of the Trinity decode step's device time at 16 x 200,192: ISSUE 29).
    The `sample` scope the trace reducer reads is still there."""
    from paddle_tpu.distributed.mesh import set_hybrid_communicate_group
    from paddle_tpu.observability import device_trace
    from paddle_tpu.serving import ServingEngine

    set_hybrid_communicate_group(None)
    paddle.seed(0)
    if family == "gpt":
        from paddle_tpu.models import GPTForPretraining, gpt_tiny

        model, vocab = GPTForPretraining(gpt_tiny()), gpt_tiny().vocab_size
    else:
        from paddle_tpu.models import AfmoeForCausalLM, afmoe_tiny

        model, vocab = AfmoeForCausalLM(afmoe_tiny()), afmoe_tiny().vocab_size
    model.eval()
    eng = ServingEngine(model, slot_count=2, ladder=(8, 16), max_seq_len=32,
                        max_new_cap=8, steps_per_dispatch=2)

    def vec(dtype):
        return jnp.zeros((eng.slot_count,), dtype)

    decode = eng._build_decode("sample").lower(
        eng._params, *eng.slot_cache.args(), vec(jnp.int32), vec(jnp.int32),
        vec(jnp.bool_), vec(jnp.float32), vec(jnp.int32), vec(jnp.float32),
        vec(jnp.int32), vec(jnp.int32), vec(jnp.int32))
    prefill = eng._build_prefill(8).lower(
        eng._params, *eng.slot_cache.args(), jnp.zeros((1, 8), jnp.int64),
        jnp.int32(0), jnp.int32(0), jnp.float32(0.0), jnp.int32(0),
        jnp.float32(1.0), jnp.int32(0))
    for name, lowered in (("decode", decode), ("prefill", prefill)):
        text = lowered.as_text()
        assert re.search(rf"tensor<\d+x{vocab}xf32>", text), \
            f"{name}: no [rows, {vocab}] logits to sample from"
        wide = [s for s in _sort_operand_shapes(text)
                if any(vocab in shape for shape in s)]
        assert not wide, (
            f"{name}: a sort over the whole vocabulary is back in the "
            f"program: {wide}")
        scopes = {device_trace.scope_of(op)[0] for op in re.findall(
            r'op_name="([^"]+)"', lowered.compile().as_text())}
        assert f"{name}/sample" in scopes, \
            f"{name}: the `sample` scope is gone: {sorted(scopes)}"
