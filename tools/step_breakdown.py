"""Localize the bench train step's time across its major regions, on-chip.

The bench headline (GPT-124M, batch 16, seq 1024) sits at MFU ~0.35 against
the builder target of >= 0.45. This probe answers WHERE the
other 65% goes, the way the reference localizes with its op micro-benchmark
harness (paddle/fluid/operators/benchmark/op_tester.cc) — but at region
granularity, since under XLA per-op timings are meaningless after fusion.

Times, per region (each its own jitted program, bf16 autocast like bench.py):
  full_step        loss + grads + clip + AdamW update   (== engine.step body)
  fwd_bwd          loss + grads only (no optimizer)
  fwd_only         loss only
  attn_micro       flash attention fwd+bwd at bench shapes, summed over layers
  lmloss_micro     fused LM-head cross-entropy fwd+bwd at [b*s, h] x [h, V]
  mlp_micro        the 2 MLP matmuls + gelu fwd+bwd, summed over layers
  adamw_micro      the AdamW tree update alone at bench param count

Implied splits (full-fwd_bwd = optimizer+clip; fwd_bwd-fwd = backward) print
alongside, with achieved TFLOP/s per region so the under-performer is
obvious. Usage: python tools/step_breakdown.py [--model base|medium]
[--batch N]. Writes one JSON line per region.

Relation to paddle_tpu.observability: this probe re-times each region in a
FRESH synthetic run; the in-process tracer + StepTelemetry record what a
REAL run did (spans, per-step JSONL) with no separate probe launch. Use
tools/trace_summary.py on a run's telemetry output, then this probe to dig
into a region it flags.
"""
import json

import _bootstrap  # noqa: F401  (repo-root sys.path)

from _timing import timeit


def main():
    import argparse
    import os

    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="base",
                    choices=("tiny", "base", "medium"))
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--device", default=None, choices=(None, "cpu", "tpu"),
                    help="cpu forces the host platform through jax.config")
    args = ap.parse_args()

    if args.device == "cpu":
        from paddle_tpu.device.probe import force_cpu_platform

        force_cpu_platform()

    import jax
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed import fleet
    from paddle_tpu.models import GPTConfig, GPTForPretraining

    on_tpu = jax.default_backend() != "cpu"
    if args.model == "tiny":  # CPU smoke config for the tool itself
        cfg = GPTConfig(vocab_size=1024, hidden_size=128, num_layers=2,
                        num_heads=4, max_seq_len=args.seq)
    elif args.model == "medium":
        cfg = GPTConfig(vocab_size=50304, hidden_size=1024, num_layers=24,
                        num_heads=16, max_seq_len=args.seq)
    else:
        cfg = GPTConfig(vocab_size=50304, hidden_size=768, num_layers=12,
                        num_heads=12, max_seq_len=args.seq)
    b, s, h, L, V = args.batch, args.seq, cfg.hidden_size, cfg.num_layers, \
        cfg.vocab_size

    rng = np.random.RandomState(0)
    ids = rng.randint(0, V, (b, s)).astype(np.int64)
    labels = np.roll(ids, -1, 1)

    paddle.seed(0)
    strategy = dist.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": jax.device_count(), "mp_degree": 1}
    fleet.init(is_collective=True, strategy=strategy)
    model = GPTForPretraining(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters(),
                                 weight_decay=0.01)
    engine = fleet.distributed_engine(model, opt)
    t_ids, t_labels = paddle.to_tensor(ids), paddle.to_tensor(labels)
    n_params = sum(p.size for p in model.parameters())

    results = {}

    def report(name, dt, flops=None):
        results[name] = dt
        line = {"region": name, "ms": round(dt * 1e3, 2)}
        if flops:
            line["tflops_per_sec"] = round(flops / dt / 1e12, 1)
        print(json.dumps(line), flush=True)

    # --- region 1-3: the engine's own step decomposed ------------------
    raw = engine._raw_step()
    params, opt_state = engine.params, engine.opt_state
    lr = jnp.float32(1e-4)
    step_i = jnp.int32(1)
    key = jax.random.key(0)

    full = jax.jit(raw)  # no donation: params reused across iters

    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.jit import functional_call

    buffers = engine.buffers
    buffer_names = engine._buffer_names

    def compute_loss(ps, i, l):
        state = dict(ps)
        for bn in buffer_names:
            state[bn] = buffers[bn]
        with paddle.amp.auto_cast(enable=on_tpu, dtype="bfloat16"):
            out = functional_call(model, state,
                                  Tensor(i, stop_gradient=True),
                                  Tensor(l, stop_gradient=True))
        loss = out[0] if isinstance(out, (tuple, list)) else out
        return loss._data if isinstance(loss, Tensor) else loss

    fwd = jax.jit(compute_loss)
    vgrad = jax.jit(lambda p, i, l: jax.value_and_grad(compute_loss)(p, i, l))

    dt_full = timeit(
        lambda: full(params, opt_state, lr, step_i, key, t_ids._data,
                     t_labels._data), (), iters=args.iters)
    # 6*N*tokens + causal-attention matmul term (QK^T + AV, fwd + 2x bwd)
    step_flops = 6 * n_params * b * s + 3 * L * (4 * b * s * s * h // 2)
    report("full_step", dt_full, step_flops)
    report("fwd_bwd", timeit(
        lambda: vgrad(params, t_ids._data, t_labels._data), (),
        iters=args.iters), step_flops)
    report("fwd_only", timeit(
        lambda: fwd(params, t_ids._data, t_labels._data), (),
        iters=args.iters), step_flops // 3)

    # --- microbenches --------------------------------------------------
    import paddle_tpu.nn.functional as F

    nh, hd = cfg.num_heads, h // cfg.num_heads
    q = jnp.asarray(rng.randn(b, s, nh, hd), jnp.bfloat16)

    def attn_fb(qq):
        def one(x):
            o = F.scaled_dot_product_attention(
                Tensor(x), Tensor(x), Tensor(x), is_causal=True)
            return o._data.astype(jnp.float32).sum()
        val, g = jax.value_and_grad(one)(qq)
        return g

    attn_j = jax.jit(attn_fb)
    dt = timeit(lambda: attn_j(q), (), iters=args.iters)
    # per layer: fwd 2*2*b*s^2/2*nh*hd*... causal flash ~ 2 matmuls * b*s*s*h
    attn_flops = 3 * (4 * b * s * s * h // 2)  # fwd + ~2x bwd, causal half
    report("attn_micro_per_layer", dt, attn_flops)
    results["attn_micro_total"] = dt * L

    from paddle_tpu.ops.fused import fused_linear_cross_entropy

    hid = jnp.asarray(rng.randn(b * s, h), jnp.bfloat16)
    w = jnp.asarray(rng.randn(V, h), jnp.bfloat16)
    lab = jnp.asarray(labels.reshape(-1))

    def lml(hh, ww):
        out = fused_linear_cross_entropy(
            Tensor(hh), Tensor(ww), Tensor(lab), transpose_y=True)
        loss = out[0] if isinstance(out, (tuple, list)) else out
        return loss._data.astype(jnp.float32).mean()

    lml_j = jax.jit(lambda hh, ww: jax.value_and_grad(lml, argnums=(0, 1))(hh, ww))
    dt = timeit(lambda: lml_j(hid, w), (), iters=args.iters)
    report("lmloss_micro", dt, 3 * 2 * b * s * h * V)

    w1 = jnp.asarray(rng.randn(h, 4 * h), jnp.bfloat16)
    w2 = jnp.asarray(rng.randn(4 * h, h), jnp.bfloat16)
    x0 = jnp.asarray(rng.randn(b * s, h), jnp.bfloat16)

    def mlp(xx, a, c):
        y = F.gelu(Tensor(xx @ a), approximate=True)._data @ c
        return y.astype(jnp.float32).sum()

    mlp_j = jax.jit(lambda xx, a, c: jax.value_and_grad(mlp, argnums=(1, 2))(xx, a, c))
    dt = timeit(lambda: mlp_j(x0, w1, w2), (), iters=args.iters)
    report("mlp_micro_per_layer", dt, 3 * 2 * b * s * (8 * h * h))
    results["mlp_micro_total"] = dt * L

    # AdamW alone at param scale
    from paddle_tpu.optimizer import functional as opt_funct

    update = opt_funct.make_tree_update(
        opt, {n: engine._state_refs[n] for n in engine._param_names})
    fake_grads = {n: jnp.zeros_like(v) for n, v in params.items()}
    upd_j = jax.jit(lambda p, g, st: update(p, g, st, lr, step_i))
    dt = timeit(lambda: upd_j(params, fake_grads, opt_state), (),
                iters=args.iters)
    report("adamw_micro", dt)

    # --- summary -------------------------------------------------------
    opt_ms = (results["full_step"] - results["fwd_bwd"]) * 1e3
    bwd_ms = (results["fwd_bwd"] - results["fwd_only"]) * 1e3
    acct = (results["attn_micro_total"] + results["mlp_micro_total"] +
            results["lmloss_micro"]) * 1e3
    print(json.dumps({
        "summary": {
            "full_step_ms": round(results["full_step"] * 1e3, 2),
            "optimizer_and_clip_ms": round(opt_ms, 2),
            "backward_ms": round(bwd_ms, 2),
            "fwd_ms": round(results["fwd_only"] * 1e3, 2),
            "attn_total_ms": round(results["attn_micro_total"] * 1e3, 2),
            "mlp_total_ms": round(results["mlp_micro_total"] * 1e3, 2),
            "lmloss_ms": round(results["lmloss_micro"] * 1e3, 2),
            "accounted_micro_ms": round(acct, 2),
            "n_params": int(n_params),
            "platform": jax.default_backend(),
        }}, ))


if __name__ == "__main__":
    main()
