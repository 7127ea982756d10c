"""Isolate WHY the optimizer region runs ~5-8x off its HBM roofline.

step_breakdown (round 5, on-chip, after the D2H-sync fix) measured the
bench engine's optimizer+clip region at 47.5 ms and the bare AdamW tree
update at 21 ms, against a ~4-6 ms roofline (28 B/param of HBM traffic at
819 GB/s on v5e). Candidate explanations, each isolated here as its own
jitted program at the bench model's exact leaf-shape census:

  tree          the production make_tree_update over the real leaf dict
  tree_donated  + buffer donation (aliased outputs: no fresh allocations)
  flat          ONE fused AdamW over a single concatenated [P] f32 vector
                (the multi-tensor-apply layout; upper bound on fusion)
  flat_donated  + donation
  clip_tree     global-norm clip alone over the leaf dict (150 reductions)
  clip_fused    global-norm via one concatenated reduction

If flat_donated ~= roofline but tree_donated is far off, the gap is
per-leaf kernel overhead -> the engine should flatten the optimizer state
(multi-tensor update). If donation closes the gap instead, the cost was
allocator churn. If nothing closes it, the region is genuinely
bandwidth-bound on this chip and the roofline estimate is wrong.

Usage: python tools/opt_fusion_probe.py [--iters 20]
"""
from __future__ import annotations

import _bootstrap  # noqa: F401

import argparse
import json
import time

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--device", default="auto", choices=("auto", "cpu"),
                    help="cpu forces the host platform BEFORE jax backend "
                         "init")
    args = ap.parse_args()

    if args.device == "cpu":
        from paddle_tpu.device.probe import force_cpu_platform

        force_cpu_platform()

    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import GPTConfig, GPTForPretraining
    import paddle_tpu as paddle

    paddle.seed(0)
    cfg = GPTConfig(vocab_size=50304, hidden_size=768, num_layers=12,
                    num_heads=12, max_seq_len=1024)
    model = GPTForPretraining(cfg)
    shapes = [(n, tuple(p.shape)) for n, p in model.state_dict().items()
              if not p.stop_gradient]
    rng = np.random.RandomState(0)

    def leafdict(scale=1e-2):
        return {n: jnp.asarray(rng.randn(*s).astype(np.float32) * scale)
                for n, s in shapes}

    params, grads = leafdict(), leafdict()
    m = {n: jnp.zeros(s, jnp.float32) for n, s in shapes}
    v = {n: jnp.zeros(s, jnp.float32) for n, s in shapes}
    n_total = sum(int(np.prod(s)) for _, s in shapes)
    lr, b1, b2, eps, wd = (jnp.float32(1e-4), 0.9, 0.999, 1e-8, 0.01)
    step = jnp.int32(7)

    def adamw_one(p, g, mm, vv):
        mm = b1 * mm + (1 - b1) * g
        vv = b2 * vv + (1 - b2) * jnp.square(g)
        mh = mm / (1 - b1 ** step)
        vh = vv / (1 - b2 ** step)
        return p - lr * (mh / (jnp.sqrt(vh) + eps) + wd * p), mm, vv

    def tree_up(params, grads, m, v):
        out = {n: adamw_one(params[n], grads[n], m[n], v[n]) for n in params}
        return ({n: o[0] for n, o in out.items()},
                {n: o[1] for n, o in out.items()},
                {n: o[2] for n, o in out.items()})

    flat_p = jnp.concatenate([params[n].ravel() for n, _ in shapes])
    flat_g = jnp.concatenate([grads[n].ravel() for n, _ in shapes])
    flat_m = jnp.zeros_like(flat_p)
    flat_v = jnp.zeros_like(flat_p)

    def clip_tree(grads):
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(g).astype(jnp.float32))
                            for g in grads.values()))
        scale = jnp.minimum(1.0, 1.0 / (norm + 1e-6))
        return {n: g * scale for n, g in grads.items()}

    def clip_flat(fg):
        norm = jnp.sqrt(jnp.sum(jnp.square(fg)))
        return fg * jnp.minimum(1.0, 1.0 / (norm + 1e-6))

    progs = {
        "tree": (jax.jit(tree_up), (params, grads, m, v)),
        "tree_donated": (jax.jit(tree_up, donate_argnums=(0, 2, 3)),
                         None),  # fresh copies per call, see below
        "flat": (jax.jit(adamw_one), (flat_p, flat_g, flat_m, flat_v)),
        "flat_donated": (jax.jit(adamw_one, donate_argnums=(0, 2, 3)), None),
        "clip_tree": (jax.jit(clip_tree), (grads,)),
        "clip_fused": (jax.jit(clip_flat), (flat_g,)),
    }

    from _timing import sync, timeit

    def timeit_donated(fn, first_args, grads_arg):
        """Donated buffers are consumed: thread each call's outputs back in
        as the next call's inputs (steady-state aliasing, like a train
        loop). first_args = (p, g, m, v) with fresh copies of the donated
        operands."""
        out = fn(*first_args)
        sync(out)
        t0 = time.perf_counter()
        for _ in range(args.iters):
            out = fn(out[0], grads_arg, out[1], out[2])
        sync(out)
        return (time.perf_counter() - t0) / args.iters

    for name, (fn, fargs) in progs.items():
        if name == "tree_donated":
            p2, m2, v2 = jax.tree_util.tree_map(jnp.copy, (params, m, v))
            dt = timeit_donated(fn, (p2, grads, m2, v2), grads)
        elif name == "flat_donated":
            dt = timeit_donated(fn, (jnp.copy(flat_p), flat_g,
                                     jnp.copy(flat_m), jnp.copy(flat_v)),
                                flat_g)
        else:
            dt = timeit(fn, fargs, iters=args.iters, warmup=1)
        gbps = None
        if name.startswith(("tree", "flat")):
            gbps = round(28 * n_total / dt / 1e9, 1)  # 16B read + 12B write
        elif name.startswith("clip"):
            # 12 B/param: the norm reduction reads g, then the scaling —
            # which cannot fuse past the reduction barrier — reads g again
            # and writes the scaled copy
            gbps = round(12 * n_total / dt / 1e9, 1)
        print(json.dumps({"prog": name, "ms": round(dt * 1e3, 3),
                          "achieved_GBps": gbps}), flush=True)
    print(json.dumps({"n_params": n_total,
                      "platform": jax.default_backend()}), flush=True)


if __name__ == "__main__":
    main()
