"""The quickest proof that the system still starts on the chip.

One process, no child, no probe: it imports JAX once and owns the chip. It
drives the two main paths through the entry points a user calls, at the full
width and depth of GPT-2 124M (`bench.bench_config()`), with random
weights made from a seed:

- kernel:  the Pallas flash-attention kernel against dense attention at the
           train shape, forward and all three gradients;
- trainer: fleet.init -> GPTForPretraining -> AdamW -> fleet.distributed_engine
           -> engine.step under bf16 autocast, over every device jax reports
           (dp_degree = device count);
- server:  ServingEngine on device 0 answering mixed requests, checked
           against model.generate().

Any failed check or exception ends the run with a non-zero exit code. The
last line of standard output is one JSON object with the device as JAX
reports it. There is no CPU mode and no small mode here: off a TPU it exits
non-zero before building a model. The phase functions take their sizes as
arguments so that tests/test_chip_rules.py can rehearse the control flow at
gpt_tiny on the CPU.

The only thing written is the persistent compile cache: the directory
JAX_COMPILATION_CACHE_DIR names, else <checkout>/.jax_cache
(paddle_tpu/core/compile_cache.py).
"""
from __future__ import annotations

import json
import math
import sys
import time

import numpy as np

_T0 = time.perf_counter()


def require(ok, what: str) -> None:
    """A failed check ends the run (assert would vanish under -O)."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def say(phase: str, facts: dict) -> None:
    print(f"[{phase}] " + json.dumps(facts, sort_keys=True), flush=True)


def device_facts() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def kernel_phase(batch: int, heads: int, seq: int, head_dim: int) -> dict:
    """The flash-attention entry the model calls, on [b, s, h, d] bf16
    inputs, causal, vs dense attention: forward and dq/dk/dv. On a TPU these
    are the compiled Mosaic kernels of the path the shape takes; on the CPU
    rehearsal, Pallas interpret mode."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.flash_attention import (
        _path, flash_attention, supported)

    require(supported(seq, seq, head_dim),
            f"flash kernel does not take seq={seq} head_dim={head_dim}")
    rng = np.random.RandomState(0)
    q, k, v, g = (jnp.asarray(rng.randn(batch, seq, heads, head_dim),
                              jnp.bfloat16) for _ in range(4))
    scale = 1.0 / math.sqrt(head_dim)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, sm_scale=scale)

    def dense(q, k, v):
        q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        s = jnp.where(jnp.tril(jnp.ones((seq, seq), bool)), s, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)

    def fwd_and_grads(fn):
        out, vjp = jax.vjp(fn, q, k, v)
        return (out,) + vjp(g.astype(out.dtype))

    got = jax.jit(lambda: fwd_and_grads(flash))()
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda: fwd_and_grads(dense))()
    # Tolerance: both sides read the same bf16 inputs; the reference then
    # works in f32 throughout, while the kernel rounds P (and dS in the
    # backward) to bf16 before the second matmul and rounds its results to
    # bf16 on the way out. Each rounding is at most 2^-8 relative, the
    # backward stacks three of them plus the cancellation in (dP - delta),
    # so the bound is 2e-2 of the largest reference magnitude per tensor.
    errs = {}
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        a = np.asarray(a.astype(jnp.float32))
        b = np.asarray(b)
        require(np.isfinite(a).all(), f"flash {name} is not finite")
        errs[name] = float(np.abs(a - b).max() / np.abs(b).max())
        require(errs[name] <= 2e-2,
                f"flash {name} differs from dense attention by "
                f"{errs[name]:.3e} of max|ref| (bound 2e-2)")
    return {"shape": [batch, seq, heads, head_dim],
            "path": _path(heads, head_dim, seq, seq, q.dtype)[0],
            "rel_err_vs_dense": errs}


def train_phase(cfg, batch_per_chip: int, seq: int, *, warmup: int = 2,
                steps: int = 5, mp_degree: int = 1, **engine_kw) -> dict:
    """A few optimizer steps of the model over every device: dp_degree =
    device count / mp_degree, global batch = batch_per_chip * dp_degree.
    engine_kw reaches fleet.distributed_engine (fsdp=True, zero_update=True).
    """
    import jax

    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    from paddle_tpu.core import monitor
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.mesh import set_hybrid_communicate_group
    from paddle_tpu.models import GPTForPretraining

    devs = jax.devices()
    dp = len(devs) // mp_degree
    batch = batch_per_chip * dp
    paddle.seed(0)
    set_hybrid_communicate_group(None)
    strategy = dist.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": dp, "mp_degree": mp_degree}
    fleet.init(is_collective=True, strategy=strategy)
    model = GPTForPretraining(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters(),
                                 weight_decay=0.01)
    engine = fleet.distributed_engine(model, opt, **engine_kw)

    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int64)
    t_ids = paddle.to_tensor(ids)
    t_labels = paddle.to_tensor(np.roll(ids, -1, 1))

    cold0 = monitor.stat("engine.compile_cold").get()
    warm0 = monitor.stat("engine.compile_warm").get()
    losses = []
    with paddle.amp.auto_cast(dtype="bfloat16"):
        for i in range(warmup + steps):
            if i == warmup:
                compiles_after_warmup = monitor.stat(
                    "engine.jit_compiles").get()
            losses.append(float(engine.step(t_ids, t_labels).item()))
            if i == 0:
                first_step_s = time.perf_counter() - _T0
    require(all(math.isfinite(x) for x in losses), f"losses {losses}")
    require(abs(losses[0] - math.log(cfg.vocab_size)) < 1.0,
            f"first loss {losses[0]:.4f} is not near ln(vocab) = "
            f"{math.log(cfg.vocab_size):.4f}")
    require(losses[-1] < losses[0], f"loss did not fall: {losses}")
    recompiles = monitor.stat("engine.jit_compiles").get() \
        - compiles_after_warmup
    require(recompiles == 0, f"{recompiles} compile(s) after warm-up")

    # where everything lives: every array of the step on the mesh's devices,
    # each device holding the shard its strategy says
    mesh_devs = set(engine.mesh.devices.flat)
    require(mesh_devs == set(devs), "mesh does not span jax.devices()")
    state = {"params": engine.params, "opt_state": engine.opt_state,
             "zero_opt": engine._zero_opt, "fsdp_params": engine._fsdp_params,
             "fsdp_opt": engine._fsdp_opt}
    shards = {}
    for name, tree in state.items():
        leaves = jax.tree_util.tree_leaves(tree)
        if not leaves:
            continue
        for leaf in leaves:
            require(set(leaf.devices()) <= mesh_devs,
                    f"{name} leaf lives off the mesh: {leaf.devices()}")
        big = max(leaves, key=lambda a: a.size)
        shards[name] = {
            "leaves": len(leaves), "largest_global": list(big.shape),
            "largest_per_device": list(big.addressable_shards[0].data.shape),
            "bytes_per_device": [
                sum(s.data.nbytes for leaf in leaves
                    for s in leaf.addressable_shards if s.device == d)
                for d in devs]}
    shards["batch"] = {
        "global": list(ids.shape),
        "per_device": list(engine._batch_shardings[0].shard_shape(ids.shape))}
    require(shards["batch"]["per_device"][0] == batch // dp,
            f"batch is not split {dp} ways: {shards['batch']}")

    # what the compiled step contains, read from its per-device text (one
    # AOT compile of the stashed step, served by the persistent cache)
    from paddle_tpu import analysis

    (prog,) = analysis.programs_from_stash(engine._exec_stash)
    flash = prog.custom_calls("tpu_custom_call")
    if devs[0].platform == "tpu":
        # forward, dK/dV and dQ kernels of every layer
        require(len(flash) >= 3 * cfg.num_layers,
                f"{len(flash)} Mosaic calls in {prog.label}, want >= "
                f"{3 * cfg.num_layers}: attention is not on the flash kernel")
        rows = batch_per_chip * cfg.num_heads // mp_degree
        for ins, operands in flash:
            # q, k, v (and dO) in bf16; the backward's lse/delta rows in f32
            require(operands[0].startswith(f"bf16[{rows},{seq},") and all(
                t.startswith((f"bf16[{rows},", f"f32[{rows},"))
                for t in operands),
                f"flash call {ins.name} runs on {operands}, want bf16 with "
                f"the per-device leading dim {rows}")
    else:
        require(not flash, "Mosaic call in a program compiled off the TPU")
    collectives = {k: prog.count_ops(k) for k in (
        "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
        "collective-permute")}
    report = analysis.PassManager().run([prog], engine.default_contracts())
    require(report.ok, f"analysis violations: {report.violations}")
    return {
        "label": prog.label, "dp": dp, "mp": mp_degree, "batch": batch,
        "seq": seq, "losses": [round(x, 4) for x in losses],
        "first_step_done_s": round(first_step_s, 1),
        "compile_cold": monitor.stat("engine.compile_cold").get() - cold0,
        "compile_warm": monitor.stat("engine.compile_warm").get() - warm0,
        "flash_calls": len(flash),
        "flash_operands": flash[0][1] if flash else None,
        "collectives": collectives,
        "analysis_skips": sorted({s.pass_name for s in report.skips}),
        "shards": shards,
        "peak_bytes_in_use": [
            (d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs],
    }


def _reference_logits(model, ids):
    """[len, vocab] f32 logits of the model's plain forward (no KV cache, no
    autocast, matmuls at highest precision) — the reference the served
    tokens are judged against."""
    import jax

    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.jit import functional_call

    state = {k: v._data for k, v in model.state_dict(
        include_non_persistable_buffer=True).items()}
    fwd = jax.jit(lambda p, x: functional_call(model, p, Tensor(x))._data)
    with jax.default_matmul_precision("highest"):
        return np.asarray(fwd(state, ids[None]))[0].astype(np.float32)


def serve_phase(cfg, *, slot_count: int = 8, ladder=(64, 128, 256, 512),
                max_new_cap: int = 64, kv_layout: str = "contiguous") -> dict:
    """Mixed requests through ServingEngine on device 0, in two waves, then
    two greedy streams against the model's plain forward and generate()."""
    import paddle_tpu as paddle
    from paddle_tpu.core import monitor
    from paddle_tpu.distributed.mesh import set_hybrid_communicate_group
    from paddle_tpu.models import GPTForPretraining
    from paddle_tpu.serving import ServingEngine

    set_hybrid_communicate_group(None)  # single device: no mesh in scope
    paddle.seed(0)
    model = GPTForPretraining(cfg)
    model.eval()
    rng = np.random.RandomState(1)
    n_new = max_new_cap // 2
    lo, mid, hi = ladder[0], ladder[1], ladder[2]
    sampled = {"temperature": 0.8, "top_k": 50, "top_p": 0.9}
    # (prompt length, sampled?): lengths land on three rungs at least
    plan = [(lo - 3, False), (lo - 3, False), (lo, True), (mid - 5, False),
            (mid, True), (hi - 1, True), (hi // 2 + 1, False)]
    prompts = [rng.randint(0, cfg.vocab_size, (n,)).astype(np.int64)
               for n, _ in plan]

    pre0 = monitor.stat("serving.prefill_compiles").get()
    dec0 = monitor.stat("serving.decode_compiles").get()
    with paddle.amp.auto_cast(dtype="bfloat16"):
        eng = ServingEngine(model, slot_count=slot_count, ladder=ladder,
                            max_new_cap=max_new_cap, kv_layout=kv_layout)
        reqs = [eng.submit(p, max_new_tokens=n_new, seed=i,
                           **(sampled if smp else {"temperature": 0.0}))
                for i, (p, (_, smp)) in enumerate(zip(prompts, plan))]
        eng.run()
        # Second wave, admitted into retired slots: the first greedy prompt
        # again, with the token it emitted third as EOS — it must stop
        # there. A sampled companion keeps the dispatch on the same decode
        # executable as the first wave (an all-greedy slot set runs a
        # second, slimmer one, and two differently fused bf16 programs need
        # not agree bit for bit on near-tied logits).
        eos = reqs[0].tokens[2]
        cut = reqs[0].tokens.index(eos) + 1
        early = eng.submit(prompts[0], max_new_tokens=n_new, temperature=0.0,
                           eos_token_id=eos)
        eng.submit(prompts[2], max_new_tokens=n_new, seed=2, **sampled)
        eng.run()
        generated = [
            model.generate(paddle.to_tensor(prompts[i][None]),
                           max_new_tokens=n_new, temperature=0).numpy()[0]
            for i in (0, 1)]

    for r in reqs:
        require(r.done and r.outcome == "length" and len(r.tokens) == n_new,
                f"{r!r}: outcome {r.outcome}, {len(r.tokens)} tokens, "
                f"budget {n_new}")
        require(all(0 <= t < cfg.vocab_size for t in r.tokens),
                f"{r!r}: token outside the vocabulary")
    require(early.done and early.outcome == "eos"
            and early.tokens == reqs[0].tokens[:cut],
            f"EOS request: outcome {early.outcome}, tokens {early.tokens}, "
            f"want {reqs[0].tokens[:cut]}")
    rungs = sorted({r.bucket for r in reqs})
    require(len(rungs) >= 3, f"prompts landed on rungs {rungs} only")
    prefill = monitor.stat("serving.prefill_compiles").get() - pre0
    decode = monitor.stat("serving.decode_compiles").get() - dec0
    require(prefill <= len(rungs), f"{prefill} prefill compiles for "
                                   f"{len(rungs)} rungs")
    require(decode <= 2, f"{decode} decode compiles")

    # Are the served tokens right? Each greedy token must be the argmax of
    # the model's plain f32 forward over the same prefix, up to the noise of
    # computing in bf16: within a tenth of that position's (max - mean)
    # logit spread. bf16 rounding moves a logit by about a hundredth of the
    # spread; a wrong cache row, offset or mask lands on a typical token, a
    # whole spread away. With random weights the top logits are nearly tied,
    # so exact token equality with generate() — a third, differently fused
    # program — is reported, and where the two part ways generate()'s token
    # must pass the same test on the shared prefix.
    def gap(logits, t, tok):
        row = logits[t - 1]
        return float((row.max() - row[tok]) / (row.max() - row.mean()))

    worst, agree = 0.0, []
    for r, gen in zip(reqs[:2], generated):
        out, plen = r.output_ids(), len(r.prompt_ids)
        logits = _reference_logits(model, out)
        for t in range(plen, len(out)):
            worst = max(worst, gap(logits, t, out[t]))
            require(gap(logits, t, out[t]) <= 0.1,
                    f"{r!r}: new token {t - plen} sits {gap(logits, t, out[t]):.3f} "
                    f"of the logit spread below the reference argmax")
        same = out == gen
        if same.all():
            agree.append("all")
            continue
        t = int(np.argmin(same))
        agree.append(f"first {t - plen} of {n_new}")
        require(gap(logits, t, gen[t]) <= 0.1,
                f"{r!r}: generate() parts ways at new token {t - plen} with "
                f"a token {gap(logits, t, gen[t]):.3f} of the spread below "
                f"the reference argmax")
    return {"kv_layout": kv_layout, "requests": len(reqs) + 2,
            "rungs": rungs, "tokens": [len(r.tokens) for r in reqs],
            "eos_tokens": len(early.tokens),
            "prefill_compiles": prefill, "decode_compiles": decode,
            "worst_gap_to_reference_argmax": round(worst, 4),
            "generate_same_tokens": agree,
            "decode_steps": eng.stats()["steps"]}


def main() -> int:
    dev = device_facts()
    print(f"platform={dev['platform']} device_kind={dev['kind']} "
          f"device_count={dev['count']}", flush=True)
    if dev["platform"] != "tpu":
        print("chip_smoke: JAX found no TPU; this check has no other mode",
              file=sys.stderr)
        return 1

    from bench import bench_config
    from paddle_tpu.core import compile_cache

    cfg, batch, seq, _, _ = bench_config()
    say("start", {"compile_cache_dir": compile_cache.cache_dir(),
                  "cache_entries_at_start": compile_cache.entries(),
                  "jax_ready_s": round(time.perf_counter() - _T0, 1)})
    say("kernel", kernel_phase(batch, cfg.num_heads, seq,
                               cfg.hidden_size // cfg.num_heads))
    say("train", train_phase(cfg, batch, seq))
    say("serve", serve_phase(cfg))
    say("end", {"cache_entries_at_end": compile_cache.entries(),
                "total_s": round(time.perf_counter() - _T0, 1)})
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
