"""Runner `train`: optimizer steps of a decoder LM through the entry points a
user calls: fleet.init -> fleet.distributed_engine -> engine.step under bf16
autocast, dp_degree = the cell's chips, `engine_kw` from the cell's file
(`{"fsdp": true}` makes the same runner an FSDP cell).

Set-up: weights from the seed, `distinct_batches` batches from the seed, the
plain reference's loss on batch 0 (before the engine exists, because the
step donates the weights), the first step (which compiles, or loads from the
persistent cache), one step on every other batch. The window then runs
sub-windows of `steps_per_sync` steps, each ended by fetching a loss two steps
back, until `seconds` have passed, and ends when the last step's loss is
fetched; batches are fed in turn, one host-to-device copy a step. After the
window batch 0 is visited once more.
"""
from __future__ import annotations

import math
import time

from benchmarks.lib import reference, traffic as traffic_lib
from benchmarks.runners import common

ANNOTATIONS = ("feed", "engine_step", "wait")
COUNTERS = ("engine.jit_compiles", "engine.compile_cold",
            "engine.compile_warm", "engine.compile_cold_ms",
            "engine.compile_warm_ms")

# |first-step loss - reference loss| allowed, as a share of the reference's
# SIGNAL, |reference loss - ln(vocabulary rows)|. With random weights the
# logits are small: the loss is ln(50304) = 10.826 plus a signal of about 0.02
# that the model's structure decides, so an absolute tolerance in nats would
# pass a wrong model. The step computes its matmuls in bf16 from f32 weights,
# the reference in f32 throughout. Measured on the chip at GPT-2 medium, two
# seeds: 1.9e-6 of a 0.022 signal, 0.009% (my chip run, PR 25); at gpt_tiny on
# the CPU 0.2%. 1% is five times the larger; a wrong mask, label shift or
# head moves the signal by tens of percent. That training moves the weights
# the right way is the other check (batch 0's loss falls by about a nat).
SIGNAL_TOLERANCE = 0.01
SYNC_LAG = 2       # steps the host stays ahead of the loss it fetches


def run(ctx) -> dict:
    import jax
    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed import fleet

    cell, config, traf = ctx.cell, ctx.config, ctx.traffic
    n_chips = ctx.chips
    per_sync = int(cell.get("steps_per_sync", 4))
    trace_s = float(cell.get("trace_seconds", 3.0))

    model = common.build_model(config, ctx.seed)
    cfg = model.config
    n_params = int(sum(p.size for p in model.parameters()))
    data = traffic_lib.batches(traf, ctx.seed, int(config["vocab_size"]),
                               n_chips)
    batch, seq = data[0][0].shape

    ref_fn = jax.jit(lambda st, x, y: reference.loss_per_sequence(
        st, x, y, cfg.num_layers, cfg.num_heads))
    ref_loss = float(np.mean(np.asarray(
        ref_fn(common.state_arrays(model), *data[0]))))
    del ref_fn

    counters = common.Counters(COUNTERS)
    strategy = dist.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": n_chips, "mp_degree": 1}
    fleet.init(is_collective=True, strategy=strategy)
    opt = paddle.optimizer.AdamW(learning_rate=float(cell["learning_rate"]),
                                 parameters=model.parameters(),
                                 weight_decay=float(cell["weight_decay"]))
    engine = fleet.distributed_engine(model, opt,
                                      **cell.get("engine_kw", {}))

    def feed(i):
        with jax.profiler.TraceAnnotation("feed"):
            ids, labels = data[i % len(data)]
            return paddle.to_tensor(ids), paddle.to_tensor(labels)

    def step(i):
        x, y = feed(i)
        with jax.profiler.TraceAnnotation("engine_step"):
            return engine.step(x, y)

    losses = []
    with paddle.amp.auto_cast(dtype="bfloat16"):
        first_loss = float(step(0).item())
        losses.append(first_loss)
        for i in range(1, len(data)):
            losses.append(float(step(i).item()))
        setup_counters = counters.delta()
        counters.mark()
        ctx.note("setup", {"first_loss": first_loss, "reference_loss": ref_loss,
                           "n_params": n_params, "batch": batch, "seq": seq,
                           **setup_counters})

        tracer, tracing = ctx.tracer, False
        windows = []                      # (seconds, steps) per sub-window
        pending = []                      # losses not fetched yet
        n = len(data)                     # next step's index; batch = n % 4
        t_start = ctx.start_window()
        t_sub = t_start
        while t_sub - t_start < ctx.seconds:
            if (tracer is not None and not tracing
                    and t_sub - t_start >= ctx.seconds - trace_s):
                tracer.start()
                tracing = True
            for _ in range(per_sync):
                pending.append(step(n))
                n += 1
            # a sub-window ends by fetching a loss from SYNC_LAG steps back,
            # so the device's queue never drains: a fetch of the newest loss
            # would expose the host's dispatch time once a sub-window, and
            # with it every hiccup of a shared host (3% of a run, PERF.md)
            k = max(0, len(pending) - 1 - SYNC_LAG)
            with jax.profiler.TraceAnnotation("wait"):
                losses.append(float(pending[k].item()))
            del pending[:k + 1]
            now = time.perf_counter()
            windows.append((now - t_sub, per_sync))
            t_sub = now
        with jax.profiler.TraceAnnotation("wait"):
            losses.append(float(pending[-1].item()))  # the window's end
        t_end = time.perf_counter()
        if tracing:
            tracer.stop()
        window_counters = counters.delta()
        # batch 0 once more: training on four batches must have lowered it
        while n % len(data):
            step(n)
            n += 1
        last_loss = float(step(n).item())
        losses.append(last_loss)

    steps = sum(k for _, k in windows)
    tokens_per_s = steps * batch * seq / (t_end - t_start)
    checks = {
        "loss_matches_reference": abs(first_loss - ref_loss)
        <= SIGNAL_TOLERANCE * abs(ref_loss - math.log(cfg.vocab_size)),
        "losses_finite": all(math.isfinite(x) for x in losses),
        "batch0_loss_fell": last_loss < first_loss,
        "no_compile_in_window": window_counters["engine.jit_compiles"] == 0,
    }
    step_ms = sorted(dt / k * 1e3 for dt, k in windows)
    ctx.note("window", {"steps": steps, "seconds": t_end - t_start,
                        "step_ms_min_p50_max": [step_ms[0],
                                                step_ms[len(step_ms) // 2],
                                                step_ms[-1]],
                        "first_loss": first_loss, "last_loss_batch0": last_loss,
                        "loss_minus_reference": first_loss - ref_loss,
                        "checks": checks, **window_counters})
    return {
        "correct": all(checks.values()),
        "attempted": steps,
        "failed": sum(1 for x in losses if not math.isfinite(x)),
        "end_to_end": {"train_tokens_per_s": tokens_per_s / n_chips},
        "collected": {
            "windows": windows, "tokens_per_s_per_chip": tokens_per_s / n_chips,
            "n_params": n_params, "num_layers": cfg.num_layers,
            "num_heads": cfg.num_heads, "hidden": cfg.hidden_size,
            "batch_per_chip": batch // n_chips, "seq": seq,
            "setup_counters": setup_counters,
            "window_counters": window_counters,
        },
    }
