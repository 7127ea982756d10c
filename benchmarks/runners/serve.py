"""Runner `serve`: requests through ServingEngine.submit / step on one chip,
driven by ONE thread: submit what is due, step, repeat. `step()` blocks for
a whole dispatch, so a request is submitted late by up to that long; every
latency is therefore taken from the instant the request was DUE, and the
lateness is reported (`serve.submit_lag_p95_ms`).

Two loops, chosen by the traffic file's arrival process:

- open (`poisson`, `spike`): a lead-in at the cell's rate fills the slots,
  requests due inside the window are the sample, load goes on at the same
  rate while the run drains for at most `drain_s`; a sampled request that is
  unfinished then has failed and ranks as the worst.
- closed (`backlog`): the queue is kept `depth` deep, the window starts and
  ends on a dispatch boundary, and output tokens emitted in between are
  counted. Nothing is drained.

Set-up: weights from the seed, the engine under bf16 autocast, one greedy
request on every prefill rung judged against the plain reference, two sampled
requests so that the `sample` decode program exists too.
"""
from __future__ import annotations

import gc
import math
import time

from benchmarks.lib import reference, stats, traffic as traffic_lib
from benchmarks.runners import common

ANNOTATIONS = ("submit", "serve_step", "wait")
COUNTERS = ("serving.prefill_compiles", "serving.decode_compiles",
            "serving.prefill_dispatches", "serving.tokens",
            "engine.compile_cold", "engine.compile_warm",
            "engine.compile_cold_ms", "engine.compile_warm_ms")

# A served greedy token must lie within this share of its position's
# (max - mean) reference logit spread of the reference's argmax. The engine
# computes in bf16, which moves a logit by about a hundredth of the spread
# (0.003-0.005 measured on the chip, PERF.md PR 21 and PR 25); a wrong cache
# row, offset or mask lands on a typical token, a whole spread away. With
# random weights the top logits are nearly tied, so equality of tokens would
# fail on rounding alone (chip_smoke.py's test, against this reference).
GAP_TOLERANCE = 0.1
CHECK_NEW_TOKENS = 16


class ListSink:
    """The engine's telemetry sink, kept in memory."""

    def __init__(self):
        self.records = []

    def write(self, rec: dict) -> None:
        self.records.append(rec)

    def close(self) -> None:
        pass


def check_greedy(eng, model, vocab: int, seed: int) -> dict:
    """One greedy request just under every rung; each new token against the
    reference's logits over the same prefix."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    cfg = model.config
    rng = np.random.default_rng(int(seed) + 1)
    prompts = [rng.integers(0, vocab, (max(1, rung - 3),), dtype=np.int64)
               for rung in eng.ladder]
    reqs = [eng.submit(p, max_new_tokens=CHECK_NEW_TOKENS, temperature=0.0)
            for p in prompts]
    eng.run()
    n_new = min(len(r.tokens) for r in reqs)
    width = -(-max(len(r.output_ids()) for r in reqs) // 8) * 8
    ids = np.zeros((len(reqs), width), np.int64)
    pos = np.zeros((len(reqs), n_new), np.int64)
    for k, r in enumerate(reqs):
        out = r.output_ids()
        ids[k, :len(out)] = out
        pos[k] = len(r.prompt_ids) - 1 + np.arange(n_new)
    fn = jax.jit(lambda st, x, p: reference.logits_at(
        st, x, p, cfg.num_layers, cfg.num_heads))
    logits = np.asarray(fn(common.state_arrays(model), jnp.asarray(ids),
                           jnp.asarray(pos)))
    worst, ok = 0.0, all(r.done and r.outcome == "length" for r in reqs)
    for k, r in enumerate(reqs):
        for j in range(n_new):
            row = logits[k, j]
            gap = float((row.max() - row[r.tokens[j]])
                        / (row.max() - row.mean()))
            worst = max(worst, gap)
    return {"ok": bool(ok and worst <= GAP_TOLERANCE and n_new > 1),
            "worst_gap": worst, "rungs": list(eng.ladder), "new_tokens": n_new}


def _submit(eng, row, sampling):
    import numpy as np

    return eng.submit(np.asarray(row["prompt"], np.int64),
                      max_new_tokens=row["max_new"], seed=row["seed"],
                      **sampling)


def _timed_step(eng, steps, prefills):
    import jax

    with jax.profiler.TraceAnnotation("serve_step"):
        p0 = prefills.get()
        a = time.perf_counter()
        eng.step()
        b = time.perf_counter()
    steps.append((a, b, prefills.get() - p0))
    return b


def drive_open(eng, rows, sampling, lead_in_s, seconds, drain_s, ctx,
               trace_s):
    """Returns (records, steps, w0, w1, end)."""
    import jax

    from paddle_tpu.core import monitor

    prefills = monitor.stat("serving.prefill_dispatches")
    tracer, tracing = ctx.tracer, False
    handles, steps, i = [], [], 0
    t0 = time.perf_counter()
    w0 = t0 + lead_in_s
    w1 = w0 + seconds
    started = False
    while True:
        now = time.perf_counter()
        if not started and now >= w0:
            ctx.start_window(w0)
            started = True
        if tracer is not None and not tracing and now >= w1 - trace_s:
            tracer.start()
            tracing = True
        with jax.profiler.TraceAnnotation("submit"):
            while i < len(rows) and t0 + rows[i]["due"] <= now:
                handles.append((rows[i], _submit(eng, rows[i], sampling)))
                i += 1
        if now >= w1:
            if tracing:
                tracer.stop()
                tracing, tracer = False, None
            if now >= w1 + drain_s or all(
                    r.done for row, r in handles
                    if w0 <= t0 + row["due"] < w1):
                break
        if eng.queue_depth() == 0 and eng.occupancy() == 0.0:
            with jax.profiler.TraceAnnotation("wait"):
                nxt = t0 + rows[i]["due"] if i < len(rows) else now + 0.001
                time.sleep(max(0.0, min(0.001, nxt - now)))
            continue
        _timed_step(eng, steps, prefills)
    end = time.perf_counter()
    rows_of_vocab = eng.model.config.vocab_size
    records = []
    for row, r in handles:
        due = t0 + row["due"]
        records.append({
            "due": due, "in_window": w0 <= due < w1,
            "submit": r.submit_ts, "admit": r.admit_ts,
            "first": r.first_token_ts, "done": r.done_ts,
            "tokens": len(r.tokens), "outcome": r.outcome,
            "ok": bool(r.done and r.outcome in ("ok", "eos", "length")
                       and len(r.tokens) == r.max_new_tokens),
            "token_range_ok": all(0 <= t < rows_of_vocab for t in r.tokens)})
    return records, steps, w0, w1, end


def drive_backlog(eng, rows, sampling, depth, lead_in_s, seconds, ctx,
                  trace_s):
    """Returns (handles, steps, w0, w1, tokens emitted in [w0, w1])."""
    from paddle_tpu.core import monitor

    prefills = monitor.stat("serving.prefill_dispatches")
    tracer, tracing = ctx.tracer, False
    handles, steps = [], []

    def top_up():
        while eng.queue_depth() < depth:
            if len(handles) >= len(rows):
                raise RuntimeError(
                    f"the backlog of {len(rows)} requests ran out: raise "
                    f"arrival.max_rps in the traffic file")
            handles.append(_submit(eng, rows[len(handles)], sampling))

    def emitted():
        return sum(len(r.tokens) for r in handles)

    t0 = now = time.perf_counter()
    while now - t0 < lead_in_s:
        top_up()
        now = _timed_step(eng, steps, prefills)
    w0, tok0, first = ctx.start_window(now), emitted(), len(steps)
    while now - w0 < seconds:
        if (tracer is not None and not tracing
                and now - w0 >= seconds - trace_s):
            tracer.start()
            tracing = True
        top_up()
        now = _timed_step(eng, steps, prefills)
    w1, tok1 = now, emitted()
    if tracing:
        tracer.stop()
    return handles, steps[first:], w0, w1, tok1 - tok0


def build_engine(ctx):
    """Set-up shared by run() and benchmarks/sweep_rate.py: the model, the
    engine, the greedy check, the sampled warm-up. Must be called, and the
    engine driven, inside `paddle.amp.auto_cast(dtype="bfloat16")`."""
    from paddle_tpu.serving import ServingEngine

    if ctx.chips != 1:
        raise ValueError("runner `serve` drives one engine on one chip")
    counters = common.Counters(COUNTERS)
    model = common.build_model(ctx.config, ctx.seed)
    model.eval()
    sink = ListSink() if ctx.trace else None
    eng_kw = dict(ctx.cell["engine"])
    eng_kw["ladder"] = tuple(eng_kw["ladder"])
    eng = ServingEngine(model, sink=sink, **eng_kw)
    check = check_greedy(eng, model, int(ctx.config["vocab_size"]), ctx.seed)
    warm = [eng.submit([1, 2, 3], max_new_tokens=eng.steps_per_dispatch,
                       seed=k, **ctx.traffic["sampling"]) for k in range(2)]
    eng.run()
    setup_counters = counters.delta()
    ctx.note("setup", {"check": check, "kv_cache_bytes": eng.kv_cache_bytes(),
                       **setup_counters})
    checks = {"greedy_matches_reference": check["ok"],
              "warm_up_finished": all(r.done for r in warm)}
    return eng, sink, counters, setup_counters, checks


def open_loop_metrics(records, end):
    """(sample, failed, ttft ms, tpot ms) of the requests due in the window;
    a failed or unfinished request ranks as the worst of each list."""
    sample = [r for r in records if r["in_window"]]
    good = [r for r in sample if r["ok"]]
    failed = len(sample) - len(good)
    ttft = [(r["first"] - r["due"]) * 1e3 for r in good]
    tpot = [(r["done"] - r["first"]) / (r["tokens"] - 1) * 1e3
            for r in good if r["tokens"] > 1]
    worst_ttft = max(ttft + [(end - r["due"]) * 1e3 for r in sample
                             if not r["ok"]], default=0.0)
    ttft += [worst_ttft] * failed
    tpot += [max(tpot, default=0.0)] * failed
    return sample, failed, ttft, tpot


def run(ctx) -> dict:
    import paddle_tpu as paddle

    traf = ctx.traffic
    vocab = int(ctx.config["vocab_size"])
    sampling = dict(traf["sampling"])
    trace_s = float(ctx.cell.get("trace_seconds", 3.0))
    arrival = traf["arrival"]
    closed = arrival["process"] == "backlog"
    lead_in_s = float(traf.get("lead_in_s", 0.0))
    drain_s = float(traf.get("drain_s", 0.0))

    with paddle.amp.auto_cast(dtype="bfloat16"):
        eng, sink, counters, setup_counters, checks = build_engine(ctx)
        rows = traffic_lib.requests(
            traf, ctx.seed, ctx.seconds, vocab,
            count=(math.ceil(float(arrival["max_rps"])
                             * (lead_in_s + ctx.seconds)) if closed else 0))
        if sink is not None:
            sink.records.clear()
        gc.collect()
        gc.freeze()        # set-up's objects are not scanned in the window
        counters.mark()
        if closed:
            handles, steps, w0, w1, tokens = drive_backlog(
                eng, rows, sampling, int(arrival["depth"]), lead_in_s,
                ctx.seconds, ctx, trace_s)
        else:
            records, steps, w0, w1, end = drive_open(
                eng, rows, sampling, lead_in_s, ctx.seconds, drain_s, ctx,
                trace_s)
        run_counters = counters.delta()

    checks["no_compile_after_set_up"] = (
        run_counters["serving.prefill_compiles"]
        + run_counters["serving.decode_compiles"]) == 0
    collected = {
        "steps": steps, "steps_per_dispatch": eng.steps_per_dispatch,
        "window": (w0, w1),
        # sink records carry time.time(); the window is on perf_counter
        "wall_minus_perf": time.time() - time.perf_counter(),
        "sink": sink.records if sink is not None else [],
        "setup_counters": setup_counters, "run_counters": run_counters,
    }
    if closed:
        touched = [r for r in handles
                   if r.first_token_ts is not None and r.first_token_ts < w1
                   and (r.done_ts is None or r.done_ts > w0)]
        attempted = len(touched)
        failed = sum(1 for r in touched if r.outcome in ("error", "drained"))
        end_to_end = {"serve_tokens_per_s": tokens / (w1 - w0)}
        ctx.note("window", {"seconds": w1 - w0, "tokens": tokens,
                            "requests_touched": attempted,
                            "dispatches": len(steps), "checks": checks,
                            **run_counters})
    else:
        sample, failed, ttft, tpot = open_loop_metrics(records, end)
        attempted = len(sample)
        end_to_end = {"ttft_p95_ms": stats.percentile(ttft, 0.95),
                      "tpot_p95_ms": stats.percentile(tpot, 0.95)}
        checks["tokens_in_vocabulary"] = all(r["token_range_ok"]
                                             for r in records)
        collected["requests"] = records
        ctx.note("window", {
            "sample": attempted, "failed": failed, "submitted": len(records),
            "drain_s": end - w1, "ttft_p50_ms": stats.median(ttft),
            "tpot_p50_ms": stats.median(tpot), "dispatches": len(steps),
            "checks": checks, **run_counters})
    return {"correct": all(checks.values()), "attempted": attempted,
            "failed": failed, "end_to_end": end_to_end,
            "collected": collected}
