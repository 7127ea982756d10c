"""Runner `serve_afmoe`: an `afmoe` configuration (Arcee Trinity) through
ServingEngine.submit / step on one chip, under a closed backlog. The loop,
the window, the counting and `serve_tokens_per_s` are runner `serve`'s own
code (`drive_backlog`, `_timed_step`, `ListSink`, its counters): this file
only builds the model and its check.

Set-up: weights drawn on the device from the seed straight into the
configuration's dtype (no float32 copy, no autocast: the model computes in
the dtype its weights have), the engine, one greedy request just under every
prefill rung judged against the plain reference
(benchmarks/lib/reference_afmoe.py) at the published widths, two sampled
requests so that the `sample` decode program exists too.

The check. Each greedy request generates 16 tokens: prefill, then decode
through the window layers' rings and the full layer's cache. The rungs are
512 / 1,024 / 2,048 / 3,072 / 3,584, so one request's context passes 2,048
DURING decode (2,045 + 16) and two prompts are longer than 2,048 (the ring is
filled by prefill). The reference runs its full forward pass over each
request's whole output, a layer at a time, upcasting the served bf16 weights
as it goes (8 experts at a time), and every served token is judged against
the reference's logits over the same prefix; then every row the five slots
hold, in the rings and in the full layer's cache, against the reference's
keys and values of the positions the rows should hold.
"""
from __future__ import annotations

import gc
import math
import time

from benchmarks.lib import reference_afmoe as reference
from benchmarks.lib import traffic as traffic_lib
from benchmarks.runners import common
from benchmarks.runners.serve import (ANNOTATIONS, CHECK_NEW_TOKENS,  # noqa: F401
                                      COUNTERS, ListSink, drive_backlog)

try:
    from paddle_tpu.models import AfmoeConfig, AfmoeForCausalLM
except ImportError as e:            # a program from before the model
    raise SystemExit(f"runner serve_afmoe: this program has no afmoe model "
                     f"({e})")

# The check has two measures, and `correct` needs both (PERF.md §6, PR 28,
# has every reading; benchmarks/tests/controls_afmoe.py reads them again).
#
# 1. The tokens, by runner `serve`'s measure: a served greedy token's gap is
# how far the reference's logit of that token lies under the reference's
# maximum at that position, as a share of the position's (max - mean) logit
# spread. 0 is the reference's own argmax, 1 a typical token. What moves a
# served token: bf16 arithmetic, as in GPT-2 (there 0.003 to 0.005 of the
# spread), and, new here, a routing flip: in about a tenth of the (token,
# expert layer) pairs the reference's 8th and 9th biased scores lie closer
# than 1e-3 (`margin_under_1e-3` in the set-up note, 0.07 to 0.12), which is
# what bf16 moves them by, so the program chooses the other expert. That
# replaces an eighth of the routed output by an unrelated vector, about a
# tenth of the token's hidden state, which moves the scores of every later
# expert layer by more than their margins: one flip cascades. Such a token's
# hidden state is 20 to 40% off and its gap 0.05 to 0.2; the others' is 0.
# So the WORST gap is heavy-tailed (0.018 to 0.208 over 43 seeds on the chip,
# over 0.1 in half of them) and the MEAN over the 80 tokens is what
# separates: served 0.0004 to 0.0101 over 53 seeds.
# MEAN_GAP_TOLERANCE lies 2.5 times above that (the served sum of gaps is a
# few cascades of about 0.1 each, so its tail is long, and a false alarm
# refuses a PR). GAP_TOLERANCE catches what the mean cannot, one token
# computed from a wrong row or offset: such a token reads about 1 (1.02
# measured, below), which lifts the mean of 80 by 0.0125 only; the limit
# lies above every cascade seen.
#
# 2. The rows the slots hold, which a flip does not swamp: after the greedy
# requests every slot's caches are read back and each row is compared with
# the reference's key and value of the position the row should hold (row
# `p % rows` of a window layer's ring, row p of the full layer's cache), as
# |served - reference| / |reference| over the row. A layer's rows follow
# from the stream BELOW it, so the layers up to the first expert layer hold
# rows that no routing choice has touched: there EVERY row is held to
# ROW_TOLERANCE. Above, a flipped token's row is 20 to 40% off, so the
# MEDIAN row of each layer of each request is held to ROW_MEDIAN_TOLERANCE.
# This sees the ring (which position a row holds after prefill's gather and
# after decode's writes at `position % 2048`), RoPE where it does not belong
# and the q/k norm directly, and everything below a layer through the
# stream: a window edge off by one moves the first expert layer's rows at
# positions past 2,048 (one key more among 2,048 nearly equal weights moves
# an average of 2,048 random vectors by 2%).
#
# The readings (chip, seed 2147485001; controls_afmoe.py; served: ten
# seeds, 2147485001 to 2147485161), each control through `check_greedy`:
#
#                            mean gap  worst gap  worst row   median row
#   served                   .0021-.0082 .06-.20  .0079-.0082 .0115-.0141
#   float8 e4m3 reference    .0302     .259       .119        .273
#   a token from another row .0515     1.020      as served   as served
#   window off by one        .0083     .105       .0721       .0177
#   RoPE on the full layer   .0061     .089       as served   1.325
#   gate dropped             .1921     .521       .391        .574
#   q/k norm dropped         .0687     .264       .353        .364
#   `route_scale` dropped    .0801     .401       as served   .349
#   key head `i % 4`         .8432     1.376      1.165       1.313
#   bias used as a weight    .0076     .153       as served   .0140
#
# Each limit lies between the served readings and the lowest control it is
# there for, about three times from either: ROW_TOLERANCE between .0082 and
# the window edge's .0721; ROW_MEDIAN_TOLERANCE between .0141 and float8's
# .273 (3.5 and 5.5 times); MEAN_GAP_TOLERANCE between .0101 (53 seeds) and
# float8's .0302 (it has less room below float8 than the rows have: the
# rows are what holds float8 out). The bias used as a weight moves a token's
# state by half a percent, which is what bf16 moves it by: it stays unseen
# on the chip, and tests/test_afmoe.py sees it in float32 at 1e-4.
GAP_TOLERANCE = 0.5
MEAN_GAP_TOLERANCE = 0.025
ROW_TOLERANCE = 0.025
ROW_MEDIAN_TOLERANCE = 0.05
MARGIN_NOTE = 1e-3


def build_model(config: dict, seed: int):
    import paddle_tpu as paddle
    from paddle_tpu.distributed.mesh import set_hybrid_communicate_group

    set_hybrid_communicate_group(None)
    paddle.seed(int(seed))
    model = AfmoeForCausalLM(AfmoeConfig.from_dict(config))
    model.eval()
    return model


_PROGRAMS = {}      # the reference's compiled pieces, one a kind of layer


def _program(key, fn):
    if key not in _PROGRAMS:
        import jax

        _PROGRAMS[key] = jax.jit(fn)
    return _PROGRAMS[key]


def reference_outputs(state: dict, config: dict, ids, positions,
                      lower=None):
    """One request: `ids` [s] (right-padded; the pad is inert for the
    positions before it), `positions` [n] -> ([n, vocab] float32 logits,
    the margins [expert layers, n] of the routing at those positions, the
    rows [(k, v) a layer] that a cache of every position would hold). A
    layer at a time, one compiled program a kind of layer. `lower`, where
    given, rounds every matrix and the stream between the layers to a
    lower precision (the controls' float8 reference)."""
    import jax.numpy as jnp

    def low(p):
        if lower is None:
            return p
        return {k: (lower(v) if v.ndim >= 2 else v) for k, v in p.items()}

    top = low({k: state[k] for k in ("model.embed_tokens.weight",
                                      "lm_head.weight")})
    h = _program("embed", lambda e, i: reference.embed(
        {"model.embed_tokens.weight": e}, i, config))(
        top["model.embed_tokens.weight"], ids)
    margins, rows = [], []
    for l in range(config["num_hidden_layers"]):
        kind = (config["layer_types"][l], l < config["num_dense_layers"])
        if lower is not None:
            h = lower(h)
        h, info = _program(kind, lambda p, x, l=l: reference.layer(
            p, x, l, config))(low(reference.layer_state(state, l)), h)
        rows.append((info["k"], info["v"]))
        if "margin" in info:
            margins.append(info["margin"][positions])
    if lower is not None:
        h = lower(h)
    logits = _program("head", lambda n, w, x: reference.head(
        {"model.norm.weight": n, "lm_head.weight": w}, x, config))(
        state["model.norm.weight"], top["lm_head.weight"], h[positions])
    return logits, jnp.stack(margins), rows


def row_errors(eng, slot: int, held: int, rows):
    """Every row that slot `slot` must still hold of a context of `held`
    positions against the reference's `rows`: -> [[error a position] a
    layer], the larger of the key's and the value's relative error. Row
    `p % rows` holds position p (kv_state.py); the row of position `held`
    itself is left out, an idle slot may have written its tip there."""
    import numpy as np

    out = []
    for l, (k, v) in enumerate(rows):
        size = eng._kcs[l].shape[1]
        at = np.arange(max(0, held + 1 - size), held)
        worst = np.zeros(len(at))
        for mine, ref in ((eng._kcs[l], k), (eng._vcs[l], v)):
            mine = np.asarray(mine[slot], np.float32)[at % size]
            ref = np.asarray(ref, np.float32)[at]
            err = np.sqrt(((mine - ref) ** 2).sum((1, 2))
                          / (ref ** 2).sum((1, 2)))
            worst = np.maximum(worst, err)
        out.append(worst)
    return out


def check_greedy(eng, model, config: dict, seed: int,
                 outputs=reference_outputs) -> dict:
    """One greedy request just under every rung; each new token against the
    reference's logits over the same prefix, and each row its slot holds
    afterwards against the reference's keys and values. `outputs` is the
    reference (the controls put a wrong one there)."""
    import jax.numpy as jnp
    import numpy as np

    if len(eng.ladder) > eng.slot_count:
        raise ValueError("the row check reads every request's slot after "
                         "the run: it needs a slot a rung")
    vocab = int(config["vocab_size"])
    rng = np.random.default_rng(int(seed) + 1)
    prompts = [rng.integers(0, vocab, (max(1, rung - 3),), dtype=np.int64)
               for rung in eng.ladder]
    reqs = [eng.submit(p, max_new_tokens=CHECK_NEW_TOKENS, temperature=0.0)
            for p in prompts]
    eng.run()
    n_new = min(len(r.tokens) for r in reqs)
    width = -(-max(len(r.output_ids()) for r in reqs) // 8) * 8
    state = common.state_arrays(model)
    before_experts = int(config["num_dense_layers"]) + 1
    gaps, row_worst, row_median, small, total = [], 0.0, 0.0, 0, 0
    ok = all(r.done and r.outcome == "length" for r in reqs)
    for r in reqs:
        out = r.output_ids()
        ids = np.zeros((width,), np.int64)
        ids[:len(out)] = out
        pos = len(r.prompt_ids) - 1 + np.arange(n_new)
        logits, margins, rows = outputs(state, config, jnp.asarray(ids),
                                        jnp.asarray(pos))
        logits, margins = np.asarray(logits), np.asarray(margins)
        small += int((margins < MARGIN_NOTE).sum())
        total += margins.size
        gaps += [float((row.max() - row[tok]) / (row.max() - row.mean()))
                 for row, tok in zip(logits, r.tokens)]
        errors = row_errors(eng, r.slot, len(out) - 1, rows)
        row_worst = max([row_worst] + [float(e.max())
                                       for e in errors[:before_experts]])
        row_median = max([row_median] + [float(np.median(e))
                                         for e in errors])
    worst, mean = max(gaps), sum(gaps) / len(gaps)
    return {"ok": bool(ok and n_new > 1 and worst <= GAP_TOLERANCE
                       and mean <= MEAN_GAP_TOLERANCE
                       and row_worst <= ROW_TOLERANCE
                       and row_median <= ROW_MEDIAN_TOLERANCE),
            "worst_gap": worst, "mean_gap": mean,
            "row_worst_before_experts": row_worst,
            "row_median_worst_layer": row_median,
            "rungs": list(eng.ladder), "new_tokens": n_new,
            "contexts": [len(r.output_ids()) for r in reqs],
            "margin_under_1e-3": small / max(1, total)}


def build_engine(ctx):
    """The model, the engine, the greedy check, the sampled warm-up."""
    import jax

    from paddle_tpu.serving import ServingEngine

    if ctx.chips != 1:
        raise ValueError("runner `serve_afmoe` drives one engine on one chip")
    counters = common.Counters(COUNTERS)
    model = build_model(ctx.config, ctx.seed)
    sink = ListSink() if ctx.trace else None
    eng_kw = dict(ctx.cell["engine"])
    eng_kw["ladder"] = tuple(eng_kw["ladder"])
    eng = ServingEngine(model, sink=sink, **eng_kw)
    dev = jax.devices()[0]
    held = (dev.memory_stats() or {}).get("bytes_in_use")
    check = check_greedy(eng, model, ctx.config, ctx.seed)
    warm = [eng.submit([1, 2, 3], max_new_tokens=eng.steps_per_dispatch,
                       seed=k, **ctx.traffic["sampling"]) for k in range(2)]
    eng.run()
    gc.collect()
    setup_counters = counters.delta()
    ctx.note("setup", {
        "check": check, "kv_cache_bytes": eng.kv_cache_bytes(),
        "parameters": sum(int(p._data.size) for p in model.parameters()),
        # weights and cache alone, and again with the reference's blocks
        # freed and every program compiled
        "bytes_in_use_weights_and_cache": held,
        "bytes_in_use": (dev.memory_stats() or {}).get("bytes_in_use"),
        **setup_counters})
    checks = {"greedy_matches_reference": check["ok"],
              "warm_up_finished": all(r.done for r in warm)}
    return eng, sink, counters, setup_counters, checks


def run(ctx) -> dict:
    traf = ctx.traffic
    arrival = traf["arrival"]
    if arrival["process"] != "backlog":
        raise ValueError("runner `serve_afmoe` drives a closed backlog")
    vocab = int(ctx.config["vocab_size"])
    lead_in_s = float(traf.get("lead_in_s", 0.0))
    eng, sink, counters, setup_counters, checks = build_engine(ctx)
    rows = traffic_lib.requests(
        traf, ctx.seed, ctx.seconds, vocab,
        count=math.ceil(float(arrival["max_rps"]) * (lead_in_s + ctx.seconds)))
    if sink is not None:
        sink.records.clear()
    gc.collect()
    gc.freeze()            # set-up's objects are not scanned in the window
    counters.mark()
    handles, steps, w0, w1, tokens = drive_backlog(
        eng, rows, dict(traf["sampling"]), int(arrival["depth"]), lead_in_s,
        ctx.seconds, ctx, float(ctx.cell.get("trace_seconds", 3.0)))
    run_counters = counters.delta()

    touched = [r for r in handles
               if r.first_token_ts is not None and r.first_token_ts < w1
               and (r.done_ts is None or r.done_ts > w0)]
    finished = [r for r in touched if r.done and r.done_ts <= w1]
    checks["no_compile_after_set_up"] = (
        run_counters["serving.prefill_compiles"]
        + run_counters["serving.decode_compiles"]) == 0
    checks["finished_at_their_budget"] = all(
        r.outcome == "length" and len(r.tokens) == r.max_new_tokens
        for r in finished)
    checks["tokens_in_vocabulary"] = all(
        0 <= t < vocab for r in touched for t in r.tokens)
    ctx.note("window", {"seconds": w1 - w0, "tokens": tokens,
                        "requests_touched": len(touched),
                        "requests_finished": len(finished),
                        "dispatches": len(steps), "checks": checks,
                        **run_counters})
    return {
        "correct": all(checks.values()), "attempted": len(touched),
        "failed": sum(1 for r in touched
                      if r.outcome in ("error", "drained")),
        "end_to_end": {"serve_tokens_per_s": tokens / (w1 - w0)},
        "collected": {
            "steps": steps, "steps_per_dispatch": eng.steps_per_dispatch,
            "window": (w0, w1),
            # sink records carry time.time(); the window is on perf_counter
            "wall_minus_perf": time.time() - time.perf_counter(),
            "sink": sink.records if sink is not None else [],
            "setup_counters": setup_counters, "run_counters": run_counters}}
