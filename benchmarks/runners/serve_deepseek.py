"""Runner `serve_deepseek`: a `deepseek_v2` configuration (DeepSeek-V2: latent
attention, group-routed experts of which this chip holds a share) through
ServingEngine.submit / step on one chip, under a closed backlog. The loop,
the window, the counting and `serve_tokens_per_s` are runner `serve`'s own
code (`drive_backlog`, `ListSink`, its counters), as in `serve_afmoe` and
`serve_hybrid`: this file only builds the model and its check, and hands the
readers the window's prefills.

Set-up: weights drawn on the device from the seed straight into the
configuration's dtype, the engine, one greedy request just under every
prefill rung judged against the plain reference
(benchmarks/lib/reference_deepseek_v2.py) at the published widths, beside
sampled requests in the other slots: the window's decode program (`sample`,
every slot live) is the one the check judges and the only one compiled.

The check. Each greedy request is right-padded to its rung by the engine (3
short of it), prefilled in the EXPANDED form (keys and values a head from the
latent), and generates 16 tokens in the ABSORBED form through the latent rows
its slot holds, with the other slots at other depths beside it. The
reference runs its full forward pass over each request's whole output in the
expanded form, a layer at a time, attention 8 heads at a time and the experts
8 at a time, upcasting the served bf16 weights as it goes, with the same
share (the 40 experts held, the vocabulary's slice). Judged are every served
token against the reference's logits over the same prefix, and every latent
row the five slots hold afterwards against the reference's
[n_t | rope(k_r,t)].
"""
from __future__ import annotations

import gc
import math
import time

from benchmarks.lib import reference_deepseek_v2 as reference
from benchmarks.lib import traffic as traffic_lib
from benchmarks.runners import common
from benchmarks.runners.serve import (ANNOTATIONS, CHECK_NEW_TOKENS,  # noqa: F401
                                      COUNTERS, ListSink, drive_backlog)

try:
    from paddle_tpu.models import DeepseekV2Config, DeepseekV2ForCausalLM
except ImportError as e:            # a program from before the model
    raise SystemExit(f"runner serve_deepseek: this program has no "
                     f"deepseek_v2 model ({e})")

# The check has two groups of measures, as Trinity's has and for its reason,
# and `correct` needs all (PERF.md §6, PR 35;
# benchmarks/tests/controls_deepseek_v2.py reads the controls again).
#
# 1. The tokens, by runner `serve`'s measure: a served greedy token's gap is
# how far the reference's logit of that token lies under the reference's
# maximum at that position, as a share of the position's (max - mean) logit
# spread. 0 is the reference's own argmax, 1 a typical token. What moves a
# served token is bf16 arithmetic and, through it, a routing flip: in 1.9 to
# 4.1% of the (token, expert layer) pairs the 6th and 7th scores the choice
# is made over lie closer than 1e-4 (`margin_under_1e-4`), the program
# chooses another expert, a sixth of a routed output that is weighted 16
# times and not normalised is another vector, and every expert layer above
# moves by more than its margins. So most of the 80 tokens are the
# reference's argmax exactly (gap 0) and a few are far off: the WORST gap is
# heavy-tailed (0.004 to 0.356 over 28 seeds) and so is the MEAN (0.0002 to
# 0.0133), while the 75th PERCENTILE of the 80 gaps is 0 and the 90th at
# most 0.0060 (twelve seeds). A fault of the last layers, which no row above
# them shows, moves every token a little: the last expert layer's routed
# rows dropped reads 0.050 there. GAP_TOLERANCE catches one token computed
# from a wrong row or offset, which reads about 1.
#
# 2. The latent rows the slots hold, which a flip does not swamp: after the
# greedy requests every slot's rows are read back and row p of each layer is
# compared with the reference's [n_p | rope(k_r,p)] as |served - reference|
# / |reference| over the row's 576 values. A layer's rows follow from the
# stream BELOW it, so the rows of the dense layer and of the first expert
# layer are rows no routing choice has touched: there EVERY row is held to
# ROW_TOLERANCE (the row is written once, by the prefill or by the decode
# step of its position, so this sees both forms' projections, the norm,
# YaRN and the position a row lands on). Above, a flipped token's row is
# tens of percent off, so the MEDIAN row of each layer of each request is
# held to ROW_MEDIAN_TOLERANCE. The rows of the 16 decoded positions were
# computed from what the absorbed form read of the rows before them.
#
# The readings (chip; served: 28 seeds, 2147483659 to 2147510047, the
# quantiles on twelve of them; controls: seed 2147483693, the weakest three
# again on 2147500009; each control through `check_greedy`; "=" is the
# served reading of that seed):
#
#                          mean    p75    worst   worst row   median row
#                          gap     gap    gap     layers 0,1  worst layer
#   served, lowest         .0002   .0000  .004    .0126       .0175
#   served, highest        .0133   .0000  .356    .0136       .0192
#   float8 e4m3            .0538          .424    .1626       .2777
#   latent cache in float8 .0158   .0122  .279    .0740       .1098
#   scale without mscale^2 .378           1.116   .660        .800
#   plain RoPE, no YaRN    .677           1.204   1.174       1.164
#   top 6 without groups   .0280   .0334  .278    =           .2255
#   weights normalised     .148           .528    =           .439
#   shared expert left out .345           1.079   =           .745
#   values of head i + 1   1.070          1.642   1.573       1.418
#   last expert layer's    .0363   .0498  .412    =           =
#     routed rows dropped
#
# Each limit lies between the served readings and the lowest control it is
# there for, twice or more from either: ROW_TOLERANCE 2.2 times above .0136
# and 2.5 under the float8 latent cache's .0740; ROW_MEDIAN_TOLERANCE 2.3
# above .0192 and 2.4 under its .1098; P75_GAP_TOLERANCE between the served
# 0 and the dropped layer's .0498 (5 times under it); MEAN_GAP_TOLERANCE 3
# times above .0133 (the tail is long and a false alarm refuses a PR) and
# under the heavier faults; GAP_TOLERANCE 2.1 above .356 and under a token
# from a wrong row's 1. The dropped last layer's MEAN (.036 to .040) lies
# under the mean's limit: the 75th percentile is what holds it out.
GAP_TOLERANCE = 0.75
MEAN_GAP_TOLERANCE = 0.04
P75_GAP_TOLERANCE = 0.01
ROW_TOLERANCE = 0.03
ROW_MEDIAN_TOLERANCE = 0.045
MARGIN_NOTE = 1e-4


def build_model(config: dict, seed: int):
    import paddle_tpu as paddle
    from paddle_tpu.distributed.mesh import set_hybrid_communicate_group

    set_hybrid_communicate_group(None)
    paddle.seed(int(seed))
    model = DeepseekV2ForCausalLM(DeepseekV2Config.from_dict(config))
    model.eval()
    return model


def reference_config(config: dict):
    """(the dictionary the reference reads, the first expert held): the
    configuration's file counts the experts HELD under `n_routed_experts`;
    the reference, as the published config, the router's width."""
    first, _ = config.get("experts_held", (0, config["n_routed_experts"]))
    published = config.get("published", {}).get("n_routed_experts",
                                                 config["n_routed_experts"])
    return dict(config, n_routed_experts=published), int(first)


_PROGRAMS = {}      # the reference's compiled pieces, one a kind of layer


def _program(key, fn):
    if key not in _PROGRAMS:
        import jax

        _PROGRAMS[key] = jax.jit(fn)
    return _PROGRAMS[key]


def forget_programs() -> None:
    """Let the reference's executables go. A loaded program keeps its
    scratch on the device (1.8 GB for an expert layer's at the published
    widths, beside 12.9 GB of weights and rows), and dropping the jitted
    function alone leaves the executable in jax's cache."""
    for fn in _PROGRAMS.values():
        fn.clear_cache()
    _PROGRAMS.clear()
    gc.collect()


def reference_outputs(state: dict, config: dict, ids, positions,
                      lower=None, layer=None):
    """One request: `ids` [s] (right-padded; the pad is inert for the
    positions before it), `positions` [n] -> ([n, vocab] float32 logits,
    the margins [expert layers, n] of the routing at those positions, the
    latent rows [s, 576] a layer that a cache of every position would hold).
    A layer at a time, one compiled program a kind of layer. `lower`, where
    given, rounds every matrix and the stream between the layers to a lower
    precision (the controls' float8 reference), INSIDE each program, so
    that no rounded copy of a layer's 2.3 GB of weights stands beside the
    served ones; `layer`, where given, is `reference.layer` with something
    wrong in it (the controls')."""
    import jax.numpy as jnp

    cfg, first = reference_config(config)
    run_layer = reference.layer if layer is None else layer
    kind = (id(lower), id(layer))   # wrong references: programs of their own

    def low(x):
        return x if lower is None or x.ndim < 2 else lower(x)

    h = _program(("embed", kind), lambda e, i: reference.embed(
        {"model.embed_tokens.weight": low(e)}, i, cfg))(
        state["model.embed_tokens.weight"], ids)
    margins, rows = [], []
    for l in range(cfg["num_hidden_layers"]):
        # a wrong layer may depend on its index: a program a layer then
        key = (l < cfg["first_k_dense_replace"] if layer is None else l, kind)
        h, info = _program(key, lambda p, x, l=l: run_layer(
            {k: low(v) for k, v in p.items()}, low(x), l, cfg, base=first))(
            reference.layer_state(state, l), h)
        rows.append(info["row"])
        if "margin" in info:
            margins.append(info["margin"][positions])
    logits = _program(("head", kind), lambda n, w, x: reference.head(
        {"model.norm.weight": n, "lm_head.weight": low(w)}, low(x), cfg))(
        state["model.norm.weight"], state["lm_head.weight"], h[positions])
    return logits, jnp.stack(margins), rows


def row_errors(eng, slot: int, held: int, rows):
    """Every latent row slot `slot` holds of a context of `held` positions
    against the reference's `rows`: -> [[error a position] a layer]. Row p
    holds position p (kv_state.py); the row of position `held` itself is
    left out, an idle slot may have written its tip there."""
    import numpy as np

    out = []
    for mine, ref in zip(eng.slot_cache.latent, rows):
        mine = np.asarray(mine[slot, :held], np.float32)
        ref = np.asarray(ref, np.float32)[:held]
        out.append(np.sqrt(((mine - ref) ** 2).sum(1) / (ref ** 2).sum(1)))
    return out


def check_greedy(eng, model, config: dict, seed: int, sampling: dict,
                 outputs=reference_outputs) -> dict:
    """One greedy request just under every rung, with a request sampled by
    `sampling` in every slot that is left, so that they decode as the
    window does (the `sample` program, every slot live, chunks enqueued
    ahead); each new greedy token against the reference's logits over the
    same prefix, and each latent row its slot holds afterwards against the
    reference's. `outputs` is the reference (the controls put a wrong one
    there)."""
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
    beside = [eng.submit(prompts[0], max_new_tokens=CHECK_NEW_TOKENS, seed=k,
                         **sampling)
              for k in range(eng.slot_count - len(reqs))]
    eng.run()
    n_new = min(len(r.tokens) for r in reqs)
    width = -(-max(len(r.output_ids()) for r in reqs) // 8) * 8
    state = common.state_arrays(model)
    before_experts = int(config["first_k_dense_replace"]) + 1
    gaps, row_worst, row_median, small, total = [], 0.0, 0.0, 0, 0
    ok = all(r.done and r.outcome == "length" for r in reqs + beside)
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
        # the last token was never fed back: the slot holds the rest
        errors = row_errors(eng, r.slot, len(out) - 1, rows)
        row_worst = max([row_worst] + [float(e.max())
                                       for e in errors[:before_experts]])
        row_median = max([row_median] + [float(np.median(e))
                                         for e in errors])
    worst, mean = max(gaps), sum(gaps) / len(gaps)
    ranked = sorted(gaps)
    return {"ok": bool(ok and n_new > 1 and worst <= GAP_TOLERANCE
                       and mean <= MEAN_GAP_TOLERANCE
                       and ranked[len(ranked) * 3 // 4] <= P75_GAP_TOLERANCE
                       and row_worst <= ROW_TOLERANCE
                       and row_median <= ROW_MEDIAN_TOLERANCE),
            "worst_gap": worst, "mean_gap": mean,
            "gap_p75": ranked[len(ranked) * 3 // 4],
            "gap_p90": ranked[len(ranked) * 9 // 10],
            "row_worst_before_experts": row_worst,
            "row_median_worst_layer": row_median,
            "rungs": list(eng.ladder), "new_tokens": n_new,
            "contexts": [len(r.output_ids()) for r in reqs],
            "beside": len(beside),
            "margin_under_1e-4": small / max(1, total)}


def build_engine(ctx):
    """The model, the engine and the greedy check, which is the warm-up too."""
    import jax

    from paddle_tpu.observability import metrics
    from paddle_tpu.serving import ServingEngine

    if ctx.chips != 1:
        raise ValueError("runner `serve_deepseek` drives one engine on one "
                         "chip")
    counters = common.Counters(COUNTERS)
    model = build_model(ctx.config, ctx.seed)
    sink = ListSink() if ctx.trace else None
    eng_kw = dict(ctx.cell["engine"])
    eng_kw["ladder"] = tuple(eng_kw["ladder"])
    eng = ServingEngine(model, sink=sink, **eng_kw)
    dev = jax.devices()[0]
    held = (dev.memory_stats() or {}).get("bytes_in_use")
    check = check_greedy(eng, model, ctx.config, ctx.seed,
                         ctx.traffic["sampling"])
    forget_programs()
    setup_counters = counters.delta()
    forms = {name: metrics.default_registry().counter(name).value
             for name in ("mla.calls.expanded", "mla.calls.absorbed")}
    ctx.note("setup", {
        "check": check, "kv_cache_bytes": eng.kv_cache_bytes(),
        "latent_bytes": eng.slot_cache.latent_bytes(),
        "parameters": sum(int(p._data.size) for p in model.parameters()),
        # the cores traced, by form: 5 layers a prefill rung expanded, 5 a
        # decode program absorbed
        **forms,
        # weights and cache alone, and again with the reference's blocks
        # freed and every program compiled
        "bytes_in_use_weights_and_cache": held,
        "bytes_in_use": (dev.memory_stats() or {}).get("bytes_in_use"),
        **setup_counters})
    checks = {"greedy_matches_reference": check["ok"]}
    return eng, sink, counters, setup_counters, checks


def run(ctx) -> dict:
    traf = ctx.traffic
    arrival = traf["arrival"]
    if arrival["process"] != "backlog":
        raise ValueError("runner `serve_deepseek` drives a closed backlog")
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
    # where the window's time went, by the runner's clock: a run far off its
    # kind shows here as a slower dispatch, one stall, or time between steps
    plain = sorted((b - a) * 1e3 for a, b, n in steps if not n)
    ctx.note("window", {"seconds": w1 - w0, "tokens": tokens,
                        "dispatch_ms": {
                            "p50": plain[len(plain) // 2],
                            "p95": plain[len(plain) * 95 // 100],
                            "max": plain[-1]} if plain else {},
                        "admitting_steps_s": sum(b - a for a, b, n in steps
                                                 if n),
                        # (seconds into the window, ms, prefills) of the
                        # three longest steps: where a stall sat
                        "slowest_steps": sorted(
                            ((round(a - w0, 2), round((b - a) * 1e3, 1), n)
                             for a, b, n in steps),
                            key=lambda x: -x[1])[:3],
                        "requests_touched": len(touched),
                        "requests_finished": len(finished),
                        "dispatches": len(steps), "checks": checks,
                        "decode_ahead_share":
                            eng.stats()["decode_ahead_share"],
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
            # (admitted, first token, prompt length) of every prefill, on
            # the window's clock: what the prefill roofline reads
            "prefills": [(r.admit_ts, r.first_token_ts, len(r.prompt_ids))
                         for r in handles if r.first_token_ts is not None
                         and r.admit_ts is not None],
            "setup_counters": setup_counters, "run_counters": run_counters}}
