"""Runner `serve_hybrid`: an `olmo_hybrid` configuration (Olmo-Hybrid)
through ServingEngine.submit / step on one chip, under a closed backlog. The
loop, the window, the counting and `serve_tokens_per_s` are runner `serve`'s
own code (`drive_backlog`, `ListSink`, its counters), as in `serve_afmoe`:
this file only builds the model and its check, and hands the readers the
window's prefills.

Set-up: weights drawn on the device from the seed straight into the
configuration's dtype, the engine, one greedy request just under every
prefill rung judged against the plain reference
(benchmarks/lib/reference_olmo_hybrid.py) at the published widths, beside
sampled requests in the other slots: the window's decode program (`sample`,
every slot live) is the one the check judges and the only one compiled.

The check. Each greedy request is right-padded to its rung by the engine (3
short of it, so every prefill masks a pad), prefilled through the chunked
delta rule, and generates 16 tokens through the one-position form with the
other slots at other depths beside it. The reference runs its full
forward pass over each request's whole output, a layer at a time, the delta
rule one position at a time, upcasting the served bf16 weights as it goes.
Judged are every served token against the reference's logits over the same
prefix, and what the five slots hold afterwards: each linear layer's state
matrix and convolution tail against the reference's after the positions the
slot has absorbed, and each row of the full layers' caches.
"""
from __future__ import annotations

import gc
import math
import time

from benchmarks.lib import reference_olmo_hybrid as reference
from benchmarks.lib import traffic as traffic_lib
from benchmarks.runners import common
from benchmarks.runners.serve import (ANNOTATIONS, CHECK_NEW_TOKENS,  # noqa: F401
                                      COUNTERS, ListSink, drive_backlog)

try:
    from paddle_tpu.models import OlmoHybridConfig, OlmoHybridForCausalLM
except ImportError as e:            # a program from before the model
    raise SystemExit(f"runner serve_hybrid: this program has no olmo_hybrid "
                     f"model ({e})")

# The check has three groups of measures, and `correct` needs all (PERF.md §6, PR 33,
# has every reading; benchmarks/tests/controls_olmo_hybrid.py reads them
# again). There is no routing here, so nothing cascades; what spreads is
# rounding: the OLMo block has no pre-norm, so a layer reads the raw stream,
# and the delta rule with beta up to 2 damps nothing (1 - beta k k^T has an
# eigenvalue of -1 at beta = 2), so what bf16 adds to the stream a position
# stays in a state for thousands of positions.
#
# 1. The tokens, by runner `serve`'s measure: a served greedy token's gap is
# how far the reference's logit of that token lies under the reference's
# maximum at that position, as a share of the position's (max - mean) logit
# spread. 0 is the reference's own argmax, 1 a typical token.
#
# 2. What the slots hold for the FIRST linear layer. Its input is the
# embedding's rows, which the program and the reference read alike (the
# served bf16 weights, upcast), and everything from the projection to the
# state is float32 on both sides, so nothing of the stream's rounding
# reaches it and it shows the delta rule itself. Its state matrix
# [30, 96, 192] and convolution tail [3, 11520] are compared with the
# reference's as |served - reference| / |reference|, twice. (a) Right after
# a prefill alone (a request of one token): the chunked form over a padded
# rung against the reference's recurrence stopped at the prompt's end, both
# float32 throughout, held to PREFILL_STATE_TOLERANCE. This is where the pad
# not masked, the order of decay and update, the factor on beta, the
# convolution's alignment, the normalisation and a state kept in a lower
# precision (which adds its rounding at every one of up to 3,581 positions)
# all show, with nothing to hide them. (b) After the 16 decode steps with
# the other slots idle or at other depths beside it: the slot keeps the tail
# in bfloat16, a decode step reads three of its four convolution inputs from
# it, each 0.2% off, and `S^T k` makes an error in k one in the whole state;
# held to FIRST_STATE_TOLERANCE. The tail itself is one bf16 rounding of a
# float32 projection both times (FIRST_TAIL_TOLERANCE).
#
# 3. What the slots hold in every layer, loosely: each linear layer's whole
# state matrix and tail, and the MEDIAN row of each full layer's cache (row
# p holds position p; the worst row of 3,600 is a tail of the same noise
# and is reported, not judged). This sees a later layer that is wrong alone
# by more than the stream's noise, and the full layers' keys and values. It
# does NOT see a later layer that alone keeps its state in bfloat16, which
# reads as the served path does (last row of the table; PERF.md section 7).
#
# The readings (chip; served: 32 seeds, 2147484953 to 2147542577, the last
# 17 with every slot live in the `sample` program; controls: seed
# 2147484953, benchmarks/tests/controls_olmo_hybrid.py, each through
# `check_greedy`; "=" is the served reading of that seed):
#
#                       mean   worst  prefill first  first   state  tail   row
#                       gap    gap    state   state  tail                  median
#   served, lowest      .00003 .0022  .00004  .00141 .00166  .0550  .0254  .0262
#   served, highest     .00098 .0246  .00014  .00174 .00168  .0824  .0316  .0277
#   float8 e4m3         .120   .380   .0770   .0769  .0379   .911   .480   .521
#   pad not masked      =      =      2.36    2.37   =       2.85   =      =
#   tail from the pad   =      =      =       =      1.41    =      1.43   =
#   beta not doubled    .415   .837   1.01    1.00   =       2.13   .945   .944
#   decay after update  .205   .563   .0674   .0666  =       1.42   .618   .665
#   tail off by one     .840   1.55   .266    .268   =       1.59   1.44   1.41
#   q, k not normalised (the reference overflows: not a number, not correct)
#   state in bfloat16   .0016  .0273  .0119   .0121  =       .145   .0663  .0647
#   in ONE later layer (the layers before and after it plain):
#   beta not doubled    .0015  .0443  =       =      =       1.07   =      .0553
#   decay after update  .0115  .104   =       =      =       .595   .147   .143
#   state in bfloat16   =      =      =       =      =       =      =      =
#
# Each limit lies between the served readings and the lowest control it is
# there for, twice or more from either: FIRST_STATE lies 3.4 times above the
# served and 2 times under the bfloat16 state, which PREFILL_STATE holds out
# with 7 and 12 times of room; GAP lies 4 times above the served and 3.8
# under float8; the all-layer limits lie 2.2 to 2.4 times above the served
# and 2.1 to 3 times under the decay applied after the update in one later
# layer, the mildest fault of one layer that was tried (they let the state
# in bfloat16 in every layer pass, which PREFILL_STATE holds out).
GAP_TOLERANCE = 0.1
MEAN_GAP_TOLERANCE = 0.01
PREFILL_STATE_TOLERANCE = 0.001  # the first linear layer's, after a prefill
FIRST_STATE_TOLERANCE = 0.006   # and after 16 decode steps on a bf16 tail
FIRST_TAIL_TOLERANCE = 0.01     # one bf16 rounding of the tail
STATE_TOLERANCE = 0.2           # any linear layer's whole state matrix
TAIL_TOLERANCE = 0.07
ROW_MEDIAN_TOLERANCE = 0.06


def build_model(config: dict, seed: int):
    import paddle_tpu as paddle
    from paddle_tpu.distributed.mesh import set_hybrid_communicate_group

    set_hybrid_communicate_group(None)
    paddle.seed(int(seed))
    model = OlmoHybridForCausalLM(OlmoHybridConfig.from_dict(config))
    model.eval()
    return model


_PROGRAMS = {}      # the reference's compiled pieces, one a kind of layer


def _program(key, fn):
    if key not in _PROGRAMS:
        import jax

        _PROGRAMS[key] = jax.jit(fn)
    return _PROGRAMS[key]


def reference_outputs(state: dict, config: dict, ids, positions, length,
                      lower=None, layers=None, stream=None):
    """One request: `ids` [s] (right-padded), `positions` [n], `length` the
    positions a slot has absorbed -> ([n, vocab] float32 logits, what a slot
    would hold a layer: {"state", "tail"} after `length` positions or {"k",
    "v"} of every position). A layer at a time, one compiled program a kind
    of layer. `lower`, where given, rounds every matrix and the stream
    between the layers to a lower precision (the controls' float8
    reference). `layers`, where given, is the range of layers to run, from
    the hidden states `stream` where it does not start at the embedding;
    where it stops before the last layer there are no logits and the hidden
    states come back in their place (the check's first pass runs layer 0
    alone; a control runs one layer with a wrong piece between plain ones)."""
    def low(p):
        if lower is None:
            return p
        return {k: (lower(v) if v.ndim >= 2 else v) for k, v in p.items()}

    n_layers = config["num_hidden_layers"]
    layers = range(n_layers) if layers is None else layers
    top = low({k: state[k] for k in ("model.embed_tokens.weight",
                                      "lm_head.weight")})
    h = stream
    if not layers.start:
        h = _program("embed", lambda e, i: reference.embed(
            {"model.embed_tokens.weight": e}, i, config))(
            top["model.embed_tokens.weight"], ids)
    held = []
    for l in layers:
        if lower is not None:
            h = lower(h)
        h, info = _program(
            config["layer_types"][l],
            lambda p, x, n, l=l: reference.layer(p, x, l, config, n))(
            low(reference.layer_state(state, l)), h, length)
        held.append(info)
    if layers.stop < n_layers:
        return h, held
    if lower is not None:
        h = lower(h)
    logits = _program("head", lambda n, w, x: reference.head(
        {"model.norm.weight": n, "lm_head.weight": w}, x, config))(
        state["model.norm.weight"], top["lm_head.weight"], h[positions])
    return logits, held


def _relative(mine, ref, axes=None):
    import numpy as np

    mine, ref = np.asarray(mine, np.float32), np.asarray(ref, np.float32)
    return np.sqrt(((mine - ref) ** 2).sum(axes)
                   / np.maximum((ref ** 2).sum(axes), 1e-30))


def held_errors(eng, slot: int, held: int, infos) -> dict:
    """What slot `slot` holds after absorbing `held` positions against the
    reference's `infos`: each measure of the check, the worst over the
    layers it is taken over."""
    import numpy as np

    kv = eng.slot_cache
    mine = iter(zip(kv.state, kv.tail))
    rows = iter(zip(kv.k, kv.v))
    out = {"state": 0.0, "tail": 0.0, "row_median": 0.0, "row_worst": 0.0}
    for spec, info in zip(kv.spec, infos):
        if spec.kind == "state":
            state, tail = next(mine)
            head = float(_relative(state[slot], info["state"],
                                   axes=(1, 2)).max())
            errs = {"state": float(_relative(state[slot], info["state"])),
                    "tail": float(_relative(tail[slot], info["tail"]))}
            if "first_state" not in out:
                out.update(first_state=errs["state"], first_state_head=head,
                           first_tail=errs["tail"])
        else:
            # the row of position `held` itself is left out: an idle slot
            # may have written its tip there
            err = np.zeros(held)
            for cache, ref in zip(next(rows), (info["k"], info["v"])):
                err = np.maximum(err, _relative(
                    np.asarray(cache[slot], np.float32)[:held],
                    np.asarray(ref, np.float32)[:held], axes=(1, 2)))
            errs = {"row_median": float(np.median(err)),
                    "row_worst": float(err.max())}
        out.update({k: max(out[k], v) for k, v in errs.items()})
    return out


def check_greedy(eng, model, config: dict, seed: int, sampling: dict,
                 outputs=reference_outputs) -> dict:
    """One greedy prompt just under every rung, twice. First prefilled
    alone (one token, one request at a time): what its slot holds for the
    first linear layer against the reference's after the prompt. Then all
    of them together for 16 tokens, with a request sampled by `sampling` in
    every slot that is left, so that they decode as the window does: the
    `sample` program, every slot live, chunks enqueued ahead. Each new
    greedy token is judged against the reference's logits over the same
    prefix, and what its slot holds afterwards in every layer. `outputs` is
    the reference (the controls put a wrong one there)."""
    import jax.numpy as jnp
    import numpy as np

    if len(eng.ladder) > eng.slot_count:
        raise ValueError("the check reads every request's slot after the "
                         "run: it needs a slot a rung")
    vocab = int(config["vocab_size"])
    rng = np.random.default_rng(int(seed) + 1)
    prompts = [rng.integers(0, vocab, (max(1, rung - 3),), dtype=np.int64)
               for rung in eng.ladder]
    width = -(-(max(map(len, prompts)) + CHECK_NEW_TOKENS) // 8) * 8
    state = common.state_arrays(model)

    def padded(out):
        ids = np.zeros((width,), np.int64)
        ids[:len(out)] = out
        return jnp.asarray(ids)

    worst, ok = {}, True
    for p in prompts:
        r = eng.submit(p, max_new_tokens=1, temperature=0.0)
        eng.run()
        ok = ok and r.done and r.outcome == "length"
        _, infos = outputs(state, config, padded(p), None,
                           jnp.int32(len(p)), layers=range(1))
        errors = held_errors(eng, r.slot, len(p), infos)
        for key in ("state", "tail"):
            worst["prefill_" + key] = max(worst.get("prefill_" + key, 0.0),
                                          errors["first_" + key])
    reqs = [eng.submit(p, max_new_tokens=CHECK_NEW_TOKENS, temperature=0.0)
            for p in prompts]
    beside = [eng.submit(prompts[0], max_new_tokens=CHECK_NEW_TOKENS, seed=k,
                         **sampling)
              for k in range(eng.slot_count - len(reqs))]
    eng.run()
    n_new = min(len(r.tokens) for r in reqs)
    ok = ok and all(r.done and r.outcome == "length" for r in reqs + beside)
    gaps = []
    for r in reqs:
        out = r.output_ids()
        pos = len(r.prompt_ids) - 1 + np.arange(n_new)
        # the last token was never fed back: the slot absorbed the rest
        held = len(out) - 1
        logits, infos = outputs(state, config, padded(out), jnp.asarray(pos),
                                jnp.int32(held))
        logits = np.asarray(logits)
        gaps += [float((row.max() - row[tok]) / (row.max() - row.mean()))
                 for row, tok in zip(logits, r.tokens)]
        errors = held_errors(eng, r.slot, held, infos)
        worst = {k: max(worst.get(k, 0.0), v)
                 for k, v in {**worst, **errors}.items()}
    worst_gap, mean = max(gaps), sum(gaps) / len(gaps)
    return {"ok": bool(ok and n_new > 1 and worst_gap <= GAP_TOLERANCE
                       and mean <= MEAN_GAP_TOLERANCE
                       and worst["prefill_state"] <= PREFILL_STATE_TOLERANCE
                       and worst["first_state"] <= FIRST_STATE_TOLERANCE
                       and max(worst["prefill_tail"], worst["first_tail"])
                       <= FIRST_TAIL_TOLERANCE
                       and worst["state"] <= STATE_TOLERANCE
                       and worst["tail"] <= TAIL_TOLERANCE
                       and worst["row_median"] <= ROW_MEDIAN_TOLERANCE),
            "worst_gap": worst_gap, "mean_gap": mean, **worst,
            "rungs": list(eng.ladder), "new_tokens": n_new,
            "contexts": [len(r.output_ids()) for r in reqs],
            "beside": len(beside)}


def build_engine(ctx):
    """The model, the engine and the greedy check, which is the warm-up too."""
    import jax

    from paddle_tpu.serving import ServingEngine

    if ctx.chips != 1:
        raise ValueError("runner `serve_hybrid` drives one engine on one chip")
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
    gc.collect()
    setup_counters = counters.delta()
    ctx.note("setup", {
        "check": check, "kv_cache_bytes": eng.kv_cache_bytes(),
        "state_bytes": eng.slot_cache.state_bytes(),
        "parameters": sum(int(p._data.size) for p in model.parameters()),
        # weights and cache alone, and again with the reference's blocks
        # freed and every program compiled
        "bytes_in_use_weights_and_cache": held,
        "bytes_in_use": (dev.memory_stats() or {}).get("bytes_in_use"),
        **setup_counters})
    checks = {"greedy_matches_reference": check["ok"]}
    return eng, sink, counters, setup_counters, checks


def run(ctx) -> dict:
    from paddle_tpu.core import monitor

    traf = ctx.traffic
    arrival = traf["arrival"]
    if arrival["process"] != "backlog":
        raise ValueError("runner `serve_hybrid` drives a closed backlog")
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
    state_absmax = monitor.stat("serving.state_absmax").get()
    checks["no_compile_after_set_up"] = (
        run_counters["serving.prefill_compiles"]
        + run_counters["serving.decode_compiles"]) == 0
    checks["finished_at_their_budget"] = all(
        r.outcome == "length" and len(r.tokens) == r.max_new_tokens
        for r in finished)
    checks["tokens_in_vocabulary"] = all(
        0 <= t < vocab for r in touched for t in r.tokens)
    checks["state_is_finite"] = math.isfinite(float(state_absmax))
    # where the window's time went, by the runner's clock: a run far off its
    # kind shows here as a slower dispatch, one stall, or time between steps
    plain = sorted((b - a) * 1e3 for a, b, n in steps if not n)
    trace_s = float(ctx.cell.get("trace_seconds", 3.0))
    ctx.note("window", {"seconds": w1 - w0, "tokens": tokens,
                        "dispatch_ms": {
                            "p50": plain[len(plain) // 2],
                            "p95": plain[len(plain) * 95 // 100],
                            "max": plain[-1]} if plain else {},
                        "admitting_steps_s": sum(b - a for a, b, n in steps
                                                 if n),
                        "between_steps_s": sum(
                            nxt[0] - cur[1]
                            for cur, nxt in zip(steps, steps[1:])),
                        # host time of the prefills in the traced sub-window,
                        # to hold beside the trace's (the prefill roofline)
                        "prefill_host_s_traced": sum(
                            max(0.0, min(r.first_token_ts, w1)
                                - max(r.admit_ts, w1 - trace_s))
                            for r in touched),
                        "requests_touched": len(touched),
                        "requests_finished": len(finished),
                        "dispatches": len(steps), "checks": checks,
                        "state_absmax": state_absmax,
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
