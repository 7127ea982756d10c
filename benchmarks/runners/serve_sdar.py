"""Runner `serve_sdar`: an `sdar_moe` configuration (JetLM SDAR-30B-A3B),
which generates by diffusion over blocks, through ServingEngine.submit /
step on one chip, under a closed backlog. The loop, the window, the counting
and `serve_tokens_per_s` are runner `serve`'s own code (`drive_backlog`,
`_timed_step`, `ListSink`, its counters): this file only builds the model
and its check. `serve_tokens_per_s` counts committed output TOKENS (a
forward yields 0 or 4 a slot), as every other cell's.

Set-up: weights drawn on the device from the seed straight into the
configuration's dtype, the engine, the check below, two sampled requests so
that the `sample` block-step program is the one the window runs.

The check. One greedy request just under every prefill rung generates 16
tokens (4 blocks, 20 forwards under the static schedule), and one sampled
request (the traffic's temperature, top-k and top-p) beside the shortest;
the engine records every request's block after every forward, with the
tokens it drew and their confidences (`Request.block_states`). The
reference (benchmarks/lib/reference_sdar.py), a layer at a time with the
served bf16 weights upcast, then computes

(a) its block-causal forward over each request's whole final sequence, the
    prompt's whole blocks and the committed blocks: every row the slots hold
    in every layer against its keys and values. A wrong mask moves them, and
    so do rows kept from a forward whose block still held a mask;
(b) for the shortest greedy request and the sampled one, its forward over
    [the tokens held | the block as the program had it] for every recorded
    forward: each token a greedy forward unmasked is judged against the
    reference's logits AT ITS POSITION (`serve_afmoe`'s gap measure), every
    confidence the program reported against the reference's probability of
    the same draw under the same distribution, and the position it chose by
    how far the reference's confidence there lies under the reference's best
    among the masked.
"""
from __future__ import annotations

import gc
import math
import time

from benchmarks.lib import reference_sdar as reference
from benchmarks.lib import traffic as traffic_lib
from benchmarks.runners import common
from benchmarks.runners.serve import (ANNOTATIONS, CHECK_NEW_TOKENS,  # noqa: F401
                                      COUNTERS, ListSink, drive_backlog)

try:
    from paddle_tpu.models import SdarConfig, SdarForCausalLM
except ImportError as e:            # a program from before the model
    raise SystemExit(f"runner serve_sdar: this program has no sdar model "
                     f"({e})")

# `correct` needs every measure below (PERF.md §6, PR 39, has the readings;
# benchmarks/tests/controls_sdar.py reads them again). What moves a served
# number: bf16 arithmetic, and, as in Trinity, a routing flip: where the
# reference's 8th and 9th scores lie closer than bf16 moves them (a tenth to
# a fifth of the (token, layer) pairs lie within 1% of each other,
# `relative_margin_under_1e-2`), the program chooses another expert and an
# eighth of that layer's routed output is an unrelated vector. Hence two
# kinds of limit on the rows, one on what no routing choice has touched and
# one on the bulk.
#
# 1. The rows the slots hold (a), as |served - reference| / |reference| over
# a row, the larger of the key's and the value's. Layer 0's rows follow from
# the embedding alone, so EVERY row of layer 0 is held to ROW_TOLERANCE: a
# row kept from a forward that still held a mask is the mask token's row,
# 1.5 off, and rows kept in float8 are 0.03 off. Above, the MEDIAN row of
# each layer of each request is held to ROW_MEDIAN_TOLERANCE: the causal
# mask for the block-causal one moves three rows in four.
#
# 2. The tokens (b): a greedy forward's unmasked token, by how far the
# reference's logit of it lies under the reference's maximum at that
# position, as a share of the position's (max - mean) spread: 0 is the
# reference's own argmax, 1 a typical token. With random weights at these
# widths a position's state is mostly its context's (by the initialisers'
# scales the attention's output outweighs a token's embedding about three
# to one), so neighbouring positions' logits nearly agree and the tokens see
# gross faults only (a key head `i % 4` reads 1.2); a shift by one reads
# 0.0003 here and is caught by 3.
#
# 3. The confidences (b): |program's - reference's| / reference's, of the
# same draw at every masked position, by their median a request: bf16 moves
# a logit by a hundredth of the spread and a probability by about a percent;
# the untempered distribution for the tempered, filtered one is off by two
# orders of magnitude, the logits of the row before by 5%. And the choice:
# (best - chosen) / best among the masked by the REFERENCE's confidences, by
# its mean over the forwards that had a choice, a request: 0 unless two
# positions lie within rounding of each other.
#
# The readings (chip; served: 21 seeds, 2147484001 to 2147484499 and
# 3000000019; controls: seed 2147484101, each through `check_blocks`):
#
#                          first-layer  median   mean    confidence  choice
#                          row (worst)  row      gap     error
#   served                 .0033-.0034  .0109-   0-      .0043-      0-
#                                       .0144    .0014   .0127       .0095
#   float8 e4m3 reference  .0441        .1369    0       .0434       .0062
#   rows kept in float8    .0311        .0304    0       as served   as served
#   router in bfloat16     as served    .0135    0       .0066       .0011
#   causal mask            as served    .2922    0       .2440       .1019
#   rows before the last
#     unmasking            1.5098       .0136    0       .0313       .0076
#   logits shifted by one  as served    as srvd  .0003   .0549       .0527
#   confidence untempered  as served    as srvd  0       178.2       as served
#   weights not normalised as served    .5967    0       .1651       .0924
#   key head `i % 4`       as served    1.4739   1.2177  2e10        1.0
#   q/k norm dropped       .2409        .4393    0       .0833       .0150
#
# Each limit lies between the served readings and the lowest control it is
# there for, two to three times from either: ROW_TOLERANCE between .0034 and
# float8 rows' .0311; ROW_MEDIAN_TOLERANCE between .0144 and float8's .1369;
# CONFIDENCE_TOLERANCE between .0127 and float8's .0434 / the shift's .0549;
# CHOICE_TOLERANCE between .0095 and the shift's .0527; the gaps' limits
# between .0182 (the worst served token) / .0014 and the wrong key head's
# 1.2. A router run in bfloat16 stays unseen: it flips a choice in a few
# tokens of a hundred, which the bfloat16 stream does anyway.
GAP_TOLERANCE = 0.1
MEAN_GAP_TOLERANCE = 0.02
ROW_TOLERANCE = 0.01
ROW_MEDIAN_TOLERANCE = 0.04
CONFIDENCE_TOLERANCE = 0.025
CHOICE_TOLERANCE = 0.025
MARGIN_NOTE = 1e-2    # of the 8th score: what bfloat16 moves a score by
FIRST_SPECIAL_TOKEN = 151643     # prompts draw their ids below it
# the block steps' own counters, beside runner `serve`'s
BLOCK_COUNTERS = ("serving.forwards", "serving.blocks_committed",
                  "serving.positions_unmasked", "serving.decode_ahead")


def build_model(config: dict, seed: int):
    import paddle_tpu as paddle
    from paddle_tpu.distributed.mesh import set_hybrid_communicate_group

    set_hybrid_communicate_group(None)
    paddle.seed(int(seed))
    model = SdarForCausalLM(SdarConfig.from_dict(config))
    model.eval()
    return model


def prompt_vocab(config: dict) -> int:
    """Prompt ids are drawn below the tokenizer's first special token, so
    no prompt holds the mask token."""
    return min(int(config["vocab_size"]), FIRST_SPECIAL_TOKEN,
               int(config["mask_token_id"]))


def request_kwargs(traffic: dict) -> dict:
    """What a request of this traffic is submitted with: its sampling and
    its generation schedule (`block_length` is the model's, stated there to
    be checked against the configuration)."""
    generation = dict(traffic.get("generation", {}))
    generation.pop("block_length", None)
    return dict(traffic["sampling"], **generation)


_PROGRAMS = {}      # the reference's compiled pieces


def _program(key, fn):
    if key not in _PROGRAMS:
        import jax

        _PROGRAMS[key] = jax.jit(fn)
    return _PROGRAMS[key]


def forget_programs() -> None:
    """A loaded program keeps its scratch on the device."""
    _PROGRAMS.clear()


def reference_outputs(state: dict, config: dict, ids, positions,
                      lower=None):
    """One sequence: `ids` [s] (right-padded from a block's edge; the pad is
    inert for the positions before it), `positions` [n] -> ([n, vocab]
    float32 logits AT those positions, the margins [layers, n] of the
    routing there, the rows [(k, v) a layer] that a cache of every position
    would hold). A layer at a time, one compiled program a length. `lower`,
    where given, rounds every matrix and the stream between the layers to a
    lower precision (the controls' float8 reference)."""
    import jax.numpy as jnp

    B = int(config["block_length"])

    def low(p):
        if lower is None:
            return p
        return {k: (lower(v) if v.ndim >= 2 else v) for k, v in p.items()}

    top = low({k: state[k] for k in ("model.embed_tokens.weight",
                                      "lm_head.weight")})
    h = _program("embed", lambda e, i: reference.embed(
        {"model.embed_tokens.weight": e}, i))(
        top["model.embed_tokens.weight"], ids)
    margins, rows = [], []
    for l in range(config["num_hidden_layers"]):
        if lower is not None:
            h = lower(h)
        h, info = _program("layer", lambda p, x: reference.layer(
            p, x, config, B))(low(reference.layer_state(state, l)), h)
        rows.append((info["k"], info["v"]))
        margins.append(info["margin"][positions])
    if lower is not None:
        h = lower(h)
    logits = _program("head", lambda n, w, x: reference.head(
        {"model.norm.weight": n, "lm_head.weight": w}, x, config))(
        state["model.norm.weight"], top["lm_head.weight"],
        h[jnp.asarray([reference.logit_position(int(p))
                       for p in positions])])
    return logits, jnp.stack(margins), rows


def row_errors(eng, slot: int, held: int, rows):
    """Every row slot `slot` holds of a context of `held` positions against
    the reference's `rows`: -> [[error a position] a layer], the larger of
    the key's and the value's relative error."""
    import numpy as np

    out = []
    for l, (k, v) in enumerate(rows):
        worst = np.zeros(held)
        for mine, ref in ((eng._kcs[l], k), (eng._vcs[l], v)):
            mine = np.asarray(mine[slot][:held], np.float32)
            ref = np.asarray(ref, np.float32)[:held]
            err = np.sqrt(((mine - ref) ** 2).sum((1, 2))
                          / (ref ** 2).sum((1, 2)))
            worst = np.maximum(worst, err)
        out.append(worst)
    return out


def committed_sequence(req, block: int):
    """The positions a request's slot holds at its end: the prompt's whole
    blocks, then every committed block whole (the given tokens that opened
    the first among them)."""
    head = len(req.prompt_ids) // block * block
    seq = [int(t) for t in req.prompt_ids[:head]]
    for s in req.block_states:
        if s["committed"]:
            seq += s["block"]
    return seq


def forwards_of(req, block: int, mask_token_id: int):
    """A request's recorded forwards that unmasked: [(offset, the block the
    forward was given, the state it left)]."""
    head = len(req.prompt_ids) // block * block
    given = [int(t) for t in req.prompt_ids[head:]]
    cur = given + [mask_token_id] * (block - len(given))
    out = []
    for s in req.block_states:
        if s["committed"]:
            cur = [mask_token_id] * block
            continue
        out.append((s["offset"], list(cur), s))
        cur = list(s["block"])
    return out


def judge_forwards(req, state, config, width, sampling, outputs):
    """(b) for one request -> (gaps of the tokens greedy forwards unmasked,
    relative errors of the reported confidences, shortfalls of the chosen
    positions)."""
    import jax.numpy as jnp
    import numpy as np

    B, mask_id = int(config["block_length"]), int(config["mask_token_id"])
    held = committed_sequence(req, B)
    temp = float(sampling.get("temperature", 0.0))
    top_k, top_p = int(sampling.get("top_k", 0)), float(
        sampling.get("top_p", 1.0))
    confidence = _program(
        ("confidence", temp, top_k, top_p),
        lambda row, token: reference.confidence(row, token, temp, top_k,
                                                top_p))
    gaps, conf_errors, shortfalls = [], [], []
    for offset, given, s in forwards_of(req, B, mask_id):
        ids = np.zeros((width,), np.int64)
        ids[:offset] = held[:offset]
        ids[offset:offset + B] = given
        logits, _, _ = outputs(state, config, jnp.asarray(ids),
                               jnp.arange(offset, offset + B))
        logits = np.array(logits)
        logits[:, mask_id] = -np.inf
        masked = [i for i in range(B) if given[i] == mask_id]
        took = [i for i in masked if s["block"][i] != mask_id]
        ref_conf = {i: float(confidence(jnp.asarray(logits[i]),
                                        jnp.int32(s["draws"][i])))
                    for i in masked}
        for i in masked:
            conf_errors.append(abs(s["confidences"][i] - ref_conf[i])
                               / max(ref_conf[i], 1e-12))
        if len(masked) > len(took) > 0:
            # no draw has any probability by the reference: wholly short
            best = max(ref_conf.values())
            shortfalls += [(best - ref_conf[i]) / best if best else 1.0
                           for i in took]
        if temp == 0.0:
            for i in took:
                row = logits[i]
                finite = row[np.isfinite(row)]
                gaps.append(float((finite.max() - row[s["block"][i]])
                                  / (finite.max() - finite.mean())))
    return gaps, conf_errors, shortfalls


def check_blocks(eng, model, config: dict, seed: int, sampling: dict,
                 outputs=reference_outputs) -> dict:
    """The check of this file's header. `outputs` is the reference (the
    controls put a wrong one there)."""
    import jax.numpy as jnp
    import numpy as np

    if len(eng.ladder) + 1 > eng.slot_count:
        raise ValueError("the row check reads every request's slot after "
                         "the run: it needs a slot a rung and one more")
    B = int(config["block_length"])
    vocab = prompt_vocab(config)
    rng = np.random.default_rng(int(seed) + 1)
    # just under every rung, with 1, 0, 3, 2 prompt tokens left over to open
    # the first block
    prompts = [rng.integers(0, vocab, (max(1, rung - 3 - k % B),),
                            dtype=np.int64)
               for k, rung in enumerate(eng.ladder)]
    reqs = [eng.submit(p, max_new_tokens=CHECK_NEW_TOKENS, temperature=0.0,
                       record_blocks=True) for p in prompts]
    sampled = eng.submit(
        rng.integers(0, vocab, (max(1, min(eng.ladder) - 2),),
                     dtype=np.int64),
        max_new_tokens=CHECK_NEW_TOKENS, seed=int(seed) % (1 << 30),
        record_blocks=True, **sampling)
    eng.run()
    state = common.state_arrays(model)
    ok = all(r.done and r.outcome == "length"
             and len(r.tokens) == CHECK_NEW_TOKENS for r in reqs + [sampled])
    # (a) the rows, every request over its whole final sequence
    width = -(-max(len(committed_sequence(r, B)) for r in reqs) // 8) * 8
    row_first, row_median, small, total = 0.0, 0.0, 0, 0
    for r in reqs + [sampled]:
        seq = committed_sequence(r, B)
        ids = np.zeros((width,), np.int64)
        ids[:len(seq)] = seq
        _, margins, rows = outputs(state, config, jnp.asarray(ids),
                                   jnp.arange(len(seq) - CHECK_NEW_TOKENS,
                                              len(seq)))
        errors = row_errors(eng, r.slot, len(seq), rows)
        row_first = max(row_first, float(errors[0].max()))
        row_median = max([row_median] + [float(np.median(e))
                                         for e in errors])
        small += int((np.asarray(margins) < MARGIN_NOTE).sum())
        total += int(np.asarray(margins).size)
    forget_programs()          # (b) runs at another width
    # (b) the forwards of the shortest greedy request and the sampled one
    short = -(-(len(committed_sequence(reqs[0], B)) + B) // 8) * 8
    gaps, conf_median, choice, judged = [], 0.0, 0.0, []
    for r, how in ((reqs[0], {"temperature": 0.0}), (sampled, sampling)):
        g, c, s = judge_forwards(r, state, config, short, how, outputs)
        gaps += g
        # a request at a time: the greedy one's confidences are of another
        # distribution than the sampled one's
        conf_median = max(conf_median, float(np.median(c)))
        choice = max(choice, sum(s) / max(1, len(s)))
        judged.append((len(c), len(s)))
    forget_programs()
    forwards = [len(r.block_states) for r in reqs + [sampled]]
    worst, mean = max(gaps), sum(gaps) / len(gaps)
    return {"ok": bool(ok and worst <= GAP_TOLERANCE
                       and mean <= MEAN_GAP_TOLERANCE
                       and row_first <= ROW_TOLERANCE
                       and row_median <= ROW_MEDIAN_TOLERANCE
                       and conf_median <= CONFIDENCE_TOLERANCE
                       and choice <= CHOICE_TOLERANCE),
            "worst_gap": worst, "mean_gap": mean, "tokens_judged": len(gaps),
            "row_worst_first_layer": row_first,
            "row_median_worst_layer": row_median,
            "confidence_error_median": conf_median,
            "choice_shortfall_mean": choice,
            "confidences_and_choices_judged": judged,
            "rungs": list(eng.ladder), "forwards": forwards,
            "contexts": [len(committed_sequence(r, B)) for r in reqs],
            "relative_margin_under_1e-2": small / max(1, total)}


def build_engine(ctx):
    """The model, the engine, the check, the sampled warm-up."""
    import jax

    from paddle_tpu.observability import metrics
    from paddle_tpu.serving import ServingEngine

    if ctx.chips != 1:
        raise ValueError("runner `serve_sdar` drives one engine on one chip")
    counters = common.Counters(COUNTERS + BLOCK_COUNTERS)
    model = build_model(ctx.config, ctx.seed)
    sink = ListSink() if ctx.trace else None
    eng_kw = dict(ctx.cell["engine"])
    eng_kw["ladder"] = tuple(eng_kw["ladder"])
    eng = ServingEngine(model, sink=sink, **eng_kw)
    dev = jax.devices()[0]
    held = (dev.memory_stats() or {}).get("bytes_in_use")
    sampling = request_kwargs(ctx.traffic)
    check = check_blocks(eng, model, ctx.config, ctx.seed, sampling)
    warm = [eng.submit([1, 2, 3], max_new_tokens=eng.steps_per_dispatch,
                       seed=k, **sampling) for k in range(2)]
    eng.run()
    gc.collect()
    setup_counters = counters.delta()
    ctx.note("setup", {
        "check": check, "kv_cache_bytes": eng.kv_cache_bytes(),
        "parameters": sum(int(p._data.size) for p in model.parameters()),
        "bytes_in_use_weights_and_cache": held,
        "bytes_in_use": (dev.memory_stats() or {}).get("bytes_in_use"),
        "diffusion.calls.block_step": metrics.default_registry().counter(
            "diffusion.calls.block_step").value,
        **setup_counters})
    checks = {"blocks_match_reference": check["ok"],
              "warm_up_finished": all(r.done for r in warm)}
    return eng, sink, counters, setup_counters, checks, sampling


def run(ctx) -> dict:
    traf = ctx.traffic
    arrival = traf["arrival"]
    if arrival["process"] != "backlog":
        raise ValueError("runner `serve_sdar` drives a closed backlog")
    if int(traf.get("generation", {}).get(
            "block_length", ctx.config["block_length"])) \
            != int(ctx.config["block_length"]):
        raise ValueError("the traffic's block_length is not the "
                         "configuration's")
    vocab = int(ctx.config["vocab_size"])
    lead_in_s = float(traf.get("lead_in_s", 0.0))
    eng, sink, counters, setup_counters, checks, sampling = build_engine(ctx)
    rows = traffic_lib.requests(
        traf, ctx.seed, ctx.seconds, prompt_vocab(ctx.config),
        count=math.ceil(float(arrival["max_rps"]) * (lead_in_s + ctx.seconds)))
    if sink is not None:
        sink.records.clear()
    gc.collect()
    gc.freeze()            # set-up's objects are not scanned in the window
    counters.mark()
    handles, steps, w0, w1, tokens = drive_backlog(
        eng, rows, sampling, int(arrival["depth"]), lead_in_s, ctx.seconds,
        ctx, float(ctx.cell.get("trace_seconds", 3.0)))
    run_counters = counters.delta()

    touched = [r for r in handles
               if r.admit_ts is not None and r.admit_ts < w1
               and (r.done_ts is None or r.done_ts > w0)]
    finished = [r for r in touched if r.done and r.done_ts <= w1]
    checks["no_compile_after_set_up"] = (
        run_counters["serving.prefill_compiles"]
        + run_counters["serving.decode_compiles"]) == 0
    checks["finished_at_their_budget"] = all(
        r.outcome == "length" and len(r.tokens) == r.max_new_tokens
        for r in finished)
    mask_id = int(ctx.config["mask_token_id"])
    checks["tokens_in_vocabulary"] = all(
        0 <= t < vocab and t != mask_id for r in touched for t in r.tokens)
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
