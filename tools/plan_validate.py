"""Planner validation: predicted single-chip variant ranking vs measurement.

VERDICT r3 #7 — a cost-model planner that has never predicted a measured
outcome is a hypothesis, not a tool. The multi-chip topologies need a pod;
what IS measurable on one chip are bench.py's own variants (batch size,
selective recompute, fused-CE chunk). This tool:

  1. AOT-compiles the bench-config GPT train step per variant (virtual CPU
     device; nothing executes) and reads the XLA cost model
     (auto_parallel/planner.score_compiled);
  2. predicts tokens/s up to a constant: tokens_per_step / time_proxy —
     twice: from the raw AOT score (the pre-registered model) and from the
     remat-replay-corrected score (round 5; see the correction comment in
     main());
  3. with --measured BENCH_HISTORY.jsonl, joins measured tokens/s by tag
     and reports the pairwise rank agreement for both models, plus the
     corrected model's miss pairs with their measured margins.

The scan-trainer variant is deliberately OUT of scope: its win is dispatch
overlap across steps, invisible to a per-program cost model — predicting it
would be pretending.

Usage:
  python tools/plan_validate.py [--quick] [--measured BENCH_HISTORY.jsonl]
One JSON line per variant (tag, score, pred_tokens_per_s_rel AND the
replay-corrected score_corrected / pred_tokens_per_s_rel_corrected — rows
print after the correction pass); then a summary line. On chip: run the
watcher's bench variants first, then re-run with --measured to close the
loop.
"""
from __future__ import annotations

import _bootstrap  # noqa: F401  (checkout-hermetic sys.path)

import argparse
import itertools
import json
import sys

VARIANTS = [
    # tag must match BENCH_HISTORY extra tags (watcher queue names)
    {"tag": "b8", "batch": 8},
    {"tag": "b16", "batch": 16},
    {"tag": "b24", "batch": 24},
    {"tag": "b32", "batch": 32},
    {"tag": "b16_selective", "batch": 16, "recompute": "selective"},
    {"tag": "b32_selective", "batch": 32, "recompute": "selective"},
    {"tag": "ce4096_b16", "batch": 16, "ce_chunk": 4096},
]
QUICK = {"b8", "b16", "b16_selective"}


def score_variant(v, seq, quick):
    import jax
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.auto_parallel.planner import score_compiled
    from paddle_tpu.distributed.mesh import set_hybrid_communicate_group
    from paddle_tpu.models import GPTConfig, GPTForPretraining
    import paddle_tpu.distributed as dist

    set_hybrid_communicate_group(None)
    strategy = dist.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 1}
    fleet.init(is_collective=True, strategy=strategy)
    paddle.seed(0)
    if v.get("ce_chunk"):
        paddle.set_flags({"fused_ce_chunk": int(v["ce_chunk"])})
    # quick mode shrinks the model, NOT the variant axes (ranking within the
    # shrunken family still exercises the model); full mode = bench config
    cfg = GPTConfig(vocab_size=50304, hidden_size=768, num_layers=12,
                    num_heads=12, max_seq_len=seq,
                    use_recompute=v.get("recompute") == "selective",
                    recompute_granularity="selective") if not quick else \
        GPTConfig(vocab_size=1024, hidden_size=128, num_layers=2,
                  num_heads=4, max_seq_len=seq,
                  use_recompute=v.get("recompute") == "selective",
                  recompute_granularity="selective")
    model = GPTForPretraining(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())
    eng = fleet.distributed_engine(model, opt)
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (v["batch"], seq)),
                      jnp.int64)
    labels = jnp.roll(ids, -1, 1)
    jf = eng._build([ids, labels])
    comp = jf.lower(eng.params, eng.opt_state, jnp.float32(1e-4),
                    jnp.int32(1), jax.random.key(0), ids, labels).compile()
    m = score_compiled(comp)
    # remat-corrected peak (VERDICT r4 weak #4): live state + policy-aware
    # saved residuals — the component XLA's AOT memory analysis misses, so
    # b32_selective's predicted peak finally differs from b32's
    from paddle_tpu.distributed.auto_parallel.planner import (
        policy_peak_bytes, saved_residual_bytes)

    try:
        res_b = saved_residual_bytes(eng.analysis_loss(ids, labels),
                                     eng.params)
        m["peak_policy_bytes"] = policy_peak_bytes(m, res_b)
        m["residual_bytes"] = res_b
    except Exception as e:
        m["peak_policy_bytes"] = None
        m["residual_bytes"] = None
        print(f"# residual analysis failed: {type(e).__name__}: {e}",
              file=sys.stderr)
    paddle.set_flags({"fused_ce_chunk": 0})
    return m


def apply_replay_correction(rows, seq):
    """Remat-replay corrected score (round 5, POST-HOC — the pre-registered
    table stands as committed; this corrected model's
    falsifiable content is for configs measured after it). The round-5
    on-chip rows showed selective remat costing ~15% measured throughput
    while the AOT score separated the variants by only ~1.5%: XLA's
    CPU-target AOT cost_analysis barely surfaces the backward-pass replay.
    The missing term is HBM traffic: every residual the policy chooses NOT
    to save is recomputed in backward — written once and read once (2x its
    bytes). That byte count is exactly the saved-residual delta between the
    plain twin and the policy variant, which the round-4 policy-peak
    machinery already traces — so the correction introduces no new fit
    constants. Mutates each row in place: adds score_corrected and
    pred_tokens_per_s_rel_corrected (equal to the raw values for non-remat
    variants or when either residual trace failed)."""
    by_tag = {r["tag"]: r for r in rows}
    batches = {v["tag"]: v["batch"] for v in VARIANTS}
    for r in rows:
        r["score_corrected"] = r["score"]
        if r["tag"].endswith("_selective"):
            twin = by_tag.get(r["tag"][: -len("_selective")])
            if (twin and r.get("residual_bytes") is not None
                    and twin.get("residual_bytes") is not None):
                replay = 2 * max(0, twin["residual_bytes"]
                                 - r["residual_bytes"])
                r["score_corrected"] = r["score"] + replay
        batch = r.get("batch") or batches[r["tag"]]
        r["pred_tokens_per_s_rel_corrected"] = \
            batch * seq / r["score_corrected"]


def measured_tokens(path, seq):
    """tag -> tokens/s from BENCH_HISTORY.jsonl rows (best per tag). The
    tag is DERIVED from the recorded variant knobs so it matches VARIANTS:
    b<batch>[_selective], or ce<chunk>_b<batch>. Rows that are NOT clean
    joins are skipped: scan-trainer runs (dispatch overlap is out of the
    cost model's scope), Pallas kernel variants (pallas_ln/loss),
    full/boolean recompute (a different program than the prediction —
    round 3's b32 only ran WITH recompute, which is the point: the
    predicted-fastest config was the one that couldn't run plain), wrong
    seq, and multi-device rows. Autotuned-flash rows ARE admitted (round
    5): their tuned [512,512] blocks equal the heuristic's choice, so they
    ran the default program."""
    out = {}
    with open(path) as f:
        for ln in f:
            try:
                row = json.loads(ln)
            except json.JSONDecodeError:
                continue
            ex = row.get("extra", {}) or {}
            val = row.get("value")
            if not isinstance(val, (int, float)):
                continue
            if ex.get("seq") != seq or ex.get("devices") not in (1, None):
                continue
            if ex.get("hidden") not in (768, None) \
                    or ex.get("layers") not in (12, None):
                continue  # a medium-model row must not join base predictions
            # bench.py treats ANY non-empty env value as knob-ON (even "0"),
            # so any recorded value disqualifies the row as a plain variant.
            # autotune rows are NOT excluded (round 5): their tuned flash
            # blocks equal the heuristic's, the same program as a plain
            # row. Structurally different programs (scan trainer, pallas
            # kernel variants) stay out.
            # prefetch rows are excluded like scan: input-staging overlap is
            # dispatch-level, invisible to a per-program cost model.
            # microbatch-accumulation rows (PADDLE_TPU_BENCH_ACCUM) are a
            # structurally different program (scan over K microbatches +
            # deferred grad reduce) — also out
            if any(ex.get(k) for k in ("scan", "pallas_ln", "pallas_loss",
                                       "prefetch", "microbatches")):
                continue
            rec = ex.get("recompute")
            if rec not in (None, "", False, "selective"):
                continue  # full/boolean recompute: not the predicted program
            batch = ex.get("batch")
            if batch is None:
                continue
            if ex.get("ce_chunk"):
                if rec == "selective":
                    continue  # combined knobs: no matching predicted variant
                tag = f"ce{ex['ce_chunk']}_b{batch}"
            elif rec == "selective":
                tag = f"b{batch}_selective"
            else:
                tag = f"b{batch}"
            out[tag] = max(out.get(tag, 0), val)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--quick", action="store_true",
                    help="tiny model (CPU test); full mode uses the bench "
                         "config and takes minutes per variant")
    ap.add_argument("--measured", default=None,
                    help="BENCH_HISTORY.jsonl to compare predicted vs "
                         "measured ranking")
    ap.add_argument("--tags", default=None,
                    help="comma list restricting the variants scored")
    ap.add_argument("--resolution", type=float, default=None,
                    help="override the planner's stated prediction "
                         "resolution (fraction) for batch-axis abstention")
    args = ap.parse_args()

    from paddle_tpu.device.probe import force_cpu_platform

    force_cpu_platform()

    only = set(args.tags.split(",")) if args.tags else None
    rows = []
    for v in VARIANTS:
        if args.quick and v["tag"] not in QUICK:
            continue
        if only and v["tag"] not in only:
            continue
        m = score_variant(v, args.seq, args.quick)
        tokens = v["batch"] * args.seq
        rows.append({"tag": v["tag"], "batch": v["batch"],
                     "score": m["score"],
                     "residual_bytes": m.get("residual_bytes"),
                     "peak_mb": round(m["peak_bytes"] / 1e6, 1),
                     "peak_policy_mb": (
                         round(m["peak_policy_bytes"] / 1e6, 1)
                         if m.get("peak_policy_bytes") else None),
                     "pred_tokens_per_s_rel": tokens / m["score"]})
        # progress line while variants score (minutes each in full mode); the
        # authoritative per-variant row is printed AFTER the replay
        # correction below, so corrected scores are in the tool output
        print(f"# scored {v['tag']}", file=sys.stderr, flush=True)

    apply_replay_correction(rows, args.seq)
    for r in rows:
        # one JSON line per variant, emitted post-correction: carries both
        # the raw AOT score/prediction and score_corrected /
        # pred_tokens_per_s_rel_corrected (ADVICE r5 #3 — previously the
        # rows printed pre-correction and the corrected values were
        # unrecoverable from tool output)
        print(json.dumps(r), flush=True)

    def ranked(key):
        return sorted(rows, key=lambda r: -r[key])

    from paddle_tpu.distributed.auto_parallel.planner import (
        PREDICTION_RESOLUTION, pair_verdict)

    resolution = (args.resolution if args.resolution is not None
                  else PREDICTION_RESOLUTION)
    pred = ranked("pred_tokens_per_s_rel")
    pred_c = ranked("pred_tokens_per_s_rel_corrected")
    summary = {"predicted_rank": [r["tag"] for r in pred],
               "predicted_rank_corrected": [r["tag"] for r in pred_c],
               "resolution": resolution}
    if args.measured:
        meas = measured_tokens(args.measured, args.seq)
        vmeta = {v["tag"]: v for v in VARIANTS}

        def batch_only(a, b):
            """Same program family, different batch: the axis the model's
            stated resolution cannot rank (planner.pair_verdict)."""
            va, vb = vmeta.get(a, {}), vmeta.get(b, {})
            return (va.get("recompute") == vb.get("recompute")
                    and va.get("ce_chunk") == vb.get("ce_chunk")
                    and va.get("batch") != vb.get("batch"))

        def agreement(order, key):
            # `order` is in predicted-rank order, so for each (a, b) pair
            # the model predicts a >= b; agreement = measurement concurring.
            # Batch-axis pairs predicted inside the stated resolution are
            # ABSTAINED (reported, not scored): the known b16/b24 regime
            # where ranking would be pretending (VERDICT r5 next #5)
            both = [r["tag"] for r in order if r["tag"] in meas]
            preds = {r["tag"]: r[key] for r in order}
            agree = total = 0
            misses, abstained = [], []
            for a, b in itertools.combinations(both, 2):
                verdict, margin = pair_verdict(
                    preds[a], preds[b], batch_only(a, b),
                    resolution=resolution)
                if verdict == "not_decidable":
                    abstained.append([a, b, round(margin, 4)])
                    continue
                total += 1
                if meas[a] >= meas[b]:
                    agree += 1
                else:
                    misses.append([a, b, round(meas[b] / meas[a] - 1, 4)])
            return both, (round(agree / total, 3) if total else None), \
                total, misses, abstained

        both, pw, total, misses, abst = agreement(
            pred, "pred_tokens_per_s_rel")
        _, pw_c, total_c, misses_c, abst_c = agreement(
            pred_c, "pred_tokens_per_s_rel_corrected")
        summary.update({
            "measured_tags": both,
            "measured_rank": sorted(both, key=lambda t: -meas[t]),
            # agreement over DECIDED pairs only (abstentions excluded)
            "pairwise_agreement": pw,
            "pairwise_agreement_corrected": pw_c,
            "pairs": total,
            "pairs_corrected": total_c,
            # each abstention: [pred-faster, pred-slower, predicted margin]
            # — batch-axis pairs inside the model's stated resolution
            "abstained_pairs_corrected": abst_c,
            # each miss: [predicted-faster, measured-faster, measured margin]
            "miss_pairs_corrected": misses_c})
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
