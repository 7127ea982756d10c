"""Find the knee of an open-loop serving cell, once, by hand, on the chip:

    python3 benchmarks/sweep_rate.py --workload serve-gpt2-large-chat \\
        --rates 4,6,8,10,12 --seconds 20 --seed 1

One process, one set-up, one window per rate with the cell's own traffic mix,
lead-in and drain. The knee is the highest rate at which the backlog does
not grow over a window: the queue is no deeper at its end than at its start
and every sampled request finishes within the drain. The cell then runs at
0.8 of it, written into its traffic file as a number; the readings go into
PERF.md. Not part of a check: the benchmark offers load at a fixed rate and
never searches for one.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import run as bench_run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    resolved = bench_run.resolve(args.workload)

    import jax

    if jax.devices()[0].platform != "tpu":
        print("sweep_rate.py: no TPU; a knee is only found on the chip",
              file=sys.stderr)
        return 1
    sys.path.insert(0, bench_run.ROOT)
    import paddle_tpu as paddle

    from benchmarks.lib import stats, traffic as traffic_lib

    serve = bench_run.load_module("runners", "serve")
    ctx = bench_run.Run(resolved, args.seed, args.seconds, False)
    traf = ctx.traffic
    vocab = int(ctx.config["vocab_size"])
    with paddle.amp.auto_cast(dtype="bfloat16"):
        eng, _, _, _, checks = serve.build_engine(ctx)
        for rate in (float(r) for r in args.rates.split(",")):
            mix = dict(traf, arrival=dict(traf["arrival"], rate_rps=rate))
            rows = traffic_lib.requests(mix, args.seed, args.seconds, vocab)
            records, steps, w0, w1, end = serve.drive_open(
                eng, rows, dict(mix["sampling"]), float(mix["lead_in_s"]),
                args.seconds, float(mix["drain_s"]), ctx, 0.0)
            sample, failed, ttft, tpot = serve.open_loop_metrics(records, end)

            def waiting(at):
                return sum(1 for r in records if r["due"] <= at and (
                    r["admit"] is None or r["admit"] > at))

            tokens = sum(r["tokens"] for r in sample)
            ctx.note("rate", {
                "rate_rps": rate, "sample": len(sample), "failed": failed,
                "waiting_at_window_start": waiting(w0),
                "waiting_at_window_end": waiting(w1),
                "drain_s": end - w1,
                "ttft_p50_ms": stats.median(ttft),
                "ttft_p95_ms": stats.percentile(ttft, 0.95),
                "tpot_p50_ms": stats.median(tpot),
                "tpot_p95_ms": stats.percentile(tpot, 0.95),
                "sample_tokens_per_s": tokens / args.seconds,
                "step_ms_p50": stats.median(
                    [(b - a) * 1e3 / eng.steps_per_dispatch
                     for a, b, _ in steps])})
            eng.run()                       # empty the engine between rates
            time.sleep(0.5)
    print(json.dumps({"checks": checks}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
