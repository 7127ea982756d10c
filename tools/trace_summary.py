"""Summarize telemetry artifacts: StepTelemetry/serve JSONL, chrome-trace
JSON, or a metrics-registry snapshot.

The offline half of paddle_tpu/observability: point it at what a run wrote
and get per-region/per-step tables of what the REAL run did.

  python tools/trace_summary.py /tmp/tele/step_telemetry.jsonl
  python tools/trace_summary.py /tmp/serve/serve.jsonl      # serve_request
  python tools/trace_summary.py /tmp/slo/alerts.jsonl       # alert timeline
  python tools/trace_summary.py /tmp/paddle_tpu_profile/host_1234.json
  python tools/trace_summary.py /tmp/paddle_tpu_profile/   # merged dir
  python tools/trace_summary.py <profile dir>/.../host.xplane.pb  # by scope
  python tools/trace_summary.py snapshot.json  # exporter /metrics.json dump
  python tools/trace_summary.py /tmp/w0 /tmp/w1     # fleet: merged report
  python tools/trace_summary.py '/tmp/workers/w*'   # fleet: glob of dirs

Fleet mode (ISSUE 14): more than one path — or a glob matching more than
one — pools every worker's JSONL records into ONE merged report (per-
worker record counts + pooled percentile tables) and merges any metrics
snapshots losslessly via the fleet histogram-merge (bucket counts add,
percentiles recomputed), mirroring what the live FleetCollector serves
at /fleet/metrics.

A device trace (`*.xplane.pb`, what `paddle.profiler.Profiler` and
`jax.profiler` write; a directory is searched for the newest) gets the
program's table by scope (observability/device_trace.py): device seconds by
named scope with forward and backward apart, by kernel, by executable, the
unnamed and metadata-less remainder, collectives by opcode, and every idle
gap of chip 0 put down to the `serve.*` / `engine.*` span open on the host
then. `--window NAME` clips to a host annotation of that name.

Otherwise the format is auto-detected: a JSONL stream of step records gets the per-step
throughput table (plus a TTFT/TPOT/step-time p50/p90/p99 percentile table
when serve_request records are present); a JSON object with "histograms"
(the exporter's /metrics.json shape, also written into flight-recorder
state.json) gets the registry-percentile table; anything loadable by
profiler.load_profiler_result gets the per-span table (calls/total/avg/
max/min, the Profiler.summary layout) and, where the trace holds the
program's own spans (`get_tracer().export_chrome_trace(path)`), the span
ring's table: self time a span, the time no span was open, every jit phase
of the process inside and outside a registered executable's first call,
the largest by function: where a start-up went. Output ends with one
machine-readable JSON summary line, matching the other tools/ probes'
convention.
"""
import json
import os
import sys

import _bootstrap  # noqa: F401  (repo-root sys.path)


def _fmt_table(header, rows):
    widths = [max(len(str(r[i])) for r in [header] + rows)
              for i in range(len(header))]
    def line(r):
        return "  ".join(str(c).rjust(w) if i else str(c).ljust(w)
                         for i, (c, w) in enumerate(zip(r, widths)))
    print(line(header))
    for r in rows:
        print(line(r))


def _is_snapshot(path):
    """A (possibly pretty-printed) JSON object carrying a metrics-registry
    snapshot: the exporter's /metrics.json or a flight-recorder state.json."""
    try:
        with open(path) as f:
            doc = json.load(f)
    except (json.JSONDecodeError, UnicodeDecodeError):
        return False
    return isinstance(doc, dict) and (
        "histograms" in doc
        or "histograms" in doc.get("metrics", {}))


def _is_jsonl(path):
    with open(path) as f:
        first = f.readline().strip()
    if not first:
        return False
    try:
        doc = json.loads(first)
    except json.JSONDecodeError:
        return False
    return isinstance(doc, dict) and "traceEvents" not in doc


def _pctl(xs, q):
    """Exact linear-interpolated percentile (numpy.percentile 'linear')."""
    if not xs:
        return None
    xs = sorted(xs)
    k = (len(xs) - 1) * q
    lo = int(k)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def _pctl_table(series):
    """series: [(label, unit, values)] -> printed p50/p90/p99 table + dict."""
    rows, out = [], {}
    for label, unit, xs in series:
        if not xs:
            continue
        ps = {q: _pctl(xs, q / 100) for q in (50, 90, 99)}
        rows.append([f"{label}_{unit}", len(xs)] +
                    [f"{ps[q]:.3f}" for q in (50, 90, 99)])
        out[label] = {"n": len(xs),
                      **{f"p{q}_{unit}": round(ps[q], 4) for q in (50, 90, 99)}}
    if rows:
        _fmt_table(["percentiles", "n", "p50", "p90", "p99"], rows)
    return out


def _load_jsonl(path):
    recs = []
    with open(path) as f:
        for ln in f:
            ln = ln.strip()
            if ln:
                recs.append(json.loads(ln))
    return recs


def summarize_steps(path):
    return summarize_records(_load_jsonl(path))


def summarize_records(recs, emit_json=True):
    if not recs:
        print("no records")
        return {}
    serve_reqs = [r for r in recs if r.get("event") == "serve_request"]
    serve_steps = [r for r in recs if r.get("event") == "serve_step"]
    routes = [r for r in recs if r.get("event") == "route"]
    health = [r for r in recs if r.get("event") == "health"]
    alerts = [r for r in recs if r.get("event") == "alert"]
    caps = [r for r in recs if r.get("event") == "capacity"]
    regs = [r for r in recs if r.get("event") == "exec_registry"]
    recs = [r for r in recs if r.get("event") not in ("serve_request",
                                                      "serve_step", "health",
                                                      "route", "alert",
                                                      "capacity",
                                                      "exec_registry")]
    if not recs and caps and not (serve_reqs or serve_steps or routes
                                  or health):
        # capacity.jsonl (plus, in one merged view, alerts.jsonl): the
        # scaling timeline joined against the alert timeline so "alert
        # fired -> scaled -> resolved" reads as one story
        out = _summarize_capacity(caps, alerts, emit_json=False)
        if alerts:
            out["alerts"] = _summarize_alerts(alerts, emit_json=False)
        if emit_json:
            print(json.dumps({"summary": out}))
        return out
    if not recs and alerts and not (serve_reqs or serve_steps or routes
                                    or health):
        return _summarize_alerts(alerts, emit_json=emit_json)
    if not recs and health:
        out = _summarize_health(health, emit_json=False)
        if alerts:
            out["alerts"] = _summarize_alerts(alerts, emit_json=False)
        if emit_json:
            print(json.dumps({"summary": out}))
        return out
    if not recs:
        out = _summarize_serve(serve_reqs, serve_steps, routes,
                               regs=regs, emit_json=False)
        if alerts:
            out["alerts"] = _summarize_alerts(alerts, emit_json=False)
        if caps:
            out["capacity"] = _summarize_capacity(caps, alerts,
                                                  emit_json=False)
        if emit_json:
            print(json.dumps({"summary": out}))
        return out
    n = len(recs)

    def col(k):
        return [r[k] for r in recs if isinstance(r.get(k), (int, float))]

    def mean(xs):
        return sum(xs) / len(xs) if xs else None

    walls = col("wall_time_s")
    rows = []
    for k, fmt in (("wall_time_s", "{:.4f}"), ("reader_cost_s", "{:.4f}"),
                   ("tokens_per_sec", "{:.1f}"), ("samples_per_sec", "{:.1f}"),
                   ("tflops_per_sec", "{:.2f}"), ("mfu", "{:.4f}"),
                   ("loss", "{:.4f}")):
        xs = col(k)
        if xs:
            rows.append([k, len(xs), fmt.format(mean(xs)),
                         fmt.format(min(xs)), fmt.format(max(xs))])
    _fmt_table(["field", "n", "mean", "min", "max"], rows)
    pcts = _pctl_table([("step_time", "ms", [w * 1e3 for w in walls])])
    last = recs[-1]
    summary = {
        "kind": "step_telemetry", "steps": n,
        "mean_wall_time_s": round(mean(walls), 6) if walls else None,
        "total_wall_time_s": round(sum(walls), 4) if walls else None,
        "mean_tokens_per_sec": (round(mean(col("tokens_per_sec")), 1)
                                if col("tokens_per_sec") else None),
        "mean_mfu": round(mean(col("mfu")), 4) if col("mfu") else None,
        "jit_compiles": last.get("jit_compiles"),
        "jit_recompiles": last.get("jit_recompiles"),
        "jit_compile_ms": last.get("jit_compile_ms"),
        "nan_inf_hits": last.get("nan_inf_hits"),
        "percentiles": pcts,
    }
    # ZeRO weight-update sharding collectives (distributed/grad_comm.py):
    # the records carry running byte totals for the gradient reduce-scatter
    # and weight all-gather; the delta across the trace is what THIS run
    # put on the wire (K-independent per optimizer step)
    rs, ag = col("grad_comm_rs_bytes"), col("grad_comm_ag_bytes")
    if rs or ag:
        zsteps = sum(1 for r in recs if r.get("zero_update"))
        summary["grad_comm_rs_bytes"] = rs[-1] if rs else None
        summary["grad_comm_ag_bytes"] = ag[-1] if ag else None
        summary["grad_comm_rs_bytes_delta"] = (rs[-1] - rs[0]) if rs else None
        summary["grad_comm_ag_bytes_delta"] = (ag[-1] - ag[0]) if ag else None
        summary["zero_update_steps"] = zsteps
        print(f"grad_comm: rs_bytes={summary['grad_comm_rs_bytes']} "
              f"(+{summary['grad_comm_rs_bytes_delta']}) "
              f"ag_bytes={summary['grad_comm_ag_bytes']} "
              f"(+{summary['grad_comm_ag_bytes_delta']}) "
              f"zero_update_steps={zsteps}")
    # fsdp gather-prefetch window (ISSUE 20): engaged steps carry the
    # resolved window depth and the analytic live-window bytes
    fsdp_recs = [r for r in recs if r.get("fsdp")]
    if fsdp_recs:
        last_f = fsdp_recs[-1]
        summary["fsdp_steps"] = len(fsdp_recs)
        summary["fsdp_prefetch"] = last_f.get("fsdp_prefetch")
        summary["fsdp_window_bytes"] = last_f.get("fsdp_window_bytes")
        print(f"fsdp: steps={summary['fsdp_steps']} "
              f"prefetch={summary['fsdp_prefetch']} "
              f"window_bytes={summary['fsdp_window_bytes']}")
    if serve_reqs or serve_steps or routes:
        summary["serve"] = _summarize_serve(serve_reqs, serve_steps, routes,
                                            regs=regs, emit_json=False)
    if health:
        summary["health"] = _summarize_health(health, emit_json=False)
    if alerts:
        summary["alerts"] = _summarize_alerts(alerts, emit_json=False)
    if emit_json:
        print(json.dumps({"summary": summary}))
    return summary


def _summarize_health(health, emit_json=True):
    """health.jsonl records (observability/health.py): grad-norm/update-ratio
    percentile table + anomaly timeline naming the offending parameter."""

    def col(k):
        return [r[k] for r in health if isinstance(r.get(k), (int, float))]

    pcts = _pctl_table([
        ("grad_norm", "l2", col("grad_norm")),
        ("weight_norm", "l2", col("weight_norm")),
        ("update_ratio", "frac", col("update_ratio")),
    ])
    anomalies = [r for r in health
                 if r.get("nonfinite_count") or r.get("spike")]
    if anomalies:
        rows = []
        for r in anomalies:
            kind = ("nonfinite" if r.get("nonfinite_count") else "spike")
            gn = r.get("grad_norm")
            rows.append([r.get("step"), kind,
                         r.get("first_nonfinite_param") or "-",
                         r.get("nonfinite_count") or 0,
                         f"{gn:.4g}" if gn is not None else "inf/nan"])
        print("anomaly timeline:")
        _fmt_table(["step", "kind", "param", "nonfinite", "grad_norm"], rows)
    nf = [r for r in health if r.get("nonfinite_count")]
    summary = {
        "kind": "health_telemetry",
        "records": len(health),
        "first_step": health[0].get("step"),
        "last_step": health[-1].get("step"),
        "anomalies": len(anomalies),
        "nonfinite_steps": len(nf),
        "spike_steps": len([r for r in health if r.get("spike")]),
        "first_nonfinite_param": (nf[0].get("first_nonfinite_param")
                                  if nf else None),
        "percentiles": pcts,
    }
    if emit_json:
        print(json.dumps({"summary": summary}))
    return summary


def _summarize_alerts(alerts, emit_json=True):
    """alerts.jsonl (observability/slo.py transition events): the alert
    timeline — every pending/firing/resolved transition in ts order, then
    one per-SLO roll-up with fire->resolve durations and peak burn."""
    alerts = sorted(alerts, key=lambda r: r.get("ts", 0))
    t0 = alerts[0].get("ts", 0)
    rows = [[f"{r.get('ts', 0) - t0:+.3f}s", r.get("slo"), r.get("state"),
             r.get("severity"),
             f"{r.get('burn', 0):.2f}x",
             (f"{r['duration_s']:.3f}s" if "duration_s" in r else "-")]
            for r in alerts]
    print("alert timeline:")
    _fmt_table(["t", "slo", "state", "severity", "burn", "fire->resolve"],
               rows)
    per = {}
    for r in alerts:
        s = per.setdefault(r.get("slo"), {
            "fires": 0, "resolves": 0, "peak_burn": 0.0,
            "severity": r.get("severity"), "total_firing_s": 0.0,
            "unresolved": False})
        if r.get("state") == "firing":
            s["fires"] += 1
            s["unresolved"] = True
            s["severity"] = r.get("severity") or s["severity"]
        elif r.get("state") == "resolved":
            s["resolves"] += 1
            s["unresolved"] = False
            s["total_firing_s"] += float(r.get("duration_s", 0.0))
        s["peak_burn"] = max(s["peak_burn"],
                             float(r.get("peak_burn", r.get("burn", 0.0))))
    rows = [[name, s["severity"], s["fires"], s["resolves"],
             f"{s['peak_burn']:.2f}x", f"{s['total_firing_s']:.3f}s",
             "yes" if s["unresolved"] else "no"]
            for name, s in sorted(per.items())]
    print("per-SLO:")
    _fmt_table(["slo", "severity", "fires", "resolves", "peak_burn",
                "firing_s", "still_firing"], rows)
    summary = {
        "kind": "alert_timeline",
        "events": len(alerts),
        "span_s": round(alerts[-1].get("ts", 0) - t0, 3),
        "slos": {name: {k: (round(v, 4) if isinstance(v, float) else v)
                        for k, v in s.items()}
                 for name, s in per.items()},
        "still_firing": sorted(n for n, s in per.items()
                               if s["unresolved"]),
    }
    if emit_json:
        print(json.dumps({"summary": summary}))
    return summary


def _summarize_capacity(caps, alerts=(), emit_json=True):
    """capacity.jsonl (observability/capacity.py decision records): the
    scaling timeline. When alert records ride along (fleet mode, or the
    drill's merged stream) the two are interleaved by ts into ONE table,
    so "alert fired -> scaled out -> resolved -> scaled back" reads as a
    single story with the controller's reaction/recovery latencies."""
    caps = sorted(caps, key=lambda r: r.get("ts", 0))
    alerts = sorted(alerts, key=lambda r: r.get("ts", 0))
    merged = sorted(
        [("capacity", r) for r in caps] + [("alert", r) for r in alerts],
        key=lambda kr: kr[1].get("ts", 0))
    t0 = merged[0][1].get("ts", 0)
    # a controller polled from a drive loop logs hundreds of steady holds
    # between actions; the table keeps only the eventful rows (actions,
    # cooldown/flap holds, alerts) — the counts below stay complete
    shown = [(k, r) for k, r in merged
             if k == "alert" or r.get("action") != "hold"
             or r.get("reason") != "steady"]
    elided = len(merged) - len(shown)
    rows = []
    for kind, r in shown:
        if kind == "capacity":
            sig = r.get("signals", {})
            firing = sig.get("firing") or []
            detail = r.get("reason", "")
            if sig:
                detail += (f" occ={sig.get('occupancy', 0):.2f}"
                           f" q={sig.get('queued', 0)}"
                           f" firing={len(firing)}")
            rows.append([f"{r.get('ts', 0) - t0:+.3f}s", kind,
                         r.get("action"),
                         f"{r.get('replicas')}->{r.get('target')}", detail])
        else:
            rows.append([f"{r.get('ts', 0) - t0:+.3f}s", kind,
                         f"{r.get('slo')}:{r.get('state')}", "-",
                         f"{r.get('severity') or ''} "
                         f"burn={r.get('burn', 0):.2f}x"])
    print("scaling timeline:")
    _fmt_table(["t", "event", "action", "replicas", "detail"], rows)
    if elided:
        print(f"({elided} steady holds elided)")
    actions = {}
    for r in caps:
        a = r.get("action")
        actions[a] = actions.get(a, 0) + 1
    counts = [r.get("replicas") for r in caps
              if isinstance(r.get("replicas"), int)]
    targets = [r.get("target") for r in caps
               if isinstance(r.get("target"), int)]
    # controller latencies vs the alert stream: fired -> first scale_out
    # (reaction) and fired -> last resolve (recovery, the drill's pin)
    first_fire = next((r.get("ts") for r in alerts
                       if r.get("state") == "firing"), None)
    first_out = next((r.get("ts") for r in caps
                      if r.get("action") == "scale_out"), None)
    last_resolve = next((r.get("ts") for r in reversed(alerts)
                         if r.get("state") == "resolved"), None)
    summary = {
        "kind": "capacity_timeline",
        "decisions": len(caps),
        "span_s": round(caps[-1].get("ts", 0) - caps[0].get("ts", 0), 3),
        "actions": actions,
        "scale_outs": actions.get("scale_out", 0),
        "scale_ins": actions.get("scale_in", 0),
        "replicas_initial": counts[0] if counts else None,
        "replicas_peak": max(targets + counts) if counts else None,
        "replicas_final": (targets[-1] if targets else
                           (counts[-1] if counts else None)),
    }
    if first_fire is not None and first_out is not None:
        summary["reaction_s"] = round(first_out - first_fire, 3)
    if first_fire is not None and last_resolve is not None:
        summary["recovery_s"] = round(last_resolve - first_fire, 3)
    line = (f"capacity: scale_outs={summary['scale_outs']} "
            f"scale_ins={summary['scale_ins']} "
            f"replicas {summary['replicas_initial']}"
            f"->{summary['replicas_peak']}->{summary['replicas_final']}")
    if "recovery_s" in summary:
        line += (f"  reaction={summary.get('reaction_s', '-')}s "
                 f"recovery={summary['recovery_s']}s")
    print(line)
    if emit_json:
        print(json.dumps({"summary": summary}))
    return summary


def _summarize_serve(serve_reqs, serve_steps, routes=(), regs=(),
                     emit_json=True):
    """Percentile table over serve_request/serve_step/route records
    (ServingEngine + ReplicaRouter sink streams): TTFT/TPOT/queue-wait/
    request-wall + occupancy, plus the paged-KV gauges (pages in use,
    prefix hit rate), router placement breakdown, and the executable-
    registry rollup (per-label hit/miss/eviction + cold-vs-warm compile
    percentiles) when the engine emitted exec_registry records."""

    def col(recs, k, scale=1.0):
        return [r[k] * scale for r in recs
                if isinstance(r.get(k), (int, float))]

    # per-request speculative acceptance rate (requests that proposed at
    # least one draft token — spec fields ride on serve_request records)
    accept_rates = [r["spec_accepted"] / r["spec_proposed"]
                    for r in serve_reqs if r.get("spec_proposed")]
    pcts = _pctl_table([
        ("ttft", "ms", col(serve_reqs, "ttft_s", 1e3)),
        ("tpot", "ms", col(serve_reqs, "tpot_s", 1e3)),
        ("queue_wait", "ms", col(serve_reqs, "queue_wait_s", 1e3)),
        ("request_wall", "ms", col(serve_reqs, "wall_s", 1e3)),
        ("occupancy", "frac", col(serve_steps, "occupancy")),
        ("spec_accept_rate", "frac", accept_rates),
        ("pages_in_use", "pages", col(serve_steps, "pages_in_use")),
        ("route_queue_depth", "n", col(routes, "queue_depth")),
    ])
    toks = col(serve_reqs, "new_tokens")
    # terminal-outcome breakdown (ok|eos|length|drained|error) — older
    # streams without the field fall back to finish_reason
    outcomes = {}
    for r in serve_reqs:
        o = r.get("outcome") or r.get("finish_reason") or "ok"
        outcomes[o] = outcomes.get(o, 0) + 1
    summary = {
        "kind": "serve_telemetry",
        "requests": len(serve_reqs),
        "decode_dispatches": len(serve_steps),
        "total_new_tokens": int(sum(toks)) if toks else 0,
        "outcomes": outcomes,
        "errors": outcomes.get("error", 0),
        "percentiles": pcts,
    }
    if outcomes:
        print("outcomes: " + "  ".join(f"{k}={v}" for k, v in
                                       sorted(outcomes.items())))
    # speculative-decoding rollup: serve_step rows carry per-dispatch
    # proposed/accepted/bonus; steps_per_dispatch is the target forwards a
    # dispatch cost (1 for a verify window), so forwards / decode tokens
    # is the dispatches-per-token the spec bench pins below 1.0
    spec_steps = [r for r in serve_steps if r.get("spec")]
    if spec_steps or accept_rates:
        proposed = sum(r.get("spec_proposed", 0) for r in spec_steps)
        accepted = sum(r.get("spec_accepted", 0) for r in spec_steps)
        bonus = sum(r.get("spec_bonus", 0) for r in spec_steps)
        forwards = sum(r.get("steps_per_dispatch", 1) for r in serve_steps)
        step_toks = sum(r.get("tokens", 0) for r in serve_steps)
        dpt = forwards / step_toks if step_toks else None
        summary["spec"] = {
            "verify_dispatches": len(spec_steps),
            "proposed": proposed, "accepted": accepted, "bonus": bonus,
            "accept_rate": (round(accepted / proposed, 4)
                            if proposed else None),
            "target_forwards": forwards,
            "dispatches_per_token": (round(dpt, 4)
                                     if dpt is not None else None),
        }
        print(f"speculative: verify_dispatches={len(spec_steps)} "
              f"proposed={proposed} accepted={accepted} bonus={bonus} "
              f"accept_rate={summary['spec']['accept_rate']}")
        if dpt is not None:
            print(f"target dispatches per decoded token: {dpt:.3f} "
                  f"({forwards} forwards / {step_toks} tokens)")
    # paged-KV gauges ride on serve_step records (engine.py emits them only
    # on the paged layout); report the final sample — the steady state
    hit_rates = col(serve_steps, "prefix_hit_rate")
    if hit_rates:
        summary["prefix_hit_rate"] = round(hit_rates[-1], 4)
        summary["pages_in_use_last"] = (col(serve_steps, "pages_in_use")
                                        or [None])[-1]
        summary["pages_cached_last"] = (col(serve_steps, "pages_cached")
                                        or [None])[-1]
        summary["prefix_hit_requests"] = sum(
            1 for r in serve_reqs if r.get("prefix_hit"))
        print(f"paged kv: prefix_hit_rate={summary['prefix_hit_rate']} "
              f"pages_in_use={summary['pages_in_use_last']} "
              f"pages_cached={summary['pages_cached_last']} "
              f"prefix_hit_requests={summary['prefix_hit_requests']}")
    if routes:
        per_replica = {}
        for r in routes:
            per_replica[r.get("replica")] = \
                per_replica.get(r.get("replica"), 0) + 1
        summary["route"] = {
            "placements": len(routes),
            "per_replica": per_replica,
            "prefix_routed": sum(1 for r in routes
                                 if r.get("prefix_tokens")),
        }
        rows = [[name, n] for name, n in sorted(per_replica.items())]
        print("router placements:")
        _fmt_table(["replica", "requests"], rows)
    if regs:
        # the engine emits a CUMULATIVE rollup per run()/drain(): the last
        # record per registry name is that registry's episode total
        latest = {}
        for r in regs:
            latest[r.get("registry")] = r
        for name, reg in sorted(latest.items()):
            labels = reg.get("labels") or {}
            print(f"exec registry [{name}]: entries={reg.get('entries')} "
                  f"hits={reg.get('hits')} misses={reg.get('misses')} "
                  f"evictions={reg.get('evictions')} "
                  f"evict_refusals={reg.get('evict_refusals')} "
                  f"aot_fallbacks={reg.get('aot_fallbacks')}")
            rows = [[lbl, st.get("hits", 0), st.get("misses", 0),
                     st.get("evictions", 0)]
                    for lbl, st in sorted(labels.items())]
            if rows:
                _fmt_table(["label", "hits", "misses", "evictions"], rows)
            reg_pcts = _pctl_table([
                ("compile_cold", "ms", reg.get("compile_cold_ms") or []),
                ("compile_warm", "ms", reg.get("compile_warm_ms") or []),
                ("compile_all", "ms", reg.get("compile_ms") or []),
            ])
            summary.setdefault("exec_registry", {})[name] = {
                "entries": reg.get("entries"),
                "hits": reg.get("hits"), "misses": reg.get("misses"),
                "evictions": reg.get("evictions"),
                "evict_refusals": reg.get("evict_refusals"),
                "aot_fallbacks": reg.get("aot_fallbacks"),
                "labels": labels,
                "compile_percentiles": reg_pcts,
            }
    if emit_json:
        print(json.dumps({"summary": summary}))
    return summary


def summarize_snapshot(path):
    """Percentile table from a metrics-registry snapshot (the exporter's
    /metrics.json document or a flight-recorder state.json)."""
    with open(path) as f:
        doc = json.load(f)
    return summarize_snapshot_doc(doc)


def summarize_snapshot_doc(doc, emit_json=True):
    from paddle_tpu.observability.metrics import estimate_percentile

    hists = doc.get("histograms") or doc.get("metrics", {}).get("histograms",
                                                                {})
    rows = []
    pcts = {}
    for name, snap in sorted(hists.items()):
        if not snap.get("count"):
            continue
        if "counts" in snap:  # full snapshot: re-estimate from the buckets
            ps = {q: estimate_percentile(snap, q / 100) for q in (50, 90, 99)}
        else:                 # compact snapshot: percentiles precomputed
            ps = {q: snap.get(f"p{q}") for q in (50, 90, 99)}
        rows.append([name, snap["count"]] +
                    [f"{ps[q]:.3f}" if ps[q] is not None else "-"
                     for q in (50, 90, 99)])
        pcts[name] = {"n": snap["count"],
                      **{f"p{q}": ps[q] for q in (50, 90, 99)}}
    if rows:
        _fmt_table(["histogram", "n", "p50", "p90", "p99"], rows)
    else:
        print("no populated histograms in snapshot")
    # SLO gauges (observability/slo.py writes slo.<name>.burn_rate /
    # .error_budget_remaining / .firing): surface the judgement layer
    # next to the raw percentiles — in fleet mode this is the merged view
    slo_gauges = {k: v for k, v in (doc.get("gauges") or {}).items()
                  if k.startswith("slo.")}
    if slo_gauges:
        slos = {}
        for k, v in slo_gauges.items():
            name, _, field = k[len("slo."):].rpartition(".")
            slos.setdefault(name, {})[field] = v
        rows = [[name, f"{g.get('burn_rate', 0):.2f}x",
                 f"{g.get('error_budget_remaining', 1):.4f}",
                 "yes" if g.get("firing") else "no"]
                for name, g in sorted(slos.items())]
        print("slo state:")
        _fmt_table(["slo", "burn", "budget_left", "firing"], rows)
    summary = {
        "kind": "metrics_snapshot",
        "histograms": len(pcts),
        "counters": len(doc.get("counters", {})),
        "gauges": len(doc.get("gauges", {})),
        "percentiles": pcts,
    }
    # executable-registry rollup (core/exec_registry.py): per-label
    # hit/miss/eviction counters; the cold-vs-warm compile_ms percentiles
    # ride the generic histogram table above (exec.registry.compile_*_ms)
    ex_pre = "exec.registry."
    per_label, top = {}, {}
    for k, v in sorted((doc.get("counters") or {}).items()):
        if not k.startswith(ex_pre):
            continue
        label, _, stat = k[len(ex_pre):].rpartition(".")
        if label and stat in ("hits", "misses", "evictions"):
            per_label.setdefault(label, {})[stat] = int(v)
        else:
            top[k[len(ex_pre):]] = int(v)
    if per_label or top:
        rows = [[lbl, st.get("hits", 0), st.get("misses", 0),
                 st.get("evictions", 0)]
                for lbl, st in sorted(per_label.items())]
        if rows:
            print("executable registry (per label):")
            _fmt_table(["label", "hits", "misses", "evictions"], rows)
        if top:
            print("exec registry totals: " + "  ".join(
                f"{k}={v}" for k, v in sorted(top.items())))
        summary["exec_registry"] = {"labels": per_label, **top}
    if slo_gauges:
        summary["slo_gauges"] = slo_gauges
        summary["slo_firing"] = sorted(
            k[len("slo."):-len(".firing")] for k, v in slo_gauges.items()
            if k.endswith(".firing") and v)
    if emit_json:
        print(json.dumps({"summary": summary}))
    return summary


def _print_span_tables(path):
    """The span ring's own table (observability/tracer.py `span_table`) of
    each exported chrome trace that holds the program's spans: count, total
    and self time a span, `caller`, the jit phases inside and outside an
    `exec.first_call`, the largest jit events by `fun`."""
    from paddle_tpu.observability import tracer

    files = [path] if os.path.isfile(path) else sorted(
        os.path.join(path, f) for f in os.listdir(path)
        if f.endswith(".json"))
    for f in files:
        with open(f) as fh:
            doc = json.load(fh)
        events = tracer.events_from_chrome(doc) if isinstance(doc, dict) else []
        if any("id" in e for e in events):
            print(f"\nspans of the program in {os.path.basename(f)} "
                  f"(seconds from the process's origin):")
            print(tracer.format_span_table(tracer.span_table(events)))


def summarize_trace(path):
    from paddle_tpu.profiler import load_profiler_result

    res = load_profiler_result(path)
    stats = res.stats()
    if not stats:
        print("no complete events in trace")
        return {}
    rows = [[name, cnt, f"{tot * 1e3:.3f}", f"{tot / cnt * 1e3:.3f}",
             f"{mx * 1e3:.3f}", f"{mn * 1e3:.3f}"]
            for name, (cnt, tot, mx, mn) in
            sorted(stats.items(), key=lambda kv: -kv[1][1])]
    _fmt_table(["region", "calls", "total_ms", "avg_ms", "max_ms", "min_ms"],
               rows)
    _print_span_tables(path)
    t0, t1 = res.time_range()
    top = max(stats.items(), key=lambda kv: kv[1][1])
    summary = {
        "kind": "chrome_trace", "events": len(res.events),
        "regions": len(stats),
        "span_s": round((t1 - t0) / 1e6, 4),
        "hottest_region": top[0],
        "hottest_total_ms": round(top[1][1] * 1e3, 3),
    }
    print(json.dumps({"summary": summary}))
    return summary


# ---- fleet mode: merge many per-worker telemetry dirs into one report ------

def _expand_paths(raw_paths):
    """Glob-expand each argument (quoted globs work from any shell); keep
    literal paths as-is so a missing file still errors loudly."""
    import glob

    out = []
    for p in raw_paths:
        hits = sorted(glob.glob(p))
        out.extend(hits if hits else [p])
    return out


def _worker_label(path, root_common):
    """Stable per-source label for merged tables: the path relative to the
    common prefix of all sources (usually the per-worker dir name)."""
    rel = os.path.relpath(path, root_common) if root_common else path
    return rel if rel != "." else os.path.basename(path.rstrip("/"))


def _collect_source_files(path):
    """(jsonl_files, snapshot_files) under one source path. A directory
    contributes its top-level *.jsonl streams and snapshot-shaped *.json
    files; a file contributes itself."""
    jsonls, snaps = [], []
    if os.path.isdir(path):
        for name in sorted(os.listdir(path)):
            p = os.path.join(path, name)
            if not os.path.isfile(p):
                continue
            if name.endswith(".jsonl") and _is_jsonl(p):
                jsonls.append(p)
            elif name.endswith(".json") and _is_snapshot(p):
                snaps.append(p)
    elif _is_snapshot(path):
        snaps.append(path)
    elif _is_jsonl(path):
        jsonls.append(path)
    return jsonls, snaps


def summarize_fleet(paths):
    """One merged report over many per-worker telemetry dirs/files: pooled
    JSONL records (per-worker counts + pooled percentiles — the exact
    pooled-sample truth the fleet collector's histogram merge estimates)
    plus a losslessly merged view of any metrics snapshots."""
    from paddle_tpu.observability import fleet as _fleet

    try:
        common = os.path.commonpath([os.path.abspath(p) for p in paths])
    except ValueError:
        common = ""
    per_worker_counts = {}
    pooled = []
    snapshot_docs = {}
    for p in paths:
        if not os.path.exists(p):
            sys.exit(f"no such path: {p}")
        label = _worker_label(os.path.abspath(p), common)
        jsonls, snaps = _collect_source_files(p)
        n = 0
        for jf in jsonls:
            recs = _load_jsonl(jf)
            for r in recs:
                r.setdefault("worker", label)
            pooled.extend(recs)
            n += len(recs)
        if n:
            per_worker_counts[label] = per_worker_counts.get(label, 0) + n
        for sf in snaps:
            with open(sf) as f:
                doc = json.load(f)
            if "histograms" not in doc:    # flight state.json nests it
                doc = doc.get("metrics", {})
            snapshot_docs[label] = doc
    if per_worker_counts:
        print("fleet sources:")
        _fmt_table(["worker", "records"],
                   [[w, n] for w, n in sorted(per_worker_counts.items())])
    summary = {"kind": "fleet_merged", "sources": len(paths),
               "workers": per_worker_counts}
    if pooled:
        summary["merged"] = summarize_records(pooled, emit_json=False)
    if snapshot_docs:
        merged_snap = _fleet.merge_registry_snapshots(
            list(snapshot_docs.values()))
        print(f"merged metrics snapshots from {len(snapshot_docs)} "
              "worker(s):")
        summary["merged_snapshot"] = summarize_snapshot_doc(
            merged_snap, emit_json=False)
    if not pooled and not snapshot_docs:
        print("no mergeable telemetry under the given paths")
    print(json.dumps({"summary": summary}))
    return summary


def summarize_device(path, window=None):
    """The by-scope table of one device trace, then its JSON line."""
    from paddle_tpu.observability import device_trace

    reduced = device_trace.reduce(path, window=window)
    print(device_trace.format_table(reduced))
    print(json.dumps({"summary": "device_trace", **reduced}))


def _holds_xplane(path):
    if os.path.isfile(path):
        return path.endswith(".xplane.pb")
    return any(f.endswith(".xplane.pb")
               for _, _, fs in os.walk(path) for f in fs)


def main(argv=None):
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("paths", nargs="+",
                    help="StepTelemetry .jsonl, chrome-trace .json, a "
                         "device trace .xplane.pb, a directory of traces, "
                         "or several of these (or a quoted glob) for one "
                         "merged fleet report")
    ap.add_argument("--window", default=None,
                    help="device trace only: clip to the host annotation "
                         "of this name")
    args = ap.parse_args(argv)
    paths = _expand_paths(args.paths)
    if len(paths) > 1:
        summarize_fleet(paths)
        return 0
    path = paths[0]
    if not os.path.exists(path):
        sys.exit(f"no such path: {path}")
    if _holds_xplane(path):
        summarize_device(path, args.window)
        # a Profiler directory holds the host spans beside the device trace
        if os.path.isdir(path) and any(
                f.endswith(".json") for f in os.listdir(path)):
            summarize_trace(path)
    elif os.path.isfile(path) and _is_snapshot(path):
        summarize_snapshot(path)
    elif os.path.isfile(path) and _is_jsonl(path):
        summarize_steps(path)
    else:
        summarize_trace(path)
    return 0


if __name__ == "__main__":
    sys.exit(main())
