"""Mean share of slots that emitted a token, over the `serve_step` sink
records of the window (one a dispatch; a slot that retires inside a dispatch
idles to its end)."""
LAYER, UNIT, MOVES, SOURCE = "serving_engine", "%", "serve_tokens_per_s", "program_counter"


def read(run):
    if "window" not in run:
        return None
    w0, w1 = (t + run["wall_minus_perf"] for t in run["window"])
    occ = [r["occupancy"] for r in run.get("sink", [])
           if r.get("event") == "serve_step" and w0 <= r["ts"] <= w1]
    if not occ:
        return None
    return 100.0 * sum(occ) / len(occ)
