"""One run of one cell of the benchmark, in one process that owns the chip.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Data decides everything. BENCHMARK.json names the cell's configuration and
traffic mix; benchmarks/workloads/<name>.json names its runner, the runner's
parameters and the per-layer metrics the cell reports; each of those is a
file of its own (configs/, traffic/, runners/, layer_metrics/). Adding a
cell, a configuration, a traffic mix, a runner or a per-layer metric means
adding files and entries to BENCHMARK.json, and editing none that is here.

The last line of standard output is one JSON object: `correct`, `attempted`,
`failed`, `metrics`, `device`, and with --trace 1 `breakdown`. With --trace 0
the metrics are the cell's end-to-end metrics, with --trace 1 its per-layer
metrics. Earlier lines (`[phase] {...}`) are for people. Off a TPU, or with
fewer chips than the cell asks for, it exits 1 before it builds a model:
there is no CPU mode and no small mode.
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()      # process start, before jax is imported

import argparse                # noqa: E402
import importlib.util          # noqa: E402
import json                    # noqa: E402
import os                      # noqa: E402
import sys                     # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(folder: str, name: str, here: str = HERE):
    """benchmarks/<folder>/<name>.py as a module; names may hold dots."""
    path = os.path.join(here, folder, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{folder}_{name}".replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve(workload: str, root: str = ROOT) -> dict:
    """Everything that defines a cell, read from its files."""
    manifest = load_json(root, "BENCHMARK.json")
    here = os.path.join(root, manifest["paths"][0])
    entry = next((w for w in manifest["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        raise SystemExit(f"no cell {workload!r} in BENCHMARK.json (has: "
                         f"{[w['name'] for w in manifest['workloads']]})")
    cell = load_json(here, "workloads", workload + ".json")
    for key in ("config", "traffic", "chips"):
        if cell[key] != entry[key]:
            raise SystemExit(f"{workload}: {key} is {cell[key]!r} in the "
                             f"cell's file and {entry[key]!r} in "
                             f"BENCHMARK.json")
    conf = next(c for c in manifest["configs"] if c["name"] == cell["config"])

    def reported(metric):
        return workload in metric.get("workloads", [workload])

    return {
        "cell": cell, "config": load_json(root, conf["file"]),
        "traffic": load_json(here, "traffic", cell["traffic"] + ".json"),
        "end_to_end": [m for m in manifest["end_to_end"] if reported(m)],
        "per_layer": [m for m in manifest["per_layer"] if reported(m)],
    }


class Run:
    """What a runner is given, and where it says when the window starts."""

    def __init__(self, resolved: dict, seed: int, seconds: float,
                 trace: bool, tracer=None):
        self.cell = resolved["cell"]
        self.config = resolved["config"]
        self.traffic = resolved["traffic"]
        self.chips = int(self.cell["chips"])
        self.seed, self.seconds, self.trace = int(seed), float(seconds), trace
        self.tracer = tracer
        self.window_start = None

    def start_window(self, at: float = None) -> float:
        self.window_start = time.perf_counter() if at is None else at
        return self.window_start

    def note(self, phase: str, facts: dict) -> None:
        print(f"[{phase}] " + json.dumps(facts, sort_keys=True, default=str),
              flush=True)


def device_facts(devs) -> dict:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(max(peaks))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    resolved = resolve(args.workload)
    chips = int(resolved["cell"]["chips"])

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"benchmarks/run.py: JAX reports {len(devs)} x "
              f"{devs[0].platform}; cell {args.workload} needs {chips} TPU "
              f"chip(s). A device metric is only measured on the device.",
              file=sys.stderr)
        return 1

    sys.path.insert(0, ROOT)           # paddle_tpu, and `benchmarks.lib`
    from paddle_tpu.core import compile_cache

    from benchmarks.lib import trace as trace_lib

    runner = load_module("runners", resolved["cell"]["runner"])
    tracer = (trace_lib.Tracer(runner.ANNOTATIONS, chips)
              if args.trace else None)
    run = Run(resolved, args.seed, args.seconds, bool(args.trace), tracer)
    run.note("start", {"workload": args.workload, "seed": args.seed,
                       "compile_cache_dir": compile_cache.cache_dir(),
                       "cache_entries": compile_cache.entries(),
                       "jax_ready_s": time.perf_counter() - _T0})
    result = runner.run(run)
    setup_s = run.window_start - _T0

    device = device_facts(devs)
    line = {"correct": bool(result["correct"]),
            "attempted": int(result["attempted"]),
            "failed": int(result["failed"])}
    if not args.trace:
        values = dict(result["end_to_end"], setup_s=setup_s)
        line["metrics"] = {m["name"]: {"value": values[m["name"]],
                                       "unit": m["unit"]}
                           for m in resolved["end_to_end"]}
    else:
        reduced = tracer.reduce()
        collected = dict(result["collected"], trace=reduced, setup_s=setup_s,
                         device_kind=device["kind"], chips=chips,
                         cell=resolved["cell"], config=resolved["config"])
        metrics = {}
        for m in resolved["per_layer"]:
            value = load_module("layer_metrics", m["name"]).read(collected)
            if value is not None:       # nothing to read: left out
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        line["metrics"] = metrics
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        line["breakdown"] = {
            "device_ops": trace_lib.top(reduced["ops"]),
            "idle_gaps": trace_lib.top(reduced["idle_gaps"])}
        run.note("trace", {"modules": trace_lib.top(reduced["modules"]),
                           "busy_s": reduced["busy_s"],
                           "window_s": reduced["window_s"]})
    line["device"] = device
    run.note("end", {"setup_s": setup_s, "total_s": time.perf_counter() - _T0,
                     "cache_entries": compile_cache.entries()})
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
