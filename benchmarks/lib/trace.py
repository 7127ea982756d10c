"""Profile a sub-window with jax.profiler and reduce the .xplane.pb to what
the per-layer readers need. Nothing here imports the program under test.

What a TPU trace holds (looked at by hand, PR 25): one plane per chip,
`/device:TPU:<n>`, whose line `XLA Ops` has one event per executed HLO
instruction (the name is the instruction's whole text, with `start_ns` and
`duration_ns` on the trace's clock) and whose line `XLA Modules` has one
event per run of an executable (`jit_<name>(<hash>)`). The plane `/host:CPU`
has one line per host thread; `jax.profiler.TraceAnnotation`s land on the
thread that opened them, on the same clock as the device lines.
"""
from __future__ import annotations

import glob
import os
import re
import shutil
import tempfile
from typing import Dict, Iterable, List, Optional, Tuple

# opened by Tracer round the traced sub-window, so that the reduction knows
# the window on the trace's own clock
WINDOW = "bench_traced_window"

# instructions that only contain others: their interval counts as busy time,
# their duration would be counted twice in a table by operation
_CONTAINERS = ("while", "conditional", "call")

_OP_RE = re.compile(r"^%?([^\s=]+)\s*=\s*(?:\([^=]*?\)|\S+)\s+([\w-]+)\(")


def op_key(text: str) -> Tuple[str, str]:
    """An HLO instruction's text -> (table key, opcode). The key is the
    instruction's name without its trailing number, so the 24 layers' copies
    of one fusion add up; a Mosaic kernel is keyed `tpu_custom_call`."""
    m = _OP_RE.match(text)
    if m:
        name, opcode = m.group(1), m.group(2)
    else:                       # a bare name, as some lines carry
        name, opcode = text.lstrip("%").split(" ")[0], ""
    if 'custom_call_target="tpu_custom_call"' in text:
        return "tpu_custom_call", "custom-call"
    return re.sub(r"[.\d]+$", "", name) or name, opcode


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def reduce_xplane(path: str, annotations: Iterable[str],
                  n_chips: Optional[int] = None) -> dict:
    """The reduction. Returns seconds throughout:

    window_s      length of the traced window (the WINDOW annotation; the
                  span of all device events if there is none)
    busy_s        union of the device's instruction intervals inside the
                  window, averaged over the chips
    ops           {key: seconds} by operation (containers left out), summed
                  over chips
    modules       {executable name: seconds} from `XLA Modules`, likewise
    idle_gaps     {annotation: seconds} idle time of chip 0 inside the
                  window, by the benchmark annotation open on the host then
                  (`unannotated` where none was)
    """
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    wanted = set(annotations)
    window = None
    host: List[Tuple[float, float, str]] = []
    chips: Dict[str, dict] = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            chip = chips.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                if line.name == "XLA Ops":
                    chip["ops"] = [(e.start_ns, e.start_ns + e.duration_ns,
                                    e.name) for e in line.events]
                elif line.name == "XLA Modules":
                    chip["modules"] = [(e.start_ns, e.duration_ns, e.name)
                                       for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW:
                        window = (e.start_ns, e.start_ns + e.duration_ns)
                    elif e.name in wanted:
                        host.append((e.start_ns, e.start_ns + e.duration_ns,
                                     e.name))
    names = sorted(chips)[:n_chips] if n_chips else sorted(chips)
    all_ops = [iv for n in names for iv in chips[n]["ops"]]
    if not all_ops:
        return {"window_s": 0.0, "busy_s": 0.0, "ops": {}, "modules": {},
                "idle_gaps": {}}
    if window is None:
        window = (min(a for a, _, _ in all_ops), max(b for _, b, _ in all_ops))
    w0, w1 = window

    def clip(a, b):
        return max(a, w0), min(b, w1)

    busy_ns, ops, modules = 0.0, {}, {}
    merged0: List[Tuple[float, float]] = []
    for n in names:
        ivs = []
        for a, b, text in chips[n]["ops"]:
            a, b = clip(a, b)
            if b <= a:
                continue
            ivs.append((a, b))
            key, opcode = op_key(text)
            if opcode not in _CONTAINERS and key not in _CONTAINERS:
                ops[key] = ops.get(key, 0.0) + (b - a) * 1e-9
        merged = _union(ivs)
        busy_ns += sum(b - a for a, b in merged)
        if n == names[0]:
            merged0 = merged
        for a, dur, text in chips[n]["modules"]:
            a, b = clip(a, a + dur)
            if b > a:
                key = text.split("(")[0]
                modules[key] = modules.get(key, 0.0) + (b - a) * 1e-9
    gaps, edge = [], w0
    for a, b in merged0:
        if a > edge:
            gaps.append((edge, a))
        edge = max(edge, b)
    if w1 > edge:
        gaps.append((edge, w1))
    # the host's annotations sit on one thread and do not nest, so one sweep
    # over both sorted lists attributes every gap
    host.sort()
    idle: Dict[str, float] = {}
    j = 0
    for a, b in gaps:
        while j < len(host) and host[j][1] <= a:
            j += 1
        covered, k = 0.0, j
        while k < len(host) and host[k][0] < b:
            lap = min(b, host[k][1]) - max(a, host[k][0])
            if lap > 0:
                idle[host[k][2]] = idle.get(host[k][2], 0.0) + lap * 1e-9
                covered += lap
            k += 1
        if b - a > covered:
            idle["unannotated"] = idle.get("unannotated", 0.0) \
                + (b - a - covered) * 1e-9
    return {"window_s": (w1 - w0) * 1e-9,
            "busy_s": busy_ns * 1e-9 / len(names),
            "ops": ops, "modules": modules, "idle_gaps": idle}


def top(table: Dict[str, float], n: int = 10) -> List[list]:
    return [[k, v] for k, v in sorted(table.items(),
                                      key=lambda kv: -kv[1])[:n]]


class Tracer:
    """start() opens the profiler and the WINDOW annotation; stop() closes
    the annotation only, which costs nothing, because the runner's loop is
    still serving; reduce(), after the run, stops the profiler, reads the
    trace in this process and deletes it. What the profiler records after
    stop() lies outside the window and is clipped away. The trace goes to a
    fresh directory under TMPDIR."""

    def __init__(self, annotations: Iterable[str], n_chips: int):
        self.annotations = tuple(annotations)
        self.n_chips = n_chips
        self._dir = None
        self._span = None

    def start(self) -> None:
        import jax

        self._dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        # no per-call Python events: they slow the host that is measured
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self._dir, profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation(WINDOW)
        self._span.__enter__()

    def stop(self) -> None:
        self._span.__exit__(None, None, None)

    def reduce(self) -> dict:
        import jax

        if self._dir is None:
            raise RuntimeError("no trace was taken: the window is shorter "
                               "than the cell's trace_seconds")
        try:
            jax.profiler.stop_trace()
            paths = glob.glob(os.path.join(
                self._dir, "plugins", "profile", "*", "*.xplane.pb"))
            if not paths:
                raise RuntimeError(f"the profiler wrote no trace under "
                                   f"{self._dir}")
            return reduce_xplane(paths[0], self.annotations, self.n_chips)
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)
