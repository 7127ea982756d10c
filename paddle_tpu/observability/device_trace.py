"""Reduce a device trace to a table by scope: what an operator runs on a
profile (`paddle.profiler.Profiler`, `jax.profiler.trace`) to see where the
chip's time went in the program's own words.

    python tools/trace_summary.py <dir or file>.xplane.pb

The program names its parts with `jax.named_scope` (a fixed, unnumbered
vocabulary, `SCOPES` below, so that the 24 or 36 blocks of a model add up),
its kernels with `name=` on the `pallas_call`, and its engines open
`serve.*` / `engine.*` spans as `jax.profiler.TraceAnnotation`s. A TPU trace
carries all three: every event of a device plane's `XLA Ops` line points at
an event METADATA whose stat `tf_op` holds the JAX op path
(`jit(step)/transpose(jvp(attn))/qkv/dot_general`), and the host plane holds
the annotations on the same clock. `jax.profiler.ProfileData` shows an
event's own stats only, so this module reads the protobuf's wire format
itself (no `xplane_pb2` is installed): a few dozen lines of varints.

It reads the trace only: it imports neither jax nor the engines.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, Iterator, List, Optional, Tuple

# ---- the vocabulary (paddle_tpu/models/gpt.py, models/afmoe.py,
# models/olmo_hybrid.py, models/deepseek_v2.py,
# nn/layers/routed_experts.py, ops/fused.py,
# ops/pallas/flash_attention.py, ops/pallas/latent_decode.py,
# ops/pallas/slot_decode.py, ops/pallas/grouped_experts.py,
# distributed/engine.py, grad_comm.py,
# serving/engine.py, serving/kv_state.py, serving/sampling.py) ------------
ROOTS = ("prefill", "decode")                       # the serving programs
KERNELS = ("flash_fwd", "flash_bwd", "flash_bwd_dkv", "flash_bwd_dq",
           "latent_decode", "slot_decode", "block_decode", "grouped_experts")
SCOPES = frozenset(ROOTS + KERNELS + (
    "embed", "attn", "qkv", "core", "out", "cache_write", "mlp",
    "final_norm", "lm_head_loss", "lm_head", "sample", "grad_clip",
    "optimizer", "fsdp_gather", "grad_sync",
    # the afmoe block: attn > qk_norm, rope, gate; moe > router, dispatch,
    # experts, shared, combine
    "qk_norm", "rope", "gate", "moe", "router", "dispatch", "experts",
    "shared", "combine",
    # the olmo_hybrid block: linear_attn > proj, conv, gates, delta_rule,
    # out_gate, out; the slot's state written back
    "linear_attn", "proj", "conv", "gates", "delta_rule", "out_gate",
    "state_write",
    # the deepseek_v2 block: mla > q_lora, kv_latent, rope, expand (a
    # prefill: keys and values a head from the latent), absorb / unabsorb (a
    # decode step: queries into the latent space, the result back), core,
    # out, cache_write; moe as afmoe's
    "mla", "q_lora", "kv_latent", "expand", "absorb", "unabsorb",
    # a block step of generation by diffusion (serving/engine.py
    # `_build_block_decode`): `denoise` > `confidence`, `unmask`, `commit`
    "denoise", "confidence", "unmask", "commit"))
SPAN_PREFIXES = ("serve.", "engine.")               # the engines' spans

UNNAMED = "unnamed"           # an op_name, and no scope of the vocabulary
NO_METADATA = "no_metadata"   # no op_name at all (the compiler's own ops)
CALLER = "caller"             # idle with no program span open on the host

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute", "collective-broadcast")
# instructions that only contain others: busy time, but not a table row
_CONTAINERS = ("while", "conditional", "call")
_OPCODE_RE = re.compile(r"^%?[^\s=]+\s*=\s*(?:\([^=]*?\)|\S+)\s+([\w-]+)\(")
_WRAPPER_RE = re.compile(r"^([\w-]+)\((.*)\)$")


# ---- protobuf wire format ---------------------------------------------

def _varint(buf, i: int) -> Tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf, i: int, end: int) -> Iterator[Tuple[int, object]]:
    """(field number, value) of the message in buf[i:end]; a varint is an
    int, a length-delimited value its (start, end) in `buf`, a fixed-width
    one its bytes."""
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            value, i = bytes(buf[i:i + n]), i + n
        else:
            raise ValueError(f"wire type {wire} at byte {i}: not an xplane")
        yield key >> 3, value


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_entry(buf, span):
    key = value = None
    for no, v in _fields(buf, *span):
        if no == 1:
            key = v
        elif no == 2:
            value = v
    return key, value


def read_xplane(path: str) -> List[dict]:
    """The planes of an XSpace file: `{"name", "lines": [{"name",
    "events": [(start_ns, end_ns, metadata id)]}], "events": {metadata id:
    {"name", "tf_op"}}}`. Times are on the trace's one clock."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    planes = []
    for no, span in _fields(buf, 0, len(buf)):
        if no != 1:                               # XSpace.planes
            continue
        plane = {"name": "", "lines": [], "events": {}}
        lines, metas, stat_names = [], [], {}
        for pno, v in _fields(buf, *span):
            if pno == 2:
                plane["name"] = _text(buf, v)
            elif pno == 3:
                lines.append(v)
            elif pno == 4:
                metas.append(_map_entry(buf, v)[1])
            elif pno == 5:                        # id -> XStatMetadata
                sid, sname = None, ""
                for mno, mv in _fields(buf, *_map_entry(buf, v)[1]):
                    if mno == 1:
                        sid = mv
                    elif mno == 2:
                        sname = _text(buf, mv)
                stat_names[sid] = sname
        for mspan in metas:                       # XEventMetadata
            mid, name, tf_op = None, "", None
            for mno, mv in _fields(buf, *mspan):
                if mno == 1:
                    mid = mv
                elif mno == 2:
                    name = _text(buf, mv)
                elif mno == 5:                    # XStat
                    stat = dict(_fields(buf, *mv))
                    if stat_names.get(stat.get(1)) == "tf_op":
                        tf_op = (_text(buf, stat[5]) if 5 in stat
                                 else stat_names.get(stat.get(7)))
            plane["events"][mid] = {"name": name, "tf_op": tf_op or None}
        for lspan in lines:                       # XLine
            name, t0_ns, events = "", 0, []
            for lno, lv in _fields(buf, *lspan):
                if lno == 2:
                    name = _text(buf, lv)
                elif lno == 3:
                    t0_ns = lv
                elif lno == 4:
                    events.append(lv)
            rows = []
            for espan in events:                  # XEvent
                ev = dict(_fields(buf, *espan))
                start = t0_ns + ev.get(2, 0) * 1e-3
                rows.append((start, start + ev.get(3, 0) * 1e-3, ev.get(1)))
            plane["lines"].append({"name": name, "events": rows})
        planes.append(plane)
    return planes


# ---- names ------------------------------------------------------------

def scope_of(op_name: Optional[str]) -> Tuple[str, bool, Optional[str]]:
    """A JAX op path -> (scope, backward, kernel). Wrappers are stripped
    (`transpose(jvp(attn))` is `attn`, and marks the backward pass), jit
    names and primitives dropped, what is left of the vocabulary joined
    with `/`; the kernel's name comes out of the scope and stands alone."""
    if not op_name:
        return NO_METADATA, False, None
    kept: List[str] = []
    backward, kernel = False, None
    for part in op_name.split("/")[:-1]:          # the last is the primitive
        while True:
            m = _WRAPPER_RE.match(part)
            if not m:
                break
            outer, part = m.groups()
            if outer in ("jit", "pjit"):
                part = ""
            elif outer == "transpose":
                backward = True
        if part in KERNELS:
            kernel = part
        elif part in SCOPES and (not kept or kept[-1] != part):
            kept.append(part)
    return "/".join(kept) or UNNAMED, backward, kernel


def _opcode(text: str) -> str:
    """An `XLA Ops` event's name -> its HLO opcode. The name is the
    instruction's whole text; a container's may be its bare name
    (`while.7`), which is the opcode and a number."""
    m = _OPCODE_RE.match(text)
    if m:
        return m.group(1)
    return re.sub(r"[.\d]+$", "", text.lstrip("%").split(" ")[0])


def _union(intervals):
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _add(table: dict, key, seconds: float) -> None:
    table[key] = table.get(key, 0.0) + seconds


# ---- the reduction ----------------------------------------------------

def find_xplane(path: str) -> str:
    """A profile directory (or a file) -> its newest `*.xplane.pb`."""
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no *.xplane.pb under {path}")
    return found[-1]


def reduce(path: str, window: Optional[str] = None) -> dict:
    """One xplane file -> seconds, averaged over the chips it holds:

    window_s, busy_s   the window (the host annotation named `window` if
                       given and present, else first to last device event)
                       and the union of the chips' instruction intervals
    by_scope           {scope: {"fwd": s, "bwd": s}}, containers left out;
                       UNNAMED and NO_METADATA are rows of it
    detail             {scope: {"opcode primitive": s}}: each row by HLO
                       opcode and by the JAX primitive its path ends in
                       (NO_METADATA has no path: by opcode alone)
    collectives        {scope: {opcode: s}}
    by_kernel          {kernel name: s} of the Mosaic calls
    by_executable      {executable: s} from the `XLA Modules` line
    idle_s, idle       chip 0's idle time in the window, and the same
                       split by the innermost `serve.*` / `engine.*` span
                       open on the host then (CALLER where none was)
    """
    planes = read_xplane(find_xplane(path))
    chips = sorted((p for p in planes if p["name"].startswith("/device:TPU:")),
                   key=lambda p: p["name"])
    spans: List[Tuple[float, float, str]] = []
    w = None
    for plane in planes:
        if not plane["name"].startswith("/host:"):
            continue
        for line in plane["lines"]:
            for a, b, mid in line["events"]:
                name = plane["events"].get(mid, {}).get("name", "")
                if window and name == window:
                    w = (a, b)
                elif name.startswith(SPAN_PREFIXES):
                    spans.append((a, b, name))
    ops = [[(a, b, c["events"].get(mid, {})) for line in c["lines"]
            if line["name"] == "XLA Ops" for a, b, mid in line["events"]]
           for c in chips]
    out = {"chips": len(chips), "window_s": 0.0, "busy_s": 0.0,
           "by_scope": {}, "detail": {}, "collectives": {},
           "by_kernel": {}, "by_executable": {},
           "idle_s": 0.0, "idle": {}}
    if not any(ops):
        return out
    if w is None:
        w = (min(a for chip in ops for a, _, _ in chip),
             max(b for chip in ops for _, b, _ in chip))
    w0, w1 = w
    share = 1e-9 / len(chips)
    merged0 = []
    for n, chip in enumerate(ops):
        ivs = []
        for a, b, meta in chip:
            a, b = max(a, w0), min(b, w1)
            if b <= a:
                continue
            ivs.append((a, b))
            text = meta.get("name", "")
            opcode = _opcode(text)
            if opcode in _CONTAINERS:
                continue
            dur = (b - a) * share
            path = meta.get("tf_op")
            scope, backward, kernel = scope_of(path)
            primitive = path.split("/")[-1].rstrip(":") if path else ""
            _add(out["by_scope"].setdefault(scope, {"fwd": 0.0, "bwd": 0.0}),
                 "bwd" if backward else "fwd", dur)
            _add(out["detail"].setdefault(scope, {}),
                 f"{opcode} {primitive}".strip(), dur)
            if 'custom_call_target="tpu_custom_call"' in text:
                _add(out["by_kernel"], kernel or UNNAMED, dur)
            base = re.sub(r"-(start|done)$", "", opcode)
            if base in _COLLECTIVES:
                _add(out["collectives"].setdefault(scope, {}), opcode, dur)
        merged = _union(ivs)
        out["busy_s"] += sum(b - a for a, b in merged) * share
        if n == 0:
            merged0 = merged
    for c in chips:
        for line in c["lines"]:
            if line["name"] != "XLA Modules":
                continue
            for a, b, mid in line["events"]:
                a, b = max(a, w0), min(b, w1)
                if b > a:
                    name = c["events"].get(mid, {}).get("name", "")
                    _add(out["by_executable"], name.split("(")[0],
                         (b - a) * share)
    out["window_s"] = (w1 - w0) * 1e-9
    spans.sort()
    starts = [s[0] for s in spans]
    longest = max((s[1] - s[0] for s in spans), default=0.0)
    edge = w0
    for a, b in merged0 + [[w1, w1]]:
        if a > edge:       # only spans that can reach into the gap
            near = spans[bisect.bisect_left(starts, edge - longest):
                         bisect.bisect_left(starts, a)]
            _split_gap(edge, a, near, out["idle"])
        edge = max(edge, b)
    out["idle_s"] = sum(out["idle"].values())
    return out


def _split_gap(a: float, b: float, spans, idle: Dict[str, float]) -> None:
    """Put the idle interval [a, b] down to the innermost span open on the
    host at each instant of it: the one that started last."""
    over = [s for s in spans if s[0] < b and s[1] > a]
    cuts = sorted({a, b} | {t for s in over for t in s[:2] if a < t < b})
    for lo, hi in zip(cuts, cuts[1:]):
        mid = (lo + hi) / 2
        open_ = [s for s in over if s[0] <= mid < s[1]]
        name = max(open_)[2] if open_ else CALLER
        _add(idle, name, (hi - lo) * 1e-9)


# ---- for people -------------------------------------------------------

def format_table(r: dict, top: int = 12) -> str:
    busy = r["busy_s"] or float("nan")
    lines = [f"{r['chips']} chip(s): window {r['window_s']:.4f} s, busy "
             f"{r['busy_s']:.4f} s ({100 * busy / (r['window_s'] or 1):.2f}%)"
             f", idle of chip 0 {r['idle_s']:.4f} s; seconds a chip",
             f"{'scope':<32}{'fwd s':>10}{'bwd s':>10}{'% of busy':>11}"]
    rows = sorted(r["by_scope"].items(),
                  key=lambda kv: -(kv[1]["fwd"] + kv[1]["bwd"]))
    for n, (scope, d) in enumerate(rows):
        lines.append(f"{scope:<32}{d['fwd']:>10.4f}{d['bwd']:>10.4f}"
                     f"{100 * (d['fwd'] + d['bwd']) / busy:>11.2f}")
        if n < 8:                 # the largest rows, by opcode and primitive
            inner = sorted(r["detail"].get(scope, {}).items(),
                           key=lambda kv: -kv[1])[:3]
            lines += [f"    {k:<38}{v:>10.4f}" for k, v in inner]

    def block(title, table):
        if table:
            lines.append(title)
            for k, v in sorted(table.items(), key=lambda kv: -kv[1])[:top]:
                lines.append(f"  {k:<30}{v:>10.4f}{100 * v / busy:>11.2f}")

    block("kernels (Mosaic calls)", r["by_kernel"])
    block("executables", r["by_executable"])
    block(f"{UNNAMED}, by opcode and primitive", r["detail"].get(UNNAMED))
    block(f"{NO_METADATA}, by opcode", r["detail"].get(NO_METADATA))
    block("collectives, by scope and opcode",
          {f"{s}: {op}": v for s, d in r["collectives"].items()
           for op, v in d.items()})
    if r["idle"]:
        lines.append("idle of chip 0, by the span open on the host")
        for k, v in sorted(r["idle"].items(), key=lambda kv: -kv[1]):
            lines.append(f"  {k:<30}{v:>10.4f}"
                         f"{100 * v / (r['idle_s'] or 1):>10.1f}%")
    return "\n".join(lines)
