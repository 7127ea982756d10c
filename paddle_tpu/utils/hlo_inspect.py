"""Shared helpers for classifying compiled-HLO text in perf gates and probes.

Used by tests/test_hlo_perf_gates.py and tools/decode_hlo_probe.py so the
fragile text heuristics (XLA metadata tags, shape regexes) live in ONE place.
The reference's analogue is the IR-pass test utilities that grep ProgramDesc
text (test/ir mem_opt pass tests); here the inspected artifact is XLA's
optimized HLO.
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple


def cost_analysis_dict(compiled) -> Dict:
    """`compiled.cost_analysis()` normalized to ONE flat dict across jax
    versions: older releases return a list with one dict per device program,
    newer ones the dict itself. Every cost-model consumer goes through here
    so the version drift is absorbed in one place."""
    ca = compiled.cost_analysis() or {}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return ca

_SHAPE_RE = re.compile(r"=\s*\S*\s*(bf16|f32|f16|s32|s64)\[([\d,]*)\]")
_BF16_CONVERT_RE = re.compile(r"=\s*bf16\[([\d,]+)\]\S*\s+convert\(")


def while_body_lines(hlo_text: str) -> List[str]:
    """Ops belonging to a jitted loop body, identified by the `while/body`
    op_name metadata (robust across XLA computation-naming schemes; fusion
    roots inherit the metadata of the op they fuse)."""
    return [ln for ln in hlo_text.splitlines() if "while/body" in ln]


def shape_elems(line: str) -> Tuple[Optional[str], int]:
    """(dtype, element-count) of the op result on `line`, or (None, 0)."""
    m = _SHAPE_RE.search(line)
    if not m:
        return None, 0
    n = 1
    for d in m.group(2).split(","):
        if d:
            n *= int(d)
    return m.group(1), n


def copies_of_shape(lines: List[str], shape_csv: str) -> List[str]:
    """copy/copy-start ops whose text mentions the given `d0,d1,...` shape."""
    return [ln.strip() for ln in lines
            if shape_csv in ln and ("copy(" in ln or "copy-start" in ln)]


def count_dynamic_update_slices(lines: List[str]) -> int:
    return sum("dynamic-update-slice" in ln for ln in lines)


def jaxpr_loop_report(closed_jaxpr, min_elems: int):
    """Backend-independent loop audit: find scan/while eqns (recursively) and
    report (big_loop_inputs, weight_sized_converts_in_bodies).

    big_loop_inputs: list of "dtype[shape]" strings for loop invars whose
    element count >= min_elems. converts: count of convert_element_type eqns
    inside loop bodies whose INPUT is that large. Compiled-HLO carry checks
    are backend-contaminated (XLA CPU upcasts bf16 dots to f32 and LICM
    hoists the upcasts into the carry); the jaxpr is the traced truth."""
    import numpy as _np

    big_inputs: List[str] = []
    n_converts = 0

    def _sub_jaxprs(eqn):
        for v in eqn.params.values():
            if hasattr(v, "jaxpr"):
                yield v.jaxpr
            elif isinstance(v, (list, tuple)):
                for x in v:
                    if hasattr(x, "jaxpr"):
                        yield x.jaxpr

    def _count_converts(jxp):
        nonlocal n_converts
        for eqn in jxp.eqns:
            if eqn.primitive.name == "convert_element_type":
                a = eqn.invars[0].aval
                if a.shape and int(_np.prod(a.shape)) >= min_elems:
                    n_converts += 1
            for sub in _sub_jaxprs(eqn):
                _count_converts(sub)

    def _walk(jxp):
        for eqn in jxp.eqns:
            if eqn.primitive.name in ("scan", "while"):
                for v in eqn.invars:
                    a = getattr(v, "aval", None)
                    if (a is not None and a.shape
                            and int(_np.prod(a.shape)) >= min_elems):
                        big_inputs.append(f"{a.dtype}{list(a.shape)}")
                for sub in _sub_jaxprs(eqn):
                    _count_converts(sub)
            else:
                for sub in _sub_jaxprs(eqn):
                    _walk(sub)

    _walk(closed_jaxpr.jaxpr)
    return big_inputs, n_converts


def bf16_converts_of_min_size(lines: List[str], min_elems: int,
                              exclude_shape_csv: Optional[str] = None
                              ) -> List[str]:
    """f32->bf16 convert ops at/above `min_elems`, optionally excluding a
    shape (e.g. the KV cache, whose bf16 converts on CPU are f32-legalization
    noise — CPU dots have no native bf16)."""
    out = []
    for ln in lines:
        m = _BF16_CONVERT_RE.search(ln)
        if not m:
            continue
        n = 1
        for d in m.group(1).split(","):
            n *= int(d)
        if n >= min_elems and (exclude_shape_csv is None
                               or exclude_shape_csv not in ln):
            out.append(ln.strip())
    return out


# ---- where arrays cross a loop's boundary (tools/decode_hlo_probe.py) ----
_ARRAY_RE = re.compile(r"(bf16|f16|f32|s32|s64|u32|pred)\[([\d,]*)\]\{([\d,]*)")
_ITEM_BYTES = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4, "s64": 8,
         "pred": 1}


def computations(text):
    """name -> instruction lines of each computation in optimized HLO text,
    and the name of the ENTRY one."""
    comps, entry, name = {}, None, None
    for line in text.splitlines():
        m = re.match(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$", line)
        if m and not line.startswith(" "):
            name = m.group(2)
            comps[name] = []
            entry = name if m.group(1) else entry
        elif line.startswith("}"):
            name = None
        elif name is not None:
            comps[name].append(line)
    return comps, entry


def reached(comps, roots):
    """The computations `roots` call, transitively (fusions, bodies,
    conditions, reducers)."""
    seen, todo = set(), list(roots)
    while todo:
        c = todo.pop()
        if c in seen or c not in comps:
            continue
        seen.add(c)
        for line in comps[c]:
            todo += re.findall(
                r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)", line)
            for group in re.findall(r"branch_computations=\{([^}]*)\}", line):
                todo += [b.strip().lstrip("%") for b in group.split(",")]
    return seen


def boundary_report(text, cache_shapes):
    """Where arrays of `cache_shapes` ({(dtype, dims)}) cross the loop of an
    optimized HLO program: their layouts as ENTRY parameters and as the
    `while`'s carry, and the `copy` / `copy-start` instructions that produce
    one, outside and inside the loop (the loop's prefetches apart)."""
    comps, entry = computations(text)
    bodies = set()
    for lines in comps.values():
        for line in lines:
            if " while(" in line:
                bodies.update(re.findall(r"body=%?([\w.\-]+)", line))
    inside = reached(comps, bodies)

    def result(line):
        m = _ARRAY_RE.search(line.split("=", 1)[1]) if "=" in line else None
        if not m:
            return None
        key = (m.group(1), tuple(int(d) for d in m.group(2).split(",") if d))
        return key + (m.group(3),) if key in cache_shapes else None

    def name(r):
        return f"{r[0]}[{','.join(map(str, r[1]))}]"

    # a fusion's own computation is not a pass over memory: a `copy` in it
    # is an operand read in another order by the fused consumer. What
    # counts is a `copy` / `copy-start` instruction of a computation that
    # runs as written, and a fusion that is nothing but a copy
    fused = {c for lines in comps.values() for line in lines
             if " fusion(" in line
             for c in re.findall(r"calls=%?([\w.\-]+)", line)}
    bare = {c for c in fused
            if all(re.search(r"\s(parameter|copy|bitcast)\(", line)
                   for line in comps.get(c, ()))
            and any(" copy(" in line for line in comps.get(c, ()))}
    entry_layouts, loop_layouts = {}, {}
    # a `copy` changes the layout; a `copy-start` whose two layouts agree
    # moves the array to another memory space (a prefetch) and changes none
    copies = {k: [0, 0] for k in ("outside", "inside", "inside_prefetch")}
    for comp, lines in comps.items():
        if comp in fused:
            continue
        for line in lines:
            r = result(line)
            if r is None:
                continue
            if comp == entry and " parameter(" in line:
                by = entry_layouts.setdefault(name(r), {})
                by[r[2]] = by.get(r[2], 0) + 1
            if comp in bodies and " get-tuple-element(" in line:
                by = loop_layouts.setdefault(name(r), {})
                by[r[2]] = by.get(r[2], 0) + 1
            op = re.search(r"\s(copy|copy-start|fusion)\(", line)
            if not op or (op.group(1) == "fusion" and not bare.intersection(
                    re.findall(r"calls=%?([\w.\-]+)", line))):
                continue
            where = "inside" if comp in inside else "outside"
            both = _ARRAY_RE.findall(line.split(op.group(0))[0])
            if (op.group(1) == "copy-start" and where == "inside"
                    and len(both) > 1 and both[0] == both[1]):
                where = "inside_prefetch"
            n = _ITEM_BYTES[r[0]]
            for d in r[1]:
                n *= d
            copies[where][0] += 1
            copies[where][1] += n
    return {"entry_layouts": entry_layouts, "in_loop_layouts": loop_layouts,
            "cache_sized_copies": {
                k: {"count": c, "GiB": round(b / 2 ** 30, 3)}
                for k, (c, b) in copies.items()}}
