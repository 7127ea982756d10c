"""The analysis pass suite.

Each pass is a function ``(program, contract) -> (violations, skips)``
registered in :data:`PASSES` under a stable name. Passes only check what
the contract declares (undeclared fields are free), so one suite serves
both strict perf gates and loose hygiene sweeps.

Pass inventory:

=================== =========================================================
collective-contract collective-op counts per kind + while-loop count, with
                    the backend-combining probe turning count checks into
                    skips on non-combining (CPU) pipelines
donation-leak       input state eligible for aliasing but not donated, via
                    the compiled memory analysis' alias bytes
dtype-upcast        f32 payloads on reduction collectives inside a declared
                    bf16/int8 gradient-communication region
host-transfer       infeed/outfeed/send/recv or host-callback custom-calls
                    inside a step program
constant-bloat      literals above max_constant_bytes baked into the HLO
recompile-hazard    weak-type / Python-scalar leaks in the traced signature
schedule-order      declared schedule disciplines read from the scheduled
                    module text; "all-gather-ahead" proves the fsdp gather
                    window moved each bucket's all-gather ahead of the
                    previous bucket's compute
=================== =========================================================
"""
from __future__ import annotations

import re
from typing import Callable, Dict, List, Optional, Tuple

from .backend import (collective_combining_reason,
                      native_bf16_collective_reason)
from .contracts import (COLLECTIVE_KINDS, ProgramContract, Skip, Violation,
                        check_bound)
from .program import Program

PassResult = Tuple[List[Violation], List[Skip]]
PassFn = Callable[[Program, ProgramContract], PassResult]

#: collectives that REDUCE gradient payloads — the ops whose payload dtype
#: the comm_dtype contract governs. all-gather is exempt: ZeRO legitimately
#: gathers f32 updated params even when gradients travel as bf16/int8.
_REDUCTION_KINDS = ("all-reduce", "reduce-scatter")


def collective_contract(prog: Program, c: ProgramContract) -> PassResult:
    name = "collective-contract"
    if c.collectives is None and c.while_loops is None:
        return [], []
    if c.requires_combining:
        reason = collective_combining_reason()
        if reason is not None:
            return [], [Skip(prog.label, name, reason)]
    vs: List[Violation] = []
    for kind, bound in (c.collectives or {}).items():
        n = prog.count_ops(kind)
        want = check_bound(n, bound)
        if want is not None:
            vs.append(Violation(
                prog.label, name,
                f"{n} {kind} op(s), contract wants {want}"))
    want = check_bound(prog.count_while_loops(), c.while_loops)
    if want is not None:
        vs.append(Violation(
            prog.label, name,
            f"{prog.count_while_loops()} while loop(s), contract wants "
            f"{want} — scan fusion broken"))
    return vs, []


def donation_leak(prog: Program, c: ProgramContract) -> PassResult:
    name = "donation-leak"
    if not c.donated_bytes:
        return [], []
    mem = prog.memory_analysis()
    if mem is None or not hasattr(mem, "alias_size_in_bytes"):
        return [], [Skip(prog.label, name,
                         "backend exposes no alias/memory analysis")]
    aliased = int(mem.alias_size_in_bytes)
    need = int(c.donated_fraction * c.donated_bytes)
    if aliased >= need:
        return [], []
    return [Violation(
        prog.label, name,
        f"only {aliased} of {c.donated_bytes} eligible input-state bytes "
        f"are donation-aliased (need >= {need}); pass donate=True "
        f"or add donate_argnums")], []


def dtype_upcast(prog: Program, c: ProgramContract) -> PassResult:
    name = "dtype-upcast"
    if c.comm_dtype in (None, "f32", "float32"):
        return [], []
    if c.comm_dtype in ("bf16", "bfloat16") and not c.comm_dtype_strict:
        # CPU float normalization rewrites the bf16 psum to an f32
        # all-reduce — every declared-bf16 program would "violate" here
        # regardless of its source. Probe once; skip where the wire can't
        # carry bf16 (same design as requires_combining).
        reason = native_bf16_collective_reason()
        if reason is not None:
            return [], [Skip(prog.label, name, reason)]
    vs: List[Violation] = []
    for kind in _REDUCTION_KINDS:
        for line in prog.op_def_lines(kind):
            bad = [e for dt, e in prog.result_shapes(line)
                   if dt in ("f32", "f64") and e >= c.comm_min_elems]
            if bad:
                vs.append(Violation(
                    prog.label, name,
                    f"f32 payload ({max(bad)} elems) on a {kind} in a "
                    f"declared-{c.comm_dtype} grad-comm region: "
                    f"{line.strip()[:120]}"))
    return vs, []


def host_transfer(prog: Program, c: ProgramContract) -> PassResult:
    name = "host-transfer"
    if c.allow_host_calls:
        return [], []
    vs = [Violation(prog.label, name,
                    f"host transfer inside step program: {ln[:120]}")
          for ln in prog.host_transfer_lines()]
    return vs, []


def constant_bloat(prog: Program, c: ProgramContract) -> PassResult:
    name = "constant-bloat"
    if c.max_constant_bytes is None:
        return [], []
    vs: List[Violation] = []
    for dt, nbytes, line in prog.constants():
        if nbytes > c.max_constant_bytes:
            vs.append(Violation(
                prog.label, name,
                f"{nbytes}-byte {dt} literal baked into HLO (limit "
                f"{c.max_constant_bytes}); pass it as an argument instead: "
                f"{line[:80]}"))
    return vs, []


def recompile_hazard(prog: Program, c: ProgramContract) -> PassResult:
    name = "recompile-hazard"
    if prog.avals is None:
        return [], []
    vs: List[Violation] = []
    for i, a in enumerate(prog.avals):
        if isinstance(a, (bool, int, float, complex, str)):
            vs.append(Violation(
                prog.label, name,
                f"traced arg {i} is a Python scalar {a!r}: every distinct "
                f"value recompiles — pass a jnp array instead"))
        elif getattr(a, "weak_type", False):
            vs.append(Violation(
                prog.label, name,
                f"traced arg {i} ({getattr(a, 'dtype', '?')}"
                f"{list(getattr(a, 'shape', ()))}) is weakly typed: mixing "
                f"with a strong dtype retraces — cast explicitly at the "
                f"boundary"))
    return vs, []


_CHANNEL_RE = re.compile(r"channel_id=(\d+)")


def _first_consumer(lines: List[str], start: int, name: str,
                    ) -> Tuple[Optional[int], Optional[str]]:
    """(line index, kind) of the dominant consumer of instruction `name`:
    the first line after `start` (within the same computation — names are
    scoped) that takes %name as an operand and is a fusion/dot, falling
    back to the first consumer of any kind. Kind is "dominant" or "plain"
    or None when nothing consumes it before the computation closes."""
    tok = re.compile(re.escape(name if name.startswith("%") else "%" + name)
                     + r"(?![-.\w])")
    fallback = None
    for j in range(start + 1, len(lines)):
        if lines[j].startswith("}"):
            break
        if not tok.search(lines[j]):
            continue
        if " fusion(" in lines[j] or " dot(" in lines[j] \
                or " convolution(" in lines[j]:
            return j, "dominant"
        if fallback is None:
            fallback = j
    return (fallback, None if fallback is None else "plain")


def schedule_order(prog: Program, c: ProgramContract) -> PassResult:
    name = "schedule-order"
    if c.schedule_order is None:
        return [], []
    if c.schedule_order != "all-gather-ahead":
        return [Violation(
            prog.label, name,
            f"unknown schedule_order discipline {c.schedule_order!r} "
            f"(known: 'all-gather-ahead')")], []
    reason = collective_combining_reason()
    if reason is None:
        return [], [Skip(
            prog.label, name,
            "backend combines collectives: per-bucket all-gathers are "
            "fused, bucket schedule order is unreadable")]
    # jax-compiled modules are is_scheduled=true, so definition order in
    # the optimized text IS the execution schedule. Bucket order follows
    # channel ids (assigned in emission = bucket order) when present.
    lines = prog.hlo_text.splitlines()
    ags = []
    for ins in prog.op_defs("all-gather"):
        ch = _CHANNEL_RE.search(ins.line)
        ags.append((int(ch.group(1)) if ch else len(ags),
                    ins.index, ins.name))
    ags.sort(key=lambda t: (t[0], t[1]))
    vs: List[Violation] = []
    for (_, li, ni), (_, lj, nj) in zip(ags, ags[1:]):
        ci, kind = _first_consumer(lines, li, ni)
        if ci is None:
            continue
        if lj >= ci:
            vs.append(Violation(
                prog.label, name,
                f"{nj} is defined at line {lj + 1}, after bucket "
                f"predecessor {ni}'s {kind or ''} consumer at line "
                f"{ci + 1} — gathers sit just-in-time, the prefetch "
                f"window did not move them ahead"))
    return vs, []


#: pass name -> pass fn, in report order
PASSES: Dict[str, PassFn] = {
    "collective-contract": collective_contract,
    "donation-leak": donation_leak,
    "dtype-upcast": dtype_upcast,
    "host-transfer": host_transfer,
    "constant-bloat": constant_bloat,
    "recompile-hazard": recompile_hazard,
    "schedule-order": schedule_order,
}
