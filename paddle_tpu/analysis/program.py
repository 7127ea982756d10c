"""The analyzable unit: one lowered program + its HLO text, parsed lazily.

A :class:`Program` wraps whatever is available about one executable —
a compiled object (``jax.jit(f).lower(...).compile()``), raw optimized-HLO
text, the abstract call signature the engines stash for
``introspect_executables()``, or a (fn, avals) pair that can produce all of
the above on demand. Passes ask for what they need (`hlo_text`,
`memory_analysis`, `avals`) and the expensive steps (AOT compile) happen at
most once per program.

HLO parsing here keeps the counting semantics the perf-gate tests
established (op DEFINITIONS, `-done` halves of async pairs excluded,
`) while(` for loop count) so migrating a hand-written gate onto a contract
cannot change its verdict. An op is recognised by its OPCODE — the token
after the result type — never by the instruction's name: XLA names an
instruction after the JAX primitive that produced it
(`%psum_invariant.7 = f32[1,8]{1,0} all-reduce(%param.1), ...`).
"""
from __future__ import annotations

import re
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

_INSTR_HEAD_RE = re.compile(r"^\s*(?:ROOT\s+)?(%?[-.\w]+)\s*=\s*")
_OPCODE_RE = re.compile(r"\s*([a-z][-\w]*)\(")


def _close_paren(text: str, start: int) -> int:
    """Index of the ")" closing the "(" at text[start] (len(text) - 1 if the
    line is cut short)."""
    depth = 0
    for i in range(start, len(text)):
        if text[i] == "(":
            depth += 1
        elif text[i] == ")":
            depth -= 1
            if depth == 0:
                return i
    return len(text) - 1


class Instruction(NamedTuple):
    """One parsed HLO instruction line."""
    name: str      # "%psum_invariant.7"
    result: str    # result type text, "f32[1,8]{1,0}" or a "(..., ...)" tuple
    opcode: str    # "all-reduce"
    index: int     # line index in the module text
    line: str


def parse_instruction(line: str, index: int = 0) -> Optional[Instruction]:
    """`%name = <result type> opcode(operands), attrs` -> Instruction, or
    None for anything else (computation headers, braces, the module line).
    The result type is one space-free token or a parenthesised tuple."""
    m = _INSTR_HEAD_RE.match(line)
    if m is None:
        return None
    rest = line[m.end():]
    if rest.startswith("("):
        end = _close_paren(rest, 0) + 1
    else:
        sp = re.search(r"\s", rest)
        if sp is None:
            return None
        end = sp.start()
    op = _OPCODE_RE.match(rest, end)
    if op is None:
        return None
    return Instruction(m.group(1), rest[:end], op.group(1), index, line)


def _is_kind(opcode: str, kind: str) -> bool:
    """`-start` opens the async pair that performs the op; `-done` only
    completes it and is not another collective."""
    return opcode == kind or opcode == kind + "-start"


_WHILE_RE = re.compile(r"\) while\(")
_CONST_TYPE_RE = re.compile(r"([a-z]+[0-9]*)\[([\d,]*)\]")
_SHAPE_GROUP_RE = re.compile(r"(bf16|f16|f32|f64|s8|u8|s16|u16|s32|u32|s64|"
                             r"u64|pred|c64|c128)\[([\d,]*)\]")

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "f16": 2, "bf16": 2, "s16": 2,
                "u16": 2, "f32": 4, "s32": 4, "u32": 4, "c64": 8, "f64": 8,
                "s64": 8, "u64": 8, "c128": 16}

#: custom-call targets that bounce through the host (python callbacks); TPU
#: kernel custom-calls (tpu_custom_call, Mosaic) are NOT host transfers
_HOST_CALLBACK_MARKERS = ("callback", "host")
_HOST_OP_KINDS = ("infeed", "outfeed", "send", "recv")


def _elems(csv: str) -> int:
    n = 1
    for d in csv.split(","):
        if d:
            n *= int(d)
    return n


class Program:
    """One executable under analysis. Construct with whichever artifacts
    exist; the rest is derived lazily (and at most once)."""

    def __init__(self, label: str, compiled: Any = None,
                 hlo_text: Optional[str] = None, avals: Any = None,
                 lower_thunk: Any = None):
        self.label = label
        self.avals = avals
        self._compiled = compiled
        self._hlo_text = hlo_text
        self._lower_thunk = lower_thunk
        self._mem = _UNSET
        self._instrs: Optional[List[Instruction]] = None

    @classmethod
    def from_stash(cls, label: str, fn: Any, avals: Any) -> "Program":
        """From an engine's ``_exec_stash`` entry: AOT ``lower().compile()``
        deferred until a pass first needs the HLO (one compile per label)."""
        flat = _flatten(avals)
        return cls(label, avals=flat,
                   lower_thunk=lambda: fn.lower(*avals).compile())

    @property
    def compiled(self) -> Any:
        if self._compiled is None and self._lower_thunk is not None:
            self._compiled = self._lower_thunk()
        return self._compiled

    @property
    def hlo_text(self) -> str:
        if self._hlo_text is None:
            comp = self.compiled
            if comp is None:
                raise ValueError(
                    f"program {self.label!r} has neither HLO text nor a "
                    f"compiled executable to read it from")
            self._hlo_text = comp.as_text()
        return self._hlo_text

    def memory_analysis(self) -> Any:
        """compiled.memory_analysis() or None (text-only programs, backends
        without PJRT memory stats)."""
        if self._mem is _UNSET:
            try:
                comp = self.compiled
                self._mem = None if comp is None else comp.memory_analysis()
            except Exception:
                self._mem = None
        return self._mem

    # ---- HLO queries -------------------------------------------------------
    def instructions(self) -> List[Instruction]:
        """Every instruction of the module text, parsed once."""
        if self._instrs is None:
            self._instrs = [
                ins for i, ln in enumerate(self.hlo_text.splitlines())
                for ins in (parse_instruction(ln, i),) if ins is not None]
        return self._instrs

    def op_defs(self, kind: str) -> List[Instruction]:
        """Instructions whose opcode is `kind` (or its async `-start`)."""
        return [ins for ins in self.instructions()
                if _is_kind(ins.opcode, kind)]

    def count_ops(self, kind: str) -> int:
        """Op DEFINITIONS of `kind`, matched on the opcode."""
        return len(self.op_defs(kind))

    def op_def_lines(self, kind: str) -> List[str]:
        return [ins.line for ins in self.op_defs(kind)]

    def count_while_loops(self) -> int:
        return len(_WHILE_RE.findall(self.hlo_text))

    def constants(self) -> List[Tuple[str, int, str]]:
        """(dtype, bytes, line) per `constant` op definition."""
        out = []
        for ins in self.op_defs("constant"):
            m = _CONST_TYPE_RE.match(ins.result)
            if m:
                dt, csv = m.group(1), m.group(2)
                out.append((dt, _elems(csv) * _DTYPE_BYTES.get(dt, 4),
                            ins.line.strip()))
        return out

    def host_transfer_lines(self) -> List[str]:
        """infeed/outfeed/send/recv op definitions plus custom-calls whose
        target names a host (python) callback."""
        out = []
        for ins in self.instructions():
            if ins.opcode == "custom-call":
                m = re.search(r'custom_call_target="([^"]*)"', ins.line)
                tgt = (m.group(1) if m else "").lower()
                if any(mark in tgt for mark in _HOST_CALLBACK_MARKERS):
                    out.append(ins.line.strip())
            elif any(_is_kind(ins.opcode, k) for k in _HOST_OP_KINDS):
                out.append(ins.line.strip())
        return out

    def custom_calls(self, target: str) -> List[Tuple[Instruction, List[str]]]:
        """(instruction, operand result types) for every custom-call to
        `target`. Operands print as bare names in this XLA's text, so their
        types are looked up from the defining instructions — this is how a
        per-device program shows what shape a Mosaic kernel really runs at
        and which ops feed it."""
        types = {ins.name.lstrip("%"): ins.result
                 for ins in self.instructions()}
        out = []
        for ins in self.op_defs("custom-call"):
            if f'custom_call_target="{target}"' not in ins.line:
                continue
            start = ins.line.index("custom-call(") + len("custom-call")
            args = ins.line[start:_close_paren(ins.line, start)]
            names = re.findall(r"%([-.\w]+)", args)
            out.append((ins, [types.get(n, "?") for n in names]))
        return out

    def result_shapes(self, line: str) -> List[Tuple[str, int]]:
        """(dtype, element-count) for every typed shape mentioned on an op
        line (result + operands — operand dtypes equal their defs')."""
        return [(dt, _elems(csv))
                for dt, csv in _SHAPE_GROUP_RE.findall(line)]


_UNSET = object()


def _flatten(avals) -> List[Any]:
    """Leaves of the stash's aval tree (jax optional: avals may be plain)."""
    try:
        import jax

        return list(jax.tree_util.tree_leaves(avals))
    except Exception:
        return [avals]


def programs_from_stash(stash: Dict[str, Any]) -> List[Program]:
    """One lazy Program per engine ``_exec_stash`` entry."""
    return [Program.from_stash(label, fn, avals)
            for label, (fn, avals) in sorted(stash.items())]
