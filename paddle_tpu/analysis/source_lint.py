"""AST source linter for tracing hazards in paddle_tpu code.

jit makes certain Python idioms silently catastrophic: a ``float(x)`` on a
traced value blocks dispatch on a device→host sync (or fails under AOT), a
``time.time()`` inside a traced body freezes one wall-clock reading into
the compiled program forever, ``random.random()`` bakes a single "random"
constant, a mutable default arg aliases state across calls of a public
API, and a bare ``lock.acquire()`` in the threaded subsystems leaks the
lock on any exception path. None of these crash in tests; all of them
corrupt production. This linter encodes them as AST rules:

=============== ==========================================================
host-sync       ``float(x)``/``int(x)``/``bool(x)`` on a non-literal,
                ``.item()``/``.tolist()``, ``np.asarray``/``np.array`` —
                inside a traced (jitted/shard_mapped/scanned) body
host-time       ``time.time()``/``perf_counter()``/``datetime.now()``
                inside a traced body
host-random     Python ``random.*`` or ``np.random.*`` (not ``jax.random``)
                inside a traced body
mutable-default ``def f(x, acc=[])`` / ``={}`` / ``=set()`` in any public
                function (all files, not just traced code)
bare-lock       ``lock.acquire()`` outside a ``with`` statement (all files)
model-serving   a file under ``paddle_tpu/models/`` imports from
                ``paddle_tpu.serving``: the arrow points up
model-cache     a file under ``paddle_tpu/models/`` writes an array in
                place, ``x.at[...]`` or ``dynamic_update_slice(x, ...)``,
                whatever ``x`` is called: where a cache's row lives is
                nn/kv_cache.py's to know, and a write that is not a cache's
                (beam search's token table) is named in the baseline
=============== ==========================================================

Tracedness is syntactic: a function is traced when it is decorated with
``jit``/``shard_map``/``partial(jax.jit, ...)`` or its *name* is passed to
a tracing entry point (``jax.jit(f)``, ``lax.scan(body, ...)``,
``grad``/``vmap``/``checkpoint``/``while_loop``/``cond``...), and every
function nested inside a traced one is traced too. That under-approximates
dynamically traced code and over-approximates dead branches — both are
what a linter should do; deliberate keeps go in the baseline with a
justification.

Baseline format (``tools/lint_tracing_baseline.txt``): one
``relpath:rule:qualname:token`` key per line, optional ``# justification``
after it. The comparison is burned-down in both directions: a finding not
in the baseline fails, and a baseline entry no longer found fails too
(delete it — the debt is paid).
"""
from __future__ import annotations

import ast
import os
import re
from dataclasses import dataclass
from typing import Dict, List, Optional, Set, Tuple

#: final attribute names that trace their function-valued arguments
_TRACE_ENTRIES = {
    "jit", "shard_map", "scan", "grad", "value_and_grad", "vmap", "pmap",
    "checkpoint", "remat", "while_loop", "fori_loop", "cond", "switch",
    "custom_vjp", "custom_jvp", "eval_shape", "make_jaxpr", "xmap",
    "associative_scan", "capture_jit",
}
_HOST_TIME_CALLS = {"time", "perf_counter", "monotonic", "process_time",
                    "now", "utcnow", "time_ns", "perf_counter_ns"}
_SYNC_BUILTINS = {"float", "int", "bool", "complex"}
_SYNC_METHODS = {"item", "tolist"}
_NP_SYNC_FUNCS = {"asarray", "array"}
_MUTABLE_LITERALS = (ast.List, ast.Dict, ast.Set, ast.ListComp,
                     ast.DictComp, ast.SetComp)
_MUTABLE_CTORS = {"list", "dict", "set", "defaultdict", "OrderedDict"}
_MODELS_DIR = "paddle_tpu/models/"


@dataclass
class Finding:
    path: str       # repo-relative
    line: int
    rule: str
    qualname: str   # enclosing function ("a.b.<locals>.c" style, or <module>)
    token: str      # the offending callee/arg, for a stable baseline key
    message: str

    @property
    def key(self) -> str:
        """Line-number-free identity — survives unrelated edits above it."""
        return f"{self.path}:{self.rule}:{self.qualname}:{self.token}"

    def __str__(self) -> str:
        return (f"{self.path}:{self.line}: [{self.rule}] {self.qualname}: "
                f"{self.message}")


def _attr_chain(node: ast.AST) -> str:
    """'a.b.c' for Name/Attribute chains, '' otherwise."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def _callee(call: ast.Call) -> str:
    return _attr_chain(call.func)


def _is_partial_of_tracer(call: ast.Call) -> bool:
    """partial(jax.jit, ...) / functools.partial(shard_map, ...)."""
    if _callee(call).split(".")[-1] != "partial" or not call.args:
        return False
    return _attr_chain(call.args[0]).split(".")[-1] in _TRACE_ENTRIES


class _FileLinter(ast.NodeVisitor):
    def __init__(self, relpath: str, public_api: bool):
        self.relpath = relpath
        self.public_api = public_api
        self.findings: List[Finding] = []
        self.traced_names: Set[str] = set()
        self._stack: List[str] = []          # qualname parts
        self._traced_depth = 0               # >0 → inside a traced body
        self._with_calls: Set[ast.Call] = set()
        self._in_models = relpath.replace(os.sep, "/").startswith(_MODELS_DIR)

    # -- sweep 1: which local functions get traced? -------------------------
    # Traced names are collected PER ENCLOSING SCOPE as "scope::name": the
    # inner `step` closure a _build method hands to jax.jit must not mark a
    # same-named public `step` method on the class as traced.
    def collect_traced(self, tree: ast.AST) -> None:
        def walk(node: ast.AST, scope: str) -> None:
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                      ast.ClassDef)):
                    walk(child, f"{scope}.{child.name}" if scope
                         else child.name)
                    continue
                if isinstance(child, ast.Call) and \
                        _callee(child).split(".")[-1] in _TRACE_ENTRIES:
                    for arg in list(child.args) + [kw.value
                                                   for kw in child.keywords]:
                        nm = _attr_chain(arg)
                        if nm and "." not in nm:
                            self.traced_names.add(f"{scope}::{nm}")
                walk(child, scope)

        walk(tree, "")

    # -- sweep 2: walk, tracking qualname + tracedness ----------------------
    def _qual(self) -> str:
        return ".".join(self._stack) or "<module>"

    def _emit(self, node: ast.AST, rule: str, token: str, msg: str) -> None:
        self.findings.append(Finding(
            self.relpath, getattr(node, "lineno", 0), rule, self._qual(),
            token, msg))

    def _decorated_traced(self, node) -> bool:
        for dec in node.decorator_list:
            if isinstance(dec, ast.Call):
                if (_callee(dec).split(".")[-1] in _TRACE_ENTRIES
                        or _is_partial_of_tracer(dec)):
                    return True
            elif _attr_chain(dec).split(".")[-1] in _TRACE_ENTRIES:
                return True
        return False

    def _visit_func(self, node) -> None:
        traced = (self._decorated_traced(node)
                  or f"{'.'.join(self._stack)}::{node.name}"
                  in self.traced_names
                  or self._traced_depth > 0)
        if self.public_api and not node.name.startswith("_"):
            self._check_defaults(node)
        self._stack.append(node.name)
        if traced:
            self._traced_depth += 1
        self.generic_visit(node)
        if traced:
            self._traced_depth -= 1
        self._stack.pop()

    visit_FunctionDef = _visit_func
    visit_AsyncFunctionDef = _visit_func

    def visit_ClassDef(self, node: ast.ClassDef) -> None:
        self._stack.append(node.name)
        self.generic_visit(node)
        self._stack.pop()

    def visit_Lambda(self, node: ast.Lambda) -> None:
        # lambdas passed to tracers are traced; approximating: a lambda in an
        # already-traced scope keeps the scope's tracedness (generic_visit).
        self.generic_visit(node)

    def _check_defaults(self, node) -> None:
        args = node.args
        for a, d in list(zip(args.args[::-1], args.defaults[::-1])) + \
                list(zip(args.kwonlyargs, args.kw_defaults)):
            if d is None:
                continue
            mutable = isinstance(d, _MUTABLE_LITERALS) or (
                isinstance(d, ast.Call)
                and _callee(d).split(".")[-1] in _MUTABLE_CTORS)
            if mutable:
                self.findings.append(Finding(
                    self.relpath, node.lineno, "mutable-default",
                    ".".join(self._stack + [node.name]) or node.name, a.arg,
                    f"public API {node.name!r} has mutable default for "
                    f"{a.arg!r} — shared across calls; use None + init"))

    # -- the models' seam: no import of serving, no cache written by hand ----
    def _check_import(self, node, modules) -> None:
        for mod in modules:
            parts = mod.split(".")
            if "serving" in parts[:2] and (parts[0] in ("serving",
                                                        "paddle_tpu")):
                self._emit(node, "model-serving", mod,
                           f"a model imports {mod}: models sit below "
                           f"serving; what both need lives in nn/ or core/")

    def visit_Import(self, node: ast.Import) -> None:
        if self._in_models:
            self._check_import(node, [a.name for a in node.names])

    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        if self._in_models and (node.level == 2 or (
                node.level == 0 and (node.module or "").startswith(
                    "paddle_tpu"))):
            base = node.module or ""
            self._check_import(node, [base] if base and base != "paddle_tpu"
                               else [f"{base}.{a.name}".lstrip(".")
                                     for a in node.names])

    def _check_cache_write(self, node, target: ast.AST, how: str) -> None:
        if self._in_models:
            name = _attr_chain(target).split(".")[-1] or "<expr>"
            self._emit(node, "model-cache", f"{name}{how}",
                       f"{name}{how}: a model writes an array in place; a "
                       f"cache's rows go to the handle's update() "
                       f"(nn/kv_cache.py), anything else into the baseline")

    def visit_Subscript(self, node: ast.Subscript) -> None:
        if isinstance(node.value, ast.Attribute) and node.value.attr == "at":
            self._check_cache_write(node, node.value.value, ".at[")
        self.generic_visit(node)

    def visit_With(self, node: ast.With) -> None:
        for item in node.items:
            if isinstance(item.context_expr, ast.Call):
                self._with_calls.add(item.context_expr)
        self.generic_visit(node)

    def visit_Call(self, node: ast.Call) -> None:
        callee = _callee(node)
        # the method name survives even when the receiver is a call result
        # (x.mean().item() has no Name root, so the attr chain is empty)
        last = (node.func.attr if isinstance(node.func, ast.Attribute)
                else callee.split(".")[-1])

        # bare-lock: anywhere, any file
        if last == "acquire" and node not in self._with_calls \
                and isinstance(node.func, ast.Attribute):
            self._emit(node, "bare-lock", _attr_chain(node.func),
                       f"bare {callee}() — leaks the lock on exception; "
                       f"use `with`")

        if last == "dynamic_update_slice" and node.args:
            self._check_cache_write(node, node.args[0],
                                    " in dynamic_update_slice(")
        if self._traced_depth > 0:
            self._check_traced_call(node, callee, last)
        self.generic_visit(node)

    def _check_traced_call(self, node: ast.Call, callee: str,
                           last: str) -> None:
        # host-sync: float(x)/int(x)/bool(x) on non-literals, .item(), np.*
        if callee in _SYNC_BUILTINS and node.args and not isinstance(
                node.args[0], ast.Constant):
            self._emit(node, "host-sync", callee,
                       f"{callee}() on a traced value forces a device→host "
                       f"sync (and fails under AOT); keep it in jnp")
        elif last in _SYNC_METHODS and isinstance(node.func, ast.Attribute):
            self._emit(node, "host-sync", "." + last,
                       f".{last}() inside a traced body syncs to host")
        elif last in _NP_SYNC_FUNCS and callee.split(".")[0] in (
                "np", "numpy", "onp"):
            self._emit(node, "host-sync", callee,
                       f"{callee}() materializes a traced value on host; "
                       f"use jnp")
        # host-time
        elif last in _HOST_TIME_CALLS and callee.split(".")[0] in (
                "time", "datetime"):
            self._emit(node, "host-time", callee,
                       f"{callee}() in a traced body compiles to a frozen "
                       f"constant; time outside jit")
        # host-random (python/numpy RNG; jax.random is fine)
        elif callee.split(".")[0] == "random" or callee.startswith(
                ("np.random.", "numpy.random.", "onp.random.")):
            self._emit(node, "host-random", callee,
                       f"{callee}() in a traced body bakes one sample into "
                       f"the program; thread a jax.random key")


def lint_source(src: str, relpath: str,
                public_api: Optional[bool] = None) -> List[Finding]:
    """Lint one file's source. public_api defaults to 'is a library file'
    (paddle_tpu/*, not tests/tools)."""
    if public_api is None:
        public_api = relpath.startswith("paddle_tpu")
    try:
        tree = ast.parse(src, filename=relpath)
    except SyntaxError as e:
        return [Finding(relpath, e.lineno or 0, "parse-error", "<module>",
                        "syntax", f"cannot parse: {e.msg}")]
    linter = _FileLinter(relpath, public_api)
    linter.collect_traced(tree)
    linter.visit(tree)
    return linter.findings


def lint_tree(root: str,
              subdirs: Tuple[str, ...] = ("paddle_tpu", "tools"),
              ) -> List[Finding]:
    """Lint every .py under root/{subdirs}, sorted by (path, line)."""
    findings: List[Finding] = []
    for sub in subdirs:
        base = os.path.join(root, sub)
        if not os.path.isdir(base):
            continue
        for dirpath, dirnames, filenames in os.walk(base):
            dirnames[:] = sorted(d for d in dirnames
                                 if d not in ("__pycache__",))
            for fn in sorted(filenames):
                if not fn.endswith(".py"):
                    continue
                path = os.path.join(dirpath, fn)
                rel = os.path.relpath(path, root)
                with open(path, "r", encoding="utf-8") as f:
                    findings.extend(lint_source(f.read(), rel))
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    return findings


# ---- baseline -------------------------------------------------------------

def load_baseline(path: str) -> Dict[str, str]:
    """key -> justification. Missing file = empty baseline."""
    out: Dict[str, str] = {}
    if not os.path.exists(path):
        return out
    with open(path, "r", encoding="utf-8") as f:
        for raw in f:
            line = raw.strip()
            if not line or line.startswith("#"):
                continue
            key, _, just = line.partition("#")
            out[key.strip()] = just.strip()
    return out


def compare_to_baseline(findings: List[Finding], baseline: Dict[str, str],
                        ) -> Tuple[List[Finding], List[str]]:
    """(new findings not in baseline, stale baseline keys no longer found).

    Both directions fail: new debt must be fixed or justified, paid-off
    debt must be deleted from the baseline — that's what keeps it burned
    DOWN rather than append-only.
    """
    current = {f.key for f in findings}
    new = [f for f in findings if f.key not in baseline]
    stale = sorted(k for k in baseline if k not in current)
    return new, stale
