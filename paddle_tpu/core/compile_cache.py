"""Persistent XLA compilation cache.

Every new process pays full XLA compile cost for programs it has compiled a
thousand times before — for the bench-config GPT step that is minutes of
start-up on a TPU. XLA's persistent compilation cache closes the gap:
compiled executables are serialized keyed on (HLO, compile options, backend
version), and a second process deserializes instead of recompiling.

Where the cache lives, in order:

1. ``JAX_COMPILATION_CACHE_DIR`` set: jax itself reads it at import and the
   cache is THAT directory. Nothing here ever sets or clears
   ``jax_compilation_cache_dir`` then — a directory given from outside is
   never moved or wiped by the program.
2. otherwise ``FLAGS_compile_cache_dir``, whose default is the fixed path
   ``<checkout>/.jax_cache`` (the cache key includes the path, so a
   directory that moves never hits).

``FLAGS_compile_cache_dir=""`` turns the cache OFF in either case, through
``jax_enable_compilation_cache`` (tests/conftest.py does this for the whole
suite: cache-SERVED multi-device CPU executables can produce
nondeterministic collective results on this jax). The min-compile-time /
min-entry-size thresholds are zeroed so even sub-second programs cache.

Cold/warm accounting: jax reports every compile request that consults the
persistent cache and every hit (`jax.monitoring` events); `misses()` is their
difference. The engines snapshot it around a dispatch that compiled: if a
request missed, the compile was COLD (paid XLA), otherwise it was WARM
(served from the cache). Counting the directory's entries instead misread a
compile as warm when an evicted entry was rebuilt under a size cap, because
the count did not grow. Counters land in core.monitor (`engine.compile_cold`
/ `engine.compile_warm` and their _ms twins) and ride into StepTelemetry.

Every jit phase of the process is an event of the span ring
(`observability/tracer.py`), whether or not the persistent cache is on. jax
times each trace, lowering and backend compile of each jit, and each load
from the persistent cache, and hands them to listeners: it announces a
phase's start (`record_scalar`) and its duration at the end. Two listeners
keep a stack a thread, so an event knows at its end whether it is the
OUTERMOST: no other phase of any jit was open when it started (a jit traced
inside another's trace ends first, inside the outer's interval; a lowering
fires a short trace event for every inner jit it meets, 2,247 of them in a
DeepSeek-V2 start). An outermost event is recorded `always` (`jit.trace` /
`jit.lower` / `jit.backend`, args `fun` and, where events started inside
it, their count `inner`), under the innermost boundary span open on the
thread, and bumps `jit.trace_ms` / `jit.lower_ms` / `jit.backend_ms` and
`jit.traces`: the three sums never count a second twice. An inner event is
folded into that count, and recorded as well, under the event it started
in, only from 1 ms up. The cache's load is a part of a backend phase with
no start of its own: `jit.cache_load` is always recorded and counted
(`jit.cache_load_ms`), inside its `jit.backend`. Nothing runs unless jax
compiles: a steady-state step calls neither listener.
"""
from __future__ import annotations

import os
import threading
import time
from typing import Optional

from ..observability import tracer as _obs_tracer
from . import monitor as _monitor
from .flags import flag

ENV_DIR = "JAX_COMPILATION_CACHE_DIR"

# what configure() last applied: None = nothing yet, "" = off, else the dir
_applied: Optional[str] = None

# compile requests that consulted the persistent cache, and those it served
_requests = _hits = 0
_listening = False

_COLD = _monitor.stat("engine.compile_cold")
_WARM = _monitor.stat("engine.compile_warm")
_COLD_MS = _monitor.stat("engine.compile_cold_ms")
_WARM_MS = _monitor.stat("engine.compile_warm_ms")


# jax's event -> the ring's event name; `<name>_ms` is its counter
_JIT_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "jit.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jit.lower",
    "/jax/core/compile/backend_compile_duration": "jit.backend",
    "/jax/compilation_cache/cache_retrieval_time_sec": "jit.cache_load",
}
_JIT_MS = {name: _monitor.stat(name + "_ms") for name in _JIT_EVENTS.values()}
_JIT_TRACES = _monitor.stat("jit.traces")
_INNER_FROM_S = 1e-3          # an inner event shorter than this is only counted
_jit_open = threading.local()     # .stack: [name, id, start, inner] a phase


def _jit_stack() -> list:
    stack = getattr(_jit_open, "stack", None)
    if stack is None:
        stack = _jit_open.stack = []
    return stack


def _on_jit_start(event: str, _value, **_kw) -> None:
    name = _JIT_EVENTS.get(event)
    if name is not None:
        _jit_stack().append([name, _obs_tracer.new_span_id(),
                             time.perf_counter(), 0])


def _on_jit_duration(event: str, duration: float, fun_name=None,
                     **_kw) -> None:
    name = _JIT_EVENTS.get(event)
    if name is None:
        return
    t1 = time.perf_counter()
    stack = _jit_stack()
    if stack and stack[-1][0] == name:
        _, sid, t0, inner = stack.pop()
    else:       # no start was announced: the cache's load
        sid, t0, inner = _obs_tracer.new_span_id(), t1 - duration, 0
    tr = _obs_tracer.get_tracer()
    outermost = not stack or name == "jit.cache_load"
    if stack:
        stack[-1][3] += inner + 1
        parent = stack[-1][1]
    else:
        parent = tr.current_span_id()
    if outermost:
        _JIT_MS[name].increase((t1 - t0) * 1e3)
        if name == "jit.trace":
            _JIT_TRACES.increase()
    elif t1 - t0 < _INNER_FROM_S:
        return
    if fun_name and fun_name.startswith("jit(") and fun_name.endswith(")"):
        fun_name = fun_name[4:-1]     # a lowering's `jit(f)` is the trace's `f`
    args = {"fun": fun_name}
    if inner:
        args["inner"] = inner
    tr.record_complete(name, t0, t1, args, aggregate=outermost, span_id=sid,
                       parent=parent, always=True)


def _listen_to_jit() -> None:
    import jax

    jax.monitoring.register_scalar_listener(_on_jit_start)
    jax.monitoring.register_event_duration_secs_listener(_on_jit_duration)


def placed_from_outside() -> Optional[str]:
    """The directory JAX_COMPILATION_CACHE_DIR names, or None when unset."""
    return os.environ.get(ENV_DIR, "").strip() or None


def cache_dir() -> Optional[str]:
    """The active persistent-cache directory, or None when off."""
    return _applied or None


def enabled() -> bool:
    return bool(_applied)


def configure() -> Optional[str]:
    """Apply the rule above to jax.config. Idempotent; called at package
    import and on every set_flags touching the flag. Returns the active dir
    (None = off)."""
    global _applied
    outside = placed_from_outside()
    d = str(flag("compile_cache_dir") or "").strip()
    if d and outside:
        d = outside
    if d == _applied:
        return cache_dir()
    import jax

    jax.config.update("jax_enable_compilation_cache", bool(d))
    if not outside:
        jax.config.update("jax_compilation_cache_dir", d or None)
    if d:
        os.makedirs(d, exist_ok=True)
        # cache EVERYTHING: the default thresholds skip fast compiles, which
        # on CPU is every test program — and on TPU would skip the small
        # eager rules whose aggregate compile time dominates dygraph warmup
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # jax latches its cache singleton (and whether it is used at all) at the
    # first compile; without a reset a change made here after that compile
    # would not take effect for the life of the process
    from jax._src import compilation_cache as _jcc

    _jcc.reset_cache()
    _applied = d
    global _listening
    if d and not _listening:
        jax.monitoring.register_event_listener(_on_jax_event)
        _listening = True
    return cache_dir()


def _on_jax_event(event: str, **_kw) -> None:
    global _requests, _hits
    if event == "/jax/compilation_cache/compile_requests_use_cache":
        _requests += 1
    elif event == "/jax/compilation_cache/cache_hits":
        _hits += 1


def misses() -> int:
    """Compile requests of this process that consulted the persistent cache
    and were not served from it (-1 when off). Snapshot it round a dispatch
    that compiles: growth means XLA was paid."""
    if not _applied:
        return -1
    return _requests - _hits


def entries() -> int:
    """Number of serialized executables in the cache dir (-1 when off).
    Cheap enough to snapshot around a compile: one readdir."""
    if not _applied:
        return -1
    try:
        return sum(1 for n in os.listdir(_applied)
                   if n.endswith("-cache"))
    except OSError:
        return -1


def note_compile(wall_ms: int, persistent_before: int,
                 persistent_after: int) -> Optional[str]:
    """Classify one observed executable-cache compile as cold/warm from two
    snapshots of `misses()`.

    Only meaningful when the persistent cache is on: a compile whose
    requests were all served FROM the store was warm (deserialization cost
    only); one with a request the store missed paid XLA (cold), whether or
    not the store then held more entries than before. Returns
    "cold" / "warm" / None (cache off)."""
    if persistent_before < 0 or persistent_after < 0:
        return None
    if persistent_after > persistent_before:
        _COLD.increase()
        _COLD_MS.increase(wall_ms)
        return "cold"
    _WARM.increase()
    _WARM_MS.increase(wall_ms)
    return "warm"


def _on_flag_change(name):
    if name == "compile_cache_dir":
        configure()


from . import flags as _flags  # noqa: E402

_flags.on_change(_on_flag_change)
_listen_to_jit()
configure()
