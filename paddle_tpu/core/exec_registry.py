"""Unified keyed executable registry (ISSUE 18 tentpole).

Before this module the repo grew four parallel executable caches, each with
its own keying, eviction, and compile accounting: the decode LRU on
GPTForPretraining (``_generate_jit_cache``), the bucketed prefill / decode /
verify / draft rung dicts on ServingEngine, TrainStepEngine's step/accum/scan
caches, and the persistent XLA store in ``core.compile_cache``. One story
replaces them: an :class:`ExecutableRegistry` maps a structured key
(program id + abstract shapes/dtypes + mesh/sharding + the flags that change
lowering) to an :class:`ExecEntry` holding the jitted callable, its donation
metadata, optionally an AOT-compiled executable, and pin state.

Semantics the four legacy sites pinned, preserved here:

- LRU eviction bounded by a capacity (int or a callable reading a flag at
  eviction time, so ``FLAGS_decode_jit_cache_size`` keeps working live), with
  per-registry alias counters (``decode.jit_compiles`` /
  ``decode.cache_evictions``) so existing monitor assertions hold.
- Eviction REFUSES entries pinned by active users (the latent decode-LRU
  hazard: an evicted executable another slot family dispatches next step).
  Refusals are counted (``exec.registry.evict_refusals``), never silent.
- Serving-style compile accounting by jit-cache growth (``_cache_size``
  deltas; one-per-wrapper fallback when the attribute is missing) and
  train-style accounting (explicit before/after sizes + engine.jit_* monitor
  counters + cold/warm classification through ``core.compile_cache``).
- exec_introspect's signature stashing (label -> (fn, avals)) and donation
  map live on the registry, so ``introspect_executables`` /
  ``default_contracts`` / ``mem_report`` keep their shapes.

AOT: :meth:`ExecutableRegistry.precompile` lowers+compiles an entry at its
abstract signature (``jit(...).lower().compile()``) and installs the result
as the entry's fast path. Dispatch prefers the AOT executable and falls back
to the jitted fn on signature mismatch (counted, never fatal) — drift between
the precompiled signature and a live dispatch costs one lazy compile instead
of an outage. Compiles that go through the persistent store are classified
cold/warm exactly like the train engine's.

Telemetry (core.monitor counters, global across registries):
``exec.registry.hits / misses / evictions / evict_refusals / compile_ms /
aot_compiles / aot_fallbacks``. When an observability metrics registry is
active, per-label counters ``exec.registry.<label>.hits|misses|evictions``
and histograms ``exec.registry.compile_cold_ms`` /
``exec.registry.compile_warm_ms`` land there too; :meth:`rollup` returns the
same numbers as a plain dict for trace sinks.
"""
from __future__ import annotations

import threading
import time
import types
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Tuple, Union

from ..observability import tracer as _obs_tracer
from . import compile_cache as _compile_cache
from . import flags as _flags
from . import monitor as _monitor

_HITS = _monitor.stat("exec.registry.hits")
_MISSES = _monitor.stat("exec.registry.misses")
_EVICTIONS = _monitor.stat("exec.registry.evictions")
_EVICT_REFUSALS = _monitor.stat("exec.registry.evict_refusals")
_COMPILE_MS = _monitor.stat("exec.registry.compile_ms")
_AOT_COMPILES = _monitor.stat("exec.registry.aot_compiles")
_AOT_FALLBACKS = _monitor.stat("exec.registry.aot_fallbacks")


def _jit_cache_size(fn) -> int:
    """Executable-cache entry count of a jitted fn (-1: not exposed)."""
    try:
        return int(fn._cache_size())
    except Exception:
        return -1


def _call(fn, args):
    return fn(*args)


# An executable's first call traces and lowers its program in Python, a few
# hundred frames deep. CPython keeps a thread's frames in chunks of 16 KiB and
# gives a chunk back to the system the moment its first frame returns: a loop
# whose own frame is the last that fits its chunk maps and unmaps a chunk with
# every call it makes. Whether jax's per-equation lowering loop lands on such a
# boundary depends on how deep the CALLER of the first call stands, so the same
# program lowered in 0.6 or in 10 s (PERF.md, PR 32: 3,029 equations in the
# decode scan's body, four calls each). `_call` with a frame of 512 KiB opens
# one chunk of 1 MiB that holds every frame below it: no boundary, whatever
# the caller's depth. Used for first calls only (a mapping a call otherwise).
_call_in_one_chunk = types.FunctionType(
    _call.__code__.replace(co_stacksize=1 << 16), globals(),
    "_call_in_one_chunk")


def _default_aval(a):
    import jax

    return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                weak_type=getattr(a, "weak_type", False))


def abstract_args(call_args, aval_fn: Optional[Callable] = None):
    """ShapeDtypeStruct tree for a concrete call-arg tuple — the registry's
    canonical signature form (weak_type rides along; pass ``aval_fn`` to
    keep special leaves concrete, e.g. PRNG-key-dtyped arrays)."""
    import jax

    return jax.tree_util.tree_map(aval_fn or _default_aval, call_args)


class ExecEntry:
    """One registered executable: the jitted fn, its donation metadata, and
    (after :meth:`ExecutableRegistry.precompile`) an AOT-compiled fast path.

    Calling the entry dispatches the AOT executable when present and its
    signature still matches, else the jitted fn (fallbacks are counted)."""

    __slots__ = ("key", "fn", "label", "donate", "avals", "aot", "pins",
                 "hits", "_seen_cache_size", "_counted_once", "_via_aot")

    def __init__(self, key, fn, label: str, donate: Tuple[int, ...]):
        self.key = key
        self.fn = fn
        self.label = label
        self.donate = tuple(donate)
        self.avals = None          # set when stashed / precompiled
        self.aot = None            # AOT-compiled executable, if any
        self.pins = 0
        self.hits = 0
        self._seen_cache_size = 0  # last observed jit-cache size of fn
        self._counted_once = False  # one-per-wrapper fallback fired
        self._via_aot = False      # last dispatch went through self.aot

    def __call__(self, *args):
        if self.aot is not None:
            try:
                out = self.aot(*args)
                self._via_aot = True
                return out
            except TypeError:
                # signature drift between precompile and live dispatch:
                # fall back to the lazy jit path, once, audibly
                self.aot = None
                _AOT_FALLBACKS.increase()
        self._via_aot = False
        if self._seen_cache_size == 0 and not self._counted_once:
            return _call_in_one_chunk(self.fn, args)    # traces and lowers
        return self.fn(*args)

    def cache_size(self) -> int:
        return _jit_cache_size(self.fn)

    @property
    def pinned(self) -> bool:
        return self.pins > 0


class _LowersAsTraced:
    """A stashed jitted fn whose later AOT ``lower()`` re-enters the autocast
    scope that was active when it was stashed, i.e. first traced. Autocast
    is trace-time state: the same function lowered outside that scope is an
    f32 program that never ran, and introspection or analysis of it would
    describe the wrong executable."""

    def __init__(self, fn):
        from .dispatch import amp_ctx

        self._fn = fn
        self._amp = amp_ctx()

    def lower(self, *args, **kw):
        from .dispatch import amp_scope

        with amp_scope(self._amp):
            return self._fn.lower(*args, **kw)

    def __getattr__(self, name):
        return getattr(self._fn, name)


class ExecutableRegistry:
    """Keyed executable store with LRU eviction, pinning, donation metadata,
    compile telemetry, and optional AOT precompilation.

    Keys are hashable tuples whose first element is the program id (a dotted
    string: ``"gpt.generate"``, ``"serve.prefill"``, ``"train.accum"`` ...);
    the remaining elements are whatever distinguishes lowerings — abstract
    shapes/dtypes, mesh/sharding descriptors, flag values.

    ``capacity``: max entries (int, or a zero-arg callable read at insert
    time so flag changes apply live). <= 0 means unbounded. Eviction drops
    the least-recently-used UNPINNED entry; if every entry is pinned the
    registry refuses to evict (counted) rather than break an active
    dispatcher."""

    def __init__(self, name: str,
                 capacity: Union[int, Callable[[], int]] = 0,
                 miss_counter: Optional[str] = None,
                 eviction_counter: Optional[str] = None):
        self.name = name
        self._capacity = capacity
        self._miss_counter = miss_counter
        self._eviction_counter = eviction_counter
        self._entries: "OrderedDict[Any, ExecEntry]" = OrderedDict()
        self._lock = threading.RLock()
        # instance-local telemetry (monitor counters are process-global)
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.evict_refusals = 0
        self.aot_fallbacks = 0
        self._label_stats: Dict[str, Dict[str, int]] = {}
        self._compile_ms: List[float] = []
        self._compile_cold_ms: List[float] = []
        self._compile_warm_ms: List[float] = []
        # exec_introspect signature stash: label -> (fn, avals)
        self._stash: Dict[str, Tuple[Any, Any]] = {}
        self._donated: Dict[str, Tuple[int, ...]] = {}

    # ------------------------------------------------------------- lookup
    def capacity(self) -> int:
        cap = self._capacity
        if callable(cap):
            try:
                cap = cap()
            except Exception:
                cap = 0
        try:
            return int(cap)
        except (TypeError, ValueError):
            return 0

    def _lstats(self, label: str) -> Dict[str, int]:
        st = self._label_stats.get(label)
        if st is None:
            st = self._label_stats[label] = {
                "hits": 0, "misses": 0, "evictions": 0}
        return st

    def _metrics_registry(self):
        try:
            from ..observability import metrics as _obs_metrics

            return _obs_metrics.active_registry()
        except Exception:
            return None

    def _bump_label(self, label: str, stat: str, n: int = 1) -> None:
        self._lstats(label)[stat] += n
        reg = self._metrics_registry()
        if reg is not None:
            reg.counter(f"exec.registry.{label}.{stat}").inc(n)

    def get(self, key) -> Optional[ExecEntry]:
        """Lookup without insert (counts a hit when found)."""
        with self._lock:
            entry = self._entries.get(key)
            if entry is None:
                return None
            self._entries.move_to_end(key)
            entry.hits += 1
            self.hits += 1
            _HITS.increase()
            self._bump_label(entry.label, "hits")
            return entry

    def get_or_build(self, key, build: Callable[[], Any],
                     label: Optional[str] = None,
                     donate: Tuple[int, ...] = (),
                     pin: bool = False) -> ExecEntry:
        """The one lookup/insert story. ``build`` returns the jitted fn on a
        miss; ``label`` names the program for telemetry/introspection (key[0]
        when omitted); ``pin=True`` admits the entry pinned (engine working
        sets that must never be evicted under them)."""
        with self._lock:
            entry = self._entries.get(key)
            if entry is not None:
                self._entries.move_to_end(key)
                entry.hits += 1
                self.hits += 1
                _HITS.increase()
                self._bump_label(entry.label, "hits")
                return entry
        # build OUTSIDE the lock: tracing can be slow and may re-enter
        if label is None:
            label = str(key[0]) if isinstance(key, tuple) and key else str(key)
        fn = build()
        with self._lock:
            entry = self._entries.get(key)
            if entry is not None:  # raced: first insert wins
                self._entries.move_to_end(key)
                entry.hits += 1
                self.hits += 1
                _HITS.increase()
                self._bump_label(entry.label, "hits")
                return entry
            entry = ExecEntry(key, fn, label, donate)
            if pin:
                entry.pins = 1
            self._entries[key] = entry
            self.misses += 1
            _MISSES.increase()
            self._bump_label(label, "misses")
            if self._miss_counter:
                _monitor.stat(self._miss_counter).increase()
            self._enforce_capacity()
            return entry

    def put(self, key, fn, label: Optional[str] = None,
            donate: Tuple[int, ...] = (), pin: bool = False) -> ExecEntry:
        """Insert (or replace) an entry with an already-built fn. Counts a
        miss on first insert only; replacement keeps pin state."""
        with self._lock:
            old = self._entries.pop(key, None)
            if label is None:
                label = old.label if old is not None else (
                    str(key[0]) if isinstance(key, tuple) and key
                    else str(key))
            entry = ExecEntry(key, fn, label,
                              donate or (old.donate if old else ()))
            entry.pins = old.pins if old is not None else (1 if pin else 0)
            if old is None and pin:
                entry.pins = 1
            self._entries[key] = entry
            if old is None:
                self.misses += 1
                _MISSES.increase()
                self._bump_label(label, "misses")
                if self._miss_counter:
                    _monitor.stat(self._miss_counter).increase()
                self._enforce_capacity()
            return entry

    def _enforce_capacity(self) -> None:
        cap = self.capacity()
        if cap <= 0:
            return
        while len(self._entries) > cap:
            victim_key = None
            for k, e in self._entries.items():  # oldest-first
                if not e.pinned:
                    victim_key = k
                    break
            if victim_key is None:
                # every entry is pinned by an active user: refusing to
                # evict is the ISSUE-18 hazard fix — an over-full registry
                # beats an executable yanked out from under a live slot
                self.evict_refusals += 1
                _EVICT_REFUSALS.increase()
                return
            victim = self._entries.pop(victim_key)
            self.evictions += 1
            _EVICTIONS.increase()
            self._bump_label(victim.label, "evictions")
            if self._eviction_counter:
                _monitor.stat(self._eviction_counter).increase()

    # ------------------------------------------------------------ pinning
    def pin(self, key) -> None:
        with self._lock:
            entry = self._entries.get(key)
            if entry is not None:
                entry.pins += 1

    def unpin(self, key) -> None:
        with self._lock:
            entry = self._entries.get(key)
            if entry is not None and entry.pins > 0:
                entry.pins -= 1

    # ----------------------------------------------------- dict-like view
    def __len__(self) -> int:
        return len(self._entries)

    def __contains__(self, key) -> bool:
        return key in self._entries

    def __iter__(self):
        return iter(list(self._entries))

    def keys(self):
        return list(self._entries)

    def values(self):
        """Jitted fns, LRU-ordered (oldest first) — what the HLO perf gates
        iterate to ``.lower()`` a cached program."""
        return [e.fn for e in self._entries.values()]

    def entries(self) -> List[ExecEntry]:
        return list(self._entries.values())

    def entry_for(self, key) -> Optional[ExecEntry]:
        """Peek without touching LRU order or hit counters."""
        return self._entries.get(key)

    def count(self, prefix: str) -> int:
        """Entries whose program id (key[0]) matches ``prefix`` exactly or
        as a dotted namespace."""
        pre = prefix.rstrip(".") + "."
        n = 0
        for k in list(self._entries):
            pid = k[0] if isinstance(k, tuple) and k else k
            if pid == prefix or (isinstance(pid, str) and pid.startswith(pre)):
                n += 1
        return n

    def discard(self, prefix: str) -> int:
        """Invalidate every entry under a program-id namespace (topology /
        health reconfiguration — NOT an eviction: no eviction counters)."""
        pre = prefix.rstrip(".") + "."
        with self._lock:
            doomed = []
            for k in list(self._entries):
                pid = k[0] if isinstance(k, tuple) and k else k
                if pid == prefix or (isinstance(pid, str)
                                     and pid.startswith(pre)):
                    doomed.append(k)
            for k in doomed:
                del self._entries[k]
            return len(doomed)

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()

    # -------------------------------------------------- signature stashing
    def stash(self, label: str, fn, call_args,
              donate: Tuple[int, ...] = (1, 2),
              aval_fn: Optional[Callable] = None,
              entry: Optional[ExecEntry] = None) -> None:
        """First call per label: remember (jitted fn, abstract args) so
        introspection can AOT-lower the same program later; auto-capture now
        when FLAGS_exec_introspect is on. ShapeDtypeStructs replace the
        arrays — no live (or donated) buffer is retained."""
        if label in self._stash:
            return
        self._donated[label] = tuple(donate)
        avals = abstract_args(call_args, aval_fn)
        self._stash[label] = (_LowersAsTraced(fn), avals)
        if entry is not None and entry.avals is None:
            entry.avals = avals
        if _flags.flag("exec_introspect"):
            try:
                from ..observability import exec_introspect as _obs_exec

                _obs_exec.capture_jit(label, fn, avals)
            except Exception:
                pass  # diagnostic path must never break the engine

    def stash_map(self) -> Dict[str, Tuple[Any, Any]]:
        return self._stash

    def donated_map(self) -> Dict[str, Tuple[int, ...]]:
        return self._donated

    def clear_stash(self) -> None:
        self._stash.clear()
        self._donated.clear()

    # --------------------------------------------------- compile telemetry
    def persistent_before(self, entry: ExecEntry) -> int:
        """Snapshot of the persistent cache's misses to classify the NEXT
        dispatch's compile, taken only when this entry has never compiled
        (-1 after)."""
        if entry._counted_once or entry._seen_cache_size > 0:
            return -1
        return _compile_cache.misses()

    def note_compiles(self, entry: ExecEntry,
                      n_before: Optional[int] = None,
                      n_after: Optional[int] = None,
                      wall_s: float = 0.0,
                      persistent_before: int = -1,
                      counter: Optional[str] = None,
                      engine_counters: bool = False) -> int:
        """Unified compile accounting, both legacy flavors:

        - serving flavor (``n_before`` omitted): compiles = growth of the
          entry's jit executable cache since last dispatch (one-per-wrapper
          when the cache size is not exposed); AOT-served dispatches count
          zero. ``counter`` names the legacy per-family monitor stat
          (serving.prefill_compiles, ...).
        - train flavor (``n_before``/``n_after`` given): one compile when
          the cache grew from a non-negative floor; ``engine_counters``
          additionally drives engine.jit_compiles / jit_recompiles /
          jit_compile_ms exactly like the old module-level helper.

        Either way a detected compile lands in exec.registry.compile_ms and
        is classified cold/warm through core.compile_cache when
        ``persistent_before`` >= 0. Returns the number of compiles counted."""
        if n_before is None:
            if entry._via_aot:
                return 0
            n = entry.cache_size()
            if n < 0:
                grew = 0 if entry._counted_once else 1
                entry._counted_once = True
            else:
                grew = max(0, n - entry._seen_cache_size)
                entry._seen_cache_size = n
            recompile = False
        else:
            grew = 1 if (n_after is not None and n_after > n_before
                         and n_before >= 0) else 0
            recompile = bool(grew and n_before > 0)
            if n_after is not None and n_after >= 0:
                entry._seen_cache_size = n_after
        if not grew:
            return 0
        wall_ms = wall_s * 1000.0
        if counter:
            _monitor.stat(counter).increase(grew)
        if engine_counters:
            _monitor.stat("engine.jit_compiles").increase()
            _monitor.stat("engine.jit_compile_ms").increase(int(wall_ms))
            if recompile:
                _monitor.stat("engine.jit_recompiles").increase()
        _COMPILE_MS.increase(int(wall_ms))
        self._compile_ms.append(wall_ms)
        kind = _compile_cache.note_compile(int(wall_ms), persistent_before,
                                           _compile_cache.misses())
        self._observe_compile(kind, wall_ms, entry.label)
        return grew

    def _observe_compile(self, kind: Optional[str], wall_ms: float,
                         label: str) -> None:
        # the call that compiled, as a span after the fact: the jit events
        # of the ring that lie in its interval are this entry's, all others
        # no registry's (`kind` is None with the persistent cache off)
        now = time.perf_counter()
        tr = _obs_tracer.get_tracer()
        tr.record_complete(
            _obs_tracer.FIRST_CALL, now - wall_ms / 1e3, now,
            {"label": label, "kind": kind},
            span_id=_obs_tracer.new_span_id(), parent=tr.current_span_id(),
            always=True)
        if kind == "cold":
            self._compile_cold_ms.append(wall_ms)
        elif kind == "warm":
            self._compile_warm_ms.append(wall_ms)
        reg = self._metrics_registry()
        if reg is not None:
            reg.histogram("exec.registry.compile_ms").observe(wall_ms)
            if kind:
                reg.histogram(
                    f"exec.registry.compile_{kind}_ms").observe(wall_ms)

    # ---------------------------------------------------------------- AOT
    def precompile(self, entry: ExecEntry, call_args,
                   aval_fn: Optional[Callable] = None) -> ExecEntry:
        """AOT-lower + compile ``entry.fn`` at the abstract signature of
        ``call_args`` and install the executable as the entry's dispatch
        fast path. Goes through the persistent store when configured (the
        warm-start bundle path), classifying cold/warm like any compile."""
        avals = abstract_args(call_args, aval_fn)
        entry.avals = avals
        p0 = _compile_cache.misses()
        t0 = time.perf_counter()
        entry.aot = entry.fn.lower(*avals).compile()
        wall_ms = (time.perf_counter() - t0) * 1000.0
        _AOT_COMPILES.increase()
        _COMPILE_MS.increase(int(wall_ms))
        self._compile_ms.append(wall_ms)
        kind = _compile_cache.note_compile(int(wall_ms), p0,
                                           _compile_cache.misses())
        self._observe_compile(kind, wall_ms, entry.label)
        if _flags.flag("exec_introspect"):
            try:
                from ..observability import exec_introspect as _obs_exec

                _obs_exec.capture(entry.label, entry.aot)
            except Exception:
                pass
        return entry

    # ------------------------------------------------------------- rollup
    def rollup(self) -> Dict[str, Any]:
        """Cumulative snapshot for trace sinks / trace_summary: registry
        totals, per-label hit/miss/eviction counts, and the cold/warm
        compile wall lists (milliseconds) for percentile tables."""
        with self._lock:
            return {
                "registry": self.name,
                "entries": len(self._entries),
                "hits": self.hits,
                "misses": self.misses,
                "evictions": self.evictions,
                "evict_refusals": self.evict_refusals,
                "aot_fallbacks": self.aot_fallbacks,
                "labels": {lbl: dict(st)
                           for lbl, st in sorted(self._label_stats.items())},
                "compile_ms": list(self._compile_ms),
                "compile_cold_ms": list(self._compile_cold_ms),
                "compile_warm_ms": list(self._compile_warm_ms),
            }
