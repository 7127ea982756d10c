"""Op dispatch: the phi KernelFactory analogue.

Reference: every dygraph op goes Python -> generated python-C -> phi API -> KernelFactory::SelectKernel
(`paddle/phi/core/kernel_factory.h:260`) -> device kernel, while the tracer records a GradNode
(`paddle/fluid/imperative/tracer.cc:173`).

TPU-native: there is exactly one backend (XLA); a "kernel" is a jnp/lax/pallas function. `apply`
plays tracer + dispatcher: it unwraps Tensors, applies AMP autocast (the analogue of
`imperative/amp_auto_cast.cc`), runs the kernel (via `jax.vjp` when grads are needed so the grad
node is the vjp closure), optionally checks nan/inf (`FLAGS_check_nan_inf`,
`framework/details/nan_inf_utils_detail.cc:314`), and wires the autograd graph.

A registry records (name -> kernel) so tooling/tests can enumerate the op surface like
phi's KernelFactory::kernels() does.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Callable, Dict

import time

import jax
import jax.numpy as jnp
import numpy as np

from ..observability import tracer as _obs_tracer
from . import dtype as dtypes
from . import monitor as _monitor
from .autograd import Node, is_grad_enabled
from .flags import flag
from .tensor import Tensor

KERNELS: Dict[str, Callable] = {}

# dispatch-layer counters (core.monitor registry): per-op call counts are
# the KernelFactory-level observability the reference gets from its op
# profiler tables. StatValues are cached here so the hot path pays one dict
# lookup + one locked increment, not a registry lock per op.
_DISPATCH_CALLS = _monitor.stat("dispatch.calls")
_RULE_HITS = _monitor.stat("dispatch.rule_cache_hits")
_RULE_MISSES = _monitor.stat("dispatch.rule_cache_misses")
_NAN_INF_HITS = _monitor.stat("dispatch.nan_inf_hits")
_PER_OP_STATS: Dict[str, "_monitor.StatValue"] = {}


def _op_stat(name: str) -> "_monitor.StatValue":
    st = _PER_OP_STATS.get(name)
    if st is None:
        st = _PER_OP_STATS[name] = _monitor.stat("dispatch.op." + name)
    return st

# static-graph capture hook (installed by paddle_tpu.static.framework): when an op
# input is a symbolic Variable the op is recorded as an OpDesc, not executed
_symbolic_handler = None


def set_symbolic_handler(fn):
    global _symbolic_handler
    _symbolic_handler = fn

_amp_state = threading.local()

# AMP op lists: the analogue of the reference's black/white lists
# (python/paddle/fluid/dygraph/amp/auto_cast.py). On TPU the low dtype is bfloat16.
AMP_WHITE = {
    "matmul", "conv2d", "conv1d", "conv3d", "conv2d_transpose", "bmm", "mm",
    "einsum", "linear", "addmm", "mv", "attention",
}
AMP_BLACK = {
    "exp", "log", "log2", "log10", "log1p", "softmax", "log_softmax",
    "cross_entropy", "softmax_with_cross_entropy", "mean", "sum", "norm",
    "layer_norm", "layer_norm_pallas", "batch_norm", "group_norm",
    "instance_norm", "cumsum",
    "pow", "rsqrt", "sigmoid_cross_entropy_with_logits", "binary_cross_entropy",
    "nll_loss", "kl_div", "erf", "logsumexp", "var", "std",
}


class amp_guard:
    def __init__(self, enable=True, dtype="bfloat16", level="O1", custom_white_list=None,
                 custom_black_list=None):
        self.enable = enable
        self.dtype = dtypes.convert_dtype(dtype)
        self.level = level
        self.white = AMP_WHITE | set(custom_white_list or ())
        self.black = (AMP_BLACK - set(custom_white_list or ())) | set(custom_black_list or ())

    def __enter__(self):
        self._prev = getattr(_amp_state, "ctx", None)
        _amp_state.ctx = self if self.enable else None
        return self

    def __exit__(self, *exc):
        _amp_state.ctx = self._prev
        return False


def amp_ctx():
    return getattr(_amp_state, "ctx", None)


@contextlib.contextmanager
def amp_scope(ctx):
    """Install an existing autocast context (or None for none) for the
    duration — unlike re-entering the amp_guard itself, safe while that
    guard is still active further up the stack."""
    prev = amp_ctx()
    _amp_state.ctx = ctx
    try:
        yield
    finally:
        _amp_state.ctx = prev


def register_kernel(name: str):
    def deco(fn):
        KERNELS[name] = fn
        return fn

    return deco


def _is_float_array(x):
    return dtypes.is_floating(x.dtype)


def _is_inexact_array(x):
    """Differentiable dtypes: floats AND complex (fft ops). Autocast keeps using
    _is_float_array — complex must never be cast to bf16."""
    return dtypes.is_floating(x.dtype) or np.dtype(x.dtype).kind == "c"


def _autocast_dtype_for(name: str, arrays):
    ctx = amp_ctx()
    if ctx is None:
        return None
    if name.startswith("grad::"):
        # create_graph backward ops: the replayed bwd already embeds the
        # forward's own autocast; re-casting here would squeeze black-listed
        # ops' f32 backward through bf16
        return None
    if ctx.level == "O2":
        # pure low-precision except black list
        if name in ctx.black:
            return np.dtype(np.float32)
        return ctx.dtype
    if name in ctx.white:
        return ctx.dtype
    if name in ctx.black:
        return np.dtype(np.float32)
    return None


def _wrap_out(data, stop_gradient):
    return Tensor(data, stop_gradient=stop_gradient)


class _Unhashable(Exception):
    pass


def _freeze(v):
    """Hashable projection of closure/attr values; raises for anything whose
    change wouldn't be visible in the cache key (arrays, tracers, objects)."""
    if isinstance(v, (bool, int, float, str, bytes, type(None))):
        return v
    if isinstance(v, (list, tuple)):
        return tuple(_freeze(x) for x in v)
    if isinstance(v, np.dtype):
        return ("npdtype", str(v))
    if type(v).__module__ == "numpy" and np.isscalar(v):
        return ("npscalar", str(v.dtype), v.item())  # keep dtype in the key
    if isinstance(v, jax.Array) or isinstance(v, jax.core.Tracer):
        raise _Unhashable  # data-carrying: can never key a trace
    import types

    if isinstance(v, types.FunctionType):
        # function-valued closure cells (e.g. the jnp.power inside a binary
        # op's scalar fast path): key = code identity + recursively frozen
        # closure + defaults. Safe because the cached rule's jitted closure
        # PINS the code object, so its id cannot be recycled while the entry
        # exists (clear() drops entry + pin together); any array hiding in a
        # nested cell or default raises and disables caching.
        return ("fn", id(v.__code__),
                tuple(_freeze(c.cell_contents) for c in (v.__closure__ or ())),
                _freeze(v.__defaults__ or ()))
    if isinstance(v, types.BuiltinFunctionType) or type(v).__name__ == "ufunc":
        return ("builtin", id(v))  # stateless module-level callables
    import functools

    if isinstance(v, functools.partial):
        return ("partial", _freeze(v.func), _freeze(tuple(v.args)),
                tuple(sorted((k, _freeze(x)) for k, x in v.keywords.items())))
    mod = type(v).__module__ or ""
    if callable(v) and not hasattr(v, "__self__") and (
            mod.startswith("jax") or mod.startswith("numpy")):
        # jax/numpy callable objects (PjitFunction like jnp.tanh, jnp ufunc
        # wrappers): stateless, module-owned, pinned by the cached rule
        return ("jaxfn", id(v))
    raise _Unhashable


# (name, code id, closure values, attrs, arg signature, diff idx, cast) ->
# (jitted fwd over all args, jitted recompute-backward). The reference pays
# per-op dispatch via generated C fast paths (op_function_generator.h); here
# the analogue is jit-caching the per-op forward AND its vjp so steady-state
# dygraph ops skip Python retracing (FLAGS_eager_op_jit).
_RULE_CACHE: Dict[tuple, tuple] = {}
_RULE_CACHE_CAP = 4096
_UNSEEN = object()

# id(code) -> (code, cell content objects, frozen closure, defaults tuple,
# frozen defaults). The closure/defaults freeze is the recursive-walk cost of
# every dispatch; for stable kernels (module-level op functions — the steady
# state) the cell content objects are identity-stable across calls, so the
# frozen projection is reusable. Validity is checked by IDENTITY of every
# cell's content (and of the defaults tuple): a closure of the same code
# object over different values, or a nonlocal rebind, misses and re-freezes.
# Entries pin code + contents so ids cannot be recycled while cached; the
# memo is dropped with the rule cache (_clear_rule_cache).
_FREEZE_MEMO: Dict[int, tuple] = {}


# Fast-lane cache (FLAGS_eager_fast_path): key -> (rules, diff_idx,
# need_grad) resolved by ONE slow-path dispatch, or None for kernels proven
# value-dependent. The key deliberately omits the AMP cast (the lane only
# runs with AMP off) and the trace-time flags (any flag change clears this
# cache wholesale), so a steady-state hit pays: counter bump, memoized
# freeze lookup, signature tuple, one dict hit, jitted call — none of the
# per-call autocast resolution, nondiff dtype scans, closure building, or
# debug-flag probes of the general path. Entries share the rules objects
# with _RULE_CACHE; both are cleared together.
_FAST_CACHE: Dict[tuple, tuple] = {}
_FAST_CACHE_CAP = 8192
_FAST_HITS = _monitor.stat("dispatch.fast_hits")

# flag-derived globals, recomputed on any flag change: the hot path reads
# two module globals instead of probing the flag registry five times
_FAST_LANE_OK = True
_FUSION_ON = False


def _refresh_flag_globals():
    global _FAST_LANE_OK, _FUSION_ON
    _FAST_LANE_OK = (flag("eager_op_jit") and flag("eager_fast_path")
                     and not flag("check_nan_inf")
                     and not flag("enable_unused_var_check"))
    _FUSION_ON = bool(flag("eager_fusion"))


def _clear_rule_cache():
    _RULE_CACHE.clear()
    _FREEZE_MEMO.clear()
    _FAST_CACHE.clear()
    _fusion.clear_cache()


def _frozen_kernel_parts(kernel, code):
    """(frozen closure values, frozen defaults), memoized per code object.
    Raises _Unhashable (and memoizes nothing — an array/tracer cell must not
    be pinned) when the kernel cannot key a cache entry."""
    cells = getattr(kernel, "__closure__", None) or ()
    defaults = getattr(kernel, "__defaults__", None) or ()
    memo = _FREEZE_MEMO.get(id(code))
    if (memo is not None and len(memo[1]) == len(cells)
            and memo[3] is defaults
            and all(c.cell_contents is v for c, v in zip(cells, memo[1]))):
        return memo[2], memo[4]
    closure_vals = tuple(_freeze(c.cell_contents) for c in cells)
    frozen_defaults = _freeze(defaults)
    _FREEZE_MEMO[id(code)] = (
        code, tuple(c.cell_contents for c in cells), closure_vals, defaults,
        frozen_defaults)
    return closure_vals, frozen_defaults


def _rule_key(name, kernel, arrays, attrs, diff_idx, cast_to):
    code = getattr(kernel, "__code__", None)
    if code is None:
        return None  # pre-jitted / callable object: no stable identity to key on
    try:
        closure_vals, defaults = _frozen_kernel_parts(kernel, code)
        akey = tuple(sorted((k, _freeze(v)) for k, v in attrs.items()))
    except _Unhashable:
        return None
    sig = tuple((tuple(a.shape), str(a.dtype)) for a in arrays)
    # flags kernels read at trace time must be part of the key; autotune-state
    # changes instead CLEAR the cache via autotune.on_change (version-in-key
    # would orphan every op's rules on each new tuning)
    trace_flags = (flag("tpu_matmul_precision"), flag("use_flash_attention"),
                   flag("use_autotune"),
                   flag("pallas_interpret_ok"), flag("fused_ce_chunk"))
    return (name, id(code), closure_vals, defaults, akey, sig,
            tuple(diff_idx), str(cast_to), trace_flags)


def _has_float0(cts):
    leaves = cts if isinstance(cts, (tuple, list)) else (cts,)
    return any(getattr(c, "dtype", None) == jax.dtypes.float0 for c in leaves)


def _apply_cast(args, cast_to):
    """AMP cast shared by the cached and uncached dispatch paths."""
    if cast_to is None:
        return list(args)
    return [a.astype(cast_to) if _is_float_array(a) and a.dtype != cast_to else a
            for a in args]


def _build_rules(kernel, attrs, diff_idx, cast_to):
    def fwd(arrays_tuple):
        return kernel(*_apply_cast(arrays_tuple, cast_to), **attrs)

    def bwd(arrays_tuple, cts):
        def g(*diff_arrays):
            fa = list(arrays_tuple)
            for i, a in zip(diff_idx, diff_arrays):
                fa[i] = a
            return kernel(*_apply_cast(fa, cast_to), **attrs)

        _, vjp_fn = jax.vjp(g, *[arrays_tuple[i] for i in diff_idx])
        return vjp_fn(cts)

    # backward recomputes the forward from saved inputs inside one XLA program:
    # for linear ops XLA DCEs the recompute entirely (residuals are the
    # inputs); elementwise recompute is cheaper than a Python retrace per call
    return jax.jit(fwd), jax.jit(bwd)


def _finish_outputs(name, out_data, need_grad, vjp_fn, bwd_spec, tensor_args,
                    diff_idx):
    """Wrap kernel outputs as Tensors and wire the autograd node — the
    shared tail of the fast lane and the general dispatch path."""
    multi = isinstance(out_data, (tuple, list))
    outs_data = list(out_data) if multi else [out_data]
    outs = [_wrap_out(d, stop_gradient=not need_grad) for d in outs_data]
    if vjp_fn is not None:
        node = Node(
            vjp_fn,
            [tensor_args[i] for i in diff_idx],
            [(tuple(d.shape), np.dtype(d.dtype)) for d in outs_data],
            name=name,
            bwd_spec=bwd_spec,
        )
        for i, o in enumerate(outs):
            o._node = node
            o._out_index = i
    if multi:
        return tuple(outs)
    return outs[0]


def _fast_apply(name, kernel, tensor_args, attrs, nondiff_mask, differentiable,
                may_fuse):
    """Fast lane: returns (True, result) on a cache hit, (False, fast_key)
    when the general path should run and then populate the lane, and
    (False, None) when the call is ineligible. Preconditions (checked by the
    caller): FLAGS_eager_fast_path lane open, no AMP context, no symbolic
    inputs."""
    code = getattr(kernel, "__code__", None)
    if code is None:
        return False, None
    try:
        closure_vals, defaults = _frozen_kernel_parts(kernel, code)
        akey = (tuple(sorted((k, _freeze(v)) for k, v in attrs.items()))
                if attrs else ())
    except _Unhashable:
        return False, None
    ge = is_grad_enabled()
    sg = tuple(t._stop_gradient for t in tensor_args)
    if may_fuse and differentiable and (not ge or all(sg)):
        out = _fusion.try_fuse(name, kernel, tensor_args, attrs,
                               closure_vals, defaults, akey)
        if out is not None:
            return True, out
    arrays = [t._data for t in tensor_args]
    try:
        sig = tuple((a.shape, a.dtype) for a in arrays)
    except AttributeError:
        return False, None
    key = (name, id(code), closure_vals, defaults, akey, sig,
           None if nondiff_mask is None else tuple(nondiff_mask),
           differentiable, ge, sg)
    entry = _FAST_CACHE.get(key, _UNSEEN)
    if entry is _UNSEEN:
        return False, key  # one general dispatch resolves + stores the entry
    if entry is None:
        return False, None  # proven value-dependent: always runs eagerly
    rules, diff_idx, need_grad = entry
    arrays_tuple = tuple(arrays)
    out_data = rules[0](arrays_tuple)
    _FAST_HITS.increase()
    vjp_fn = bwd_spec = None
    if need_grad and diff_idx:
        bwd = rules[1]
        diff_set = set(diff_idx)
        bwd_spec = (bwd, tuple(
            t if i in diff_set else t.detach()
            for i, t in enumerate(tensor_args)))

        def vjp_fn(cts, _bwd=bwd, _at=arrays_tuple):
            if _has_float0(cts):
                # float0 cotangents can't enter the jitted backward — take
                # the uncached vjp for this rare call (mirrors the general
                # path's fallback)
                def g(*diff_arrays):
                    full = list(_at)
                    for i, a in zip(diff_idx, diff_arrays):
                        full[i] = a
                    return kernel(*full, **attrs)

                _, vf = jax.vjp(g, *[_at[i] for i in diff_idx])
                return vf(cts)
            return _bwd(_at, cts)

    return True, _finish_outputs(name, out_data, need_grad, vjp_fn, bwd_spec,
                                 tensor_args, diff_idx)


def apply(name: str, kernel: Callable, tensor_args, attrs=None, nondiff_mask=None,
          differentiable: bool = True):
    """Run `kernel(*arrays, **attrs)` with autograd recording.

    tensor_args: sequence of Tensors (already converted by the op wrapper).
    nondiff_mask: optional bools marking args that can never receive grad
      (e.g. integer index tensors) — they are closed over, not vjp-ed.
    differentiable=False: never record (comparisons, int-valued ops).
    """
    attrs = attrs or {}
    if _symbolic_handler is not None and any(
            getattr(t, "is_symbolic", False) for t in tensor_args):
        return _symbolic_handler(name, kernel, tensor_args, attrs, differentiable)
    _DISPATCH_CALLS.increase()
    _op_stat(name).increase()
    _tr = _obs_tracer.get_tracer()
    _span_t0 = time.perf_counter() if _tr.enabled else None

    fast_key = None
    if _FAST_LANE_OK and getattr(_amp_state, "ctx", None) is None:
        # fusion is skipped while a trace window is open so per-op spans
        # keep measuring real executions
        hit, val = _fast_apply(name, kernel, tensor_args, attrs, nondiff_mask,
                               differentiable,
                               may_fuse=_FUSION_ON and _span_t0 is None)
        if hit:
            if _span_t0 is not None:
                _tr.record_complete("op::" + name, _span_t0,
                                    time.perf_counter(), aggregate=False)
            return val
        fast_key = val
    arrays = [t._data for t in tensor_args]

    cast_to = _autocast_dtype_for(name, arrays)

    if nondiff_mask is None:
        nondiff_mask = [not _is_inexact_array(a) for a in arrays]

    diff_idx = [i for i, nd in enumerate(nondiff_mask) if not nd]
    aux_idx = [i for i, nd in enumerate(nondiff_mask) if nd]

    def f(*diff_arrays):
        full = list(arrays)
        for i, a in zip(diff_idx, diff_arrays):
            full[i] = a
        return kernel(*_apply_cast(full, cast_to), **attrs)

    diff_arrays = [arrays[i] for i in diff_idx]

    need_grad = (
        differentiable
        and is_grad_enabled()
        and any(not tensor_args[i].stop_gradient for i in diff_idx)
    )

    rules = None
    key = None
    bwd_spec = None
    if flag("eager_op_jit"):
        key = _rule_key(name, kernel, arrays, attrs, diff_idx, cast_to)
        if key is not None:
            rules = _RULE_CACHE.get(key, _UNSEEN)
            if rules is _UNSEEN:
                _RULE_MISSES.increase()
                if len(_RULE_CACHE) >= _RULE_CACHE_CAP:
                    _clear_rule_cache()
                rules = _build_rules(kernel, attrs, diff_idx, cast_to)
                _RULE_CACHE[key] = rules
            else:
                _RULE_HITS.increase()
            # rules may be None: key previously proved untraceable

    if rules is not None:
        arrays_tuple = tuple(arrays)
        try:
            out_data = rules[0](arrays_tuple)
        except jax.errors.ConcretizationTypeError:
            # value-dependent kernel (shapes depend on array values, e.g.
            # segment ops sizing by max(ids)): permanently uncacheable — run
            # eagerly like the reference's non-jittable CPU ops
            _RULE_CACHE[key] = None
            rules = None
        else:
            if need_grad and diff_idx:
                bwd = rules[1]
                # pure bwd: double-grad-able. Nondiff inputs are stored
                # DETACHED — their value feeds the recompute but their own
                # upstream graphs (e.g. the argmax producing index inputs)
                # must not be pinned for the lifetime of this node.
                diff_set = set(diff_idx)
                bwd_spec = (bwd, tuple(
                    t if i in diff_set else t.detach()
                    for i, t in enumerate(tensor_args)))

                def vjp_fn(cts, _bwd=bwd, _at=arrays_tuple):
                    if _has_float0(cts):
                        # float0 cotangents (int outputs of multi-output ops
                        # like topk) are not valid jit arguments — take the
                        # uncached vjp for this rare call
                        _, vf = jax.vjp(f, *diff_arrays)
                        return vf(cts)
                    return _bwd(_at, cts)
            else:
                vjp_fn = None
    if rules is None:
        if need_grad and diff_idx:
            out_data, vjp_fn = jax.vjp(f, *diff_arrays)
        else:
            out_data = f(*diff_arrays)
            vjp_fn = None

    if fast_key is not None:
        # this call ran under fast-lane preconditions: publish the resolved
        # entry so identical later calls skip straight to the cached rules
        # (None marks kernels proven uncacheable — they stay on this path)
        if len(_FAST_CACHE) >= _FAST_CACHE_CAP:
            _FAST_CACHE.clear()
        _FAST_CACHE[fast_key] = (None if rules is None
                                 else (rules, tuple(diff_idx), need_grad))

    if flag("check_nan_inf"):
        _check_nan_inf(name, list(out_data)
                       if isinstance(out_data, (tuple, list)) else [out_data])
    if flag("enable_unused_var_check"):
        _check_unused_vars(name, f, diff_arrays)

    res = _finish_outputs(name, out_data, need_grad, vjp_fn, bwd_spec,
                          tensor_args, diff_idx)
    if _span_t0 is not None:
        _tr.record_complete("op::" + name, _span_t0, time.perf_counter(),
                            aggregate=False)
    return res


_unused_var_warned = set()


def _check_unused_vars(name, f, diff_arrays):
    """FLAGS_enable_unused_var_check analogue (reference
    framework/unused_var_check.cc): flag ops that declare inputs their compute
    never reads. XLA-native check: trace the kernel to a jaxpr and look for
    input vars that appear in no equation — dead operands mean a wrong op
    signature or a silently dropped tensor."""
    if name in _unused_var_warned:
        return
    _unused_var_warned.add(name)
    try:
        jaxpr = jax.make_jaxpr(f)(*diff_arrays)
    except Exception:
        return  # kernels with data-dependent python control flow can't trace here
    from jax.extend.core import Literal

    used = set()
    for eqn in jaxpr.jaxpr.eqns:
        used.update(id(v) for v in eqn.invars if not isinstance(v, Literal))
    used.update(id(v) for v in jaxpr.jaxpr.outvars if not isinstance(v, Literal))
    unused = [i for i, v in enumerate(jaxpr.jaxpr.invars) if id(v) not in used]
    if unused:
        import warnings

        warnings.warn(
            f"Operator {name} declares {len(jaxpr.jaxpr.invars)} differentiable "
            f"inputs but never reads input(s) {unused} "
            f"(FLAGS_enable_unused_var_check)", stacklevel=3)


def _check_nan_inf(name, outs_data):
    for d in outs_data:
        if _is_float_array(d):
            if not bool(jnp.isfinite(d).all()):
                _NAN_INF_HITS.increase()
                # failure branch only: tee a post-mortem dump when the
                # flight recorder is enabled (no-op/no import cost otherwise)
                from ..observability import flight_recorder as _flight

                _flight.on_nan_inf(f"op_{name}")
                raise FloatingPointError(
                    f"Operator {name} output contains Inf/Nan "
                    f"(FLAGS_check_nan_inf is set)"
                )


def as_tensor(x, dtype=None):
    """Coerce op operands: Tensor passthrough, scalars/arrays wrapped."""
    if isinstance(x, Tensor):
        return x.astype(dtype) if dtype is not None and x.dtype != dtypes.convert_dtype(dtype) else x
    if isinstance(x, (bool, int, float, complex)):
        # weak-typed scalar: let jnp promote like the reference's scalar attrs do
        return Tensor(jnp.asarray(x), stop_gradient=True)
    if isinstance(x, jax.Array) or isinstance(x, jax.core.Tracer):
        # raw jax value (tracer from lax.cond/while_loop bodies, or a user's
        # jnp array): wrap without forcing a host materialization
        return Tensor(x, stop_gradient=True)
    if dtype is not None:
        return Tensor(jnp.array(x, dtypes.convert_dtype(dtype)), stop_gradient=True)
    a = np.asarray(x)
    if a.dtype == np.float64:
        a = a.astype(dtypes.get_default_dtype())
    return Tensor(jnp.array(a), stop_gradient=True)


# no import cycle: eager_fusion depends only on tensor/dtype/monitor — the
# frozen kernel parts it needs arrive as arguments from the fast lane
from . import eager_fusion as _fusion  # noqa: E402

# autotune-state changes invalidate cached rules (flash attention bakes the
# tuned block choice into its trace)
from . import autotune as _autotune  # noqa: E402

_autotune.on_change(_clear_rule_cache)

# flags listed in the cache key are safe; any OTHER flag change conservatively
# clears the cache, so a future kernel reading a new flag at trace time can
# never be served a stale trace (ADVICE r1)
_TRACE_KEY_FLAGS = frozenset({"tpu_matmul_precision", "use_flash_attention",
                              "use_autotune",
                              "pallas_interpret_ok", "fused_ce_chunk"})


def _on_flag_change(name):
    # the fast lane's key carries no trace-time flags at all — ANY flag
    # change drops it (and the fused-chain cache) wholesale
    _FAST_CACHE.clear()
    _fusion.clear_cache()
    _refresh_flag_globals()
    if name not in _TRACE_KEY_FLAGS:
        _clear_rule_cache()


from . import flags as _flags  # noqa: E402

_flags.on_change(_on_flag_change)
_refresh_flag_globals()
