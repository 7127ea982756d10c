"""Static-graph Executor: lower a Program to ONE jitted XLA computation.

Reference: Executor (paddle/fluid/framework/executor.h:57, executor.py:619 →
_run_impl:1301) and the new-executor InterpreterCore (interpretercore.cc:220 Convert,
:564 ExecuteInstructionList) with async workqueues, stream analysis, event-based deps
and scope GC.

TPU-native: the recorded OpDesc list is replayed once under jax tracing and compiled
by XLA — fusion, scheduling, memory reuse and overlap are the compiler's job, so the
whole interpreter machinery (workqueue, stream analyzer, per-op GC) collapses into a
jit cache keyed on (program version, feed shapes/dtypes, fetch names). When the
program carries a train spec (Optimizer.minimize ran under the program guard), the
lowered step also computes grads of the loss wrt captured Parameters with jax.grad and
applies the optimizer's functional rule — backward + optimizer "ops" are generated by
AD at lowering time rather than appended as IR (the append_backward analogue).
"""
from __future__ import annotations

from typing import Dict, List, Optional

import time

import jax
import jax.numpy as jnp
import numpy as np

from ..core import compile_cache as _compile_cache
from ..core import monitor as _monitor
from ..core.tensor import Tensor
from ..optimizer import functional as opt_funct
from .framework import Program, Variable, default_main_program

# whole-program compile observability, mirroring the eager engine's
# engine.jit_* counters: every Executor.run that grew a lowered program's
# executable cache is counted, its wall time accumulated, and (with the
# persistent compilation cache on) classified cold/warm
_EXEC_COMPILES = _monitor.stat("executor.jit_compiles")
_EXEC_COMPILE_MS = _monitor.stat("executor.jit_compile_ms")


def _jit_cache_size(fn) -> int:
    try:
        return fn._cache_size()
    except Exception:
        return -1


class Scope:
    """Name -> Tensor holder. Static parameters ARE the eager Parameter objects
    (captured by reference), so scope updates propagate to the dygraph view."""

    def __init__(self):
        self._vars: Dict[str, Tensor] = {}

    def var(self, name):
        return self._vars.setdefault(name, Tensor(jnp.zeros(())))

    def find_var(self, name):
        return self._vars.get(name)


_global_scope = Scope()


def global_scope() -> Scope:
    return _global_scope


def scope_guard(scope):
    import contextlib

    @contextlib.contextmanager
    def g():
        yield scope
    return g()


class _Lowered:
    __slots__ = ("fn", "param_names", "feed_names", "train", "compiled")

    def __init__(self, fn, param_names, feed_names, train):
        self.fn = fn
        self.param_names = param_names
        self.feed_names = feed_names
        self.train = train
        self.compiled = None  # AOT executable, filled by cost_analysis


class Executor:
    def __init__(self, place=None, donate: bool = True):
        self.place = place
        # donate=True (default): train programs alias params+opt state into
        # the update in place (one copy of training state in device memory).
        # The write-back into program._captures happens before control
        # returns to user code, so the donated (deleted) source buffers are
        # never observable; donate=False keeps them live for a debugger.
        self._donate = donate
        self._cache: Dict[tuple, _Lowered] = {}

    def _timed_call(self, fn, *operands):
        """Dispatch a lowered program, recording compile wall time (and the
        persistent-cache cold/warm split) when this call compiled."""
        n0 = _jit_cache_size(fn)
        p0 = _compile_cache.misses() if n0 == 0 else -1
        t0 = time.perf_counter()
        out = fn(*operands)
        if _jit_cache_size(fn) > n0 >= 0:
            ms = int((time.perf_counter() - t0) * 1000)
            _EXEC_COMPILES.increase()
            _EXEC_COMPILE_MS.increase(ms)
            _compile_cache.note_compile(ms, p0, _compile_cache.misses())
        return out

    # ---- public API (executor.py:1103 signature subset) ----
    def _prepare(self, program, feed, fetch_list):
        """Shared run()/cost_analysis() plumbing: resolve the program, lower
        (cache-keyed on program version + feed avals + fetch set), and
        assemble the call operands. Returns None for empty programs."""
        program = program or default_main_program()
        if isinstance(program, CompiledProgram):
            program = program._program
        feed = feed or {}
        fetch_list = fetch_list or []
        fetch_names = [v.name if isinstance(v, Variable) else str(v)
                       for v in fetch_list]

        if not program.global_block().ops:
            return None  # startup program: params are already initialized
            # eagerly at Layer construction

        feed_arrays = {}
        for name, val in feed.items():
            a = val._data if isinstance(val, Tensor) else jnp.asarray(np.asarray(val))
            feed_arrays[name] = a

        key = (id(program), program._version, bool(program._train),
               tuple(sorted((n, tuple(a.shape), str(a.dtype))
                            for n, a in feed_arrays.items())),
               tuple(fetch_names))
        low = self._cache.get(key)
        if low is None:
            low = self._lower(program, sorted(feed_arrays), fetch_names)
            self._cache[key] = low

        consts = {n: t._data for n, t in program._captures.items()
                  if n not in low.param_names}
        feeds = [feed_arrays[n] for n in low.feed_names]
        return program, low, consts, feeds

    def run(self, program: Optional[Program] = None, feed: Optional[dict] = None,
            fetch_list: Optional[List] = None, scope=None, return_numpy=True):
        prep = self._prepare(program, feed, fetch_list)
        if prep is None:
            return [] if fetch_list else None
        program, low, consts, feeds = prep

        if low.train:
            _, opt = program._train
            opt._step_count += 1
            params, opt_state, lr, step = self._train_operands(
                program, low, opt._step_count)
            fetches, new_params, new_opt = self._timed_call(
                low.fn, params, opt_state, consts, lr, step, *feeds)
            program._opt_state = new_opt
            for n in low.param_names:   # write updated weights back into the
                program._captures[n]._data = new_params[n]  # shared Parameter objects
        else:
            fetches = self._timed_call(low.fn, consts, *feeds)

        if return_numpy:
            return [np.asarray(f) for f in fetches]
        return [Tensor(f) for f in fetches]

    def _train_operands(self, program, low, step_i):
        """Build the positional operands of a train-program call. Optimizer
        state lives ON the program (no cross-program id-reuse); entries fill
        lazily so late-captured params get fresh state. Shared by run() and
        cost_analysis() so opt-state layout changes happen in one place."""
        params = {n: program._captures[n]._data for n in low.param_names}
        _, opt = program._train
        opt_state = {
            n: (program._opt_state[n] if n in program._opt_state
                else opt_funct.init_state(opt._rule, params[n]))
            for n in low.param_names}
        return params, opt_state, jnp.float32(opt.get_lr()), jnp.int32(step_i)

    def cost_analysis(self, program=None, feed=None, fetch_list=None):
        """XLA cost analysis of the jitted whole-program computation
        (flops / bytes accessed / transcendentals …) without executing it.
        The TPU-native replacement for the reference's CUPTI-based
        core.CostModel.ProfileMeasure (cost_model.py:58): the numbers come
        from the same compiler that schedules the program."""
        prep = self._prepare(program, feed, fetch_list)
        if prep is None:
            return {}
        program, low, consts, feeds = prep
        if not low.train and not fetch_list:
            # DCE roots an inference program at its fetches: with none, the
            # computation is empty and "zero flops" would be silently wrong
            raise ValueError(
                "cost_analysis of a non-train program needs fetch_list — "
                "without fetches the lowered computation has no outputs")
        if low.compiled is None:
            if low.train:
                params, opt_state, lr, step = self._train_operands(
                    program, low, 1)
                low.compiled = low.fn.lower(
                    params, opt_state, consts, lr, step, *feeds).compile()
            else:
                low.compiled = low.fn.lower(consts, *feeds).compile()
        from ..utils.hlo_inspect import cost_analysis_dict

        return dict(cost_analysis_dict(low.compiled))

    def close(self):
        self._cache.clear()

    # ---- trainer-stack entry points (reference executor.py train_from_dataset
    #      :2389 / infer_from_dataset :2285, backed by trainer.py #12) ----
    def train_from_dataset(self, program=None, dataset=None, scope=None,
                           thread=0, debug=False, fetch_list=None,
                           fetch_info=None, print_period=100, fetch_handler=None):
        from .trainer import TrainerFactory

        program = program or default_main_program()
        if isinstance(program, CompiledProgram):
            program = program._program
        if dataset is None:
            raise ValueError("train_from_dataset needs a dataset")
        is_dist = False
        try:
            from ..distributed import fleet
            rm = getattr(fleet.fleet, "_role_maker", None)
            is_dist = rm is not None and not getattr(rm, "_is_collective", True)
        except Exception:
            pass
        trainer = TrainerFactory.create(
            self, program, dataset, is_dist=is_dist, thread_num=thread or None,
            fetch_list=fetch_list, fetch_info=fetch_info,
            print_period=print_period, debug=debug)
        return trainer.run()

    def infer_from_dataset(self, program=None, dataset=None, scope=None,
                           thread=0, debug=False, fetch_list=None,
                           fetch_info=None, print_period=100, fetch_handler=None):
        """Same loop without parameter updates: programs carrying a train spec
        are run through a forward-only clone."""
        program = program or default_main_program()
        if isinstance(program, CompiledProgram):
            program = program._program
        if program._train is not None:
            program = program.clone(for_test=True)
        return self.train_from_dataset(
            program, dataset, scope=scope, thread=thread, debug=debug,
            fetch_list=fetch_list, fetch_info=fetch_info,
            print_period=print_period)

    # ---- lowering ----
    def run_debug(self, program: Optional[Program] = None, feed: Optional[dict] = None,
                  fetch_list: Optional[List] = None, check_nan_inf=False):
        """Per-op debug interpreter (the reference's classic Executor walk,
        framework/executor.cc — here a diagnostics mode: the production path
        compiles the whole program as one XLA computation). Executes eagerly
        op by op, records per-op wall time in `self.last_run_stats`, and can
        raise on the FIRST op producing NaN/Inf (nan_inf_utils_detail.cc:314
        behavior, better pinpointing than the fused run can give)."""
        import time as _time

        program = program or default_main_program()
        if isinstance(program, CompiledProgram):
            program = program._program
        fetch_names = [v.name if isinstance(v, Variable) else str(v)
                       for v in (fetch_list or [])]
        env = {n: t._data for n, t in program._captures.items()}
        for name, val in (feed or {}).items():
            env[name] = val._data if isinstance(val, Tensor) else \
                jnp.asarray(np.asarray(val))
        stats = []
        for op in program.global_block().ops:
            t0 = _time.perf_counter()
            ins = [env[n] for n in op.input_names]
            out = op.kernel(*ins)
            outs = list(out) if isinstance(out, (tuple, list)) else [out]
            for o in outs:
                if hasattr(o, "block_until_ready"):
                    o.block_until_ready()
            stats.append((op.type, _time.perf_counter() - t0))
            for n, o in zip(op.output_names, outs):
                env[n] = o
                if check_nan_inf and np.issubdtype(np.asarray(o).dtype, np.floating):
                    if not bool(jnp.isfinite(o).all()):
                        raise FloatingPointError(
                            f"op {op.type} output {n} contains NaN/Inf")
        self.last_run_stats = stats
        aliases = getattr(program, "_var_aliases", {})
        return [np.asarray(env[aliases.get(n, n)]) for n in fetch_names]

    # ---- lowering ----
    def _lower(self, program: Program, feed_names, fetch_names) -> _Lowered:
        from ..core.flags import flag as _flag
        from . import passes as _passes

        # passes run on a per-lowering VIEW: each (program, fetch set) pair
        # optimizes independently, the shared Program stays untouched (a DCE
        # for fetch set A must not delete ops a later fetch set B needs)
        view = _passes.ProgramView(program)
        if _flag("apply_ir_passes"):
            _passes.apply_default_passes(view, fetch_names)
        fetch_names = [view._var_aliases.get(n, n) for n in fetch_names]
        ops = list(view.global_block().ops)
        train = program._train is not None
        param_names = sorted(program.parameters()) if train else []

        def replay(env):
            for op in ops:
                ins = [env[n] for n in op.input_names]
                out = op.kernel(*ins)
                outs = list(out) if isinstance(out, (tuple, list)) else [out]
                for n, o in zip(op.output_names, outs):
                    env[n] = o
            return env

        if not train:
            def fwd(consts, *feeds):
                env = dict(consts)
                env.update(zip(feed_names, feeds))
                env = replay(env)
                return [env[n] for n in fetch_names]

            return _Lowered(jax.jit(fwd), param_names, feed_names, False)

        loss_name, opt = program._train
        rule_name = opt._rule
        hyper = dict(opt._hyper)
        if rule_name in ("sgd", "momentum", "adam", "adamw", "adamax", "adagrad",
                         "adadelta", "rmsprop"):
            hyper.setdefault("weight_decay", opt._weight_decay)
        rule = opt_funct.RULES[rule_name]
        needs_step = rule_name in opt_funct._NEEDS_STEP
        clip = opt._grad_clip

        def step(params, opt_state, consts, lr, step_i, *feeds):
            def loss_of(ps):
                env = dict(consts)
                env.update(ps)
                env.update(zip(feed_names, feeds))
                env = replay(env)
                return env[loss_name], env

            (loss, env), grads = jax.value_and_grad(loss_of, has_aux=True)(params)
            grads = opt_funct.clip_grads(grads, clip)
            new_params, new_opt = {}, {}
            for n, p in params.items():
                kw = dict(hyper)
                if needs_step:
                    kw["step"] = step_i
                new_params[n], new_opt[n] = rule(p, grads[n], opt_state[n], lr=lr, **kw)
            return [env[n] for n in fetch_names], new_params, new_opt

        return _Lowered(
            jax.jit(step, donate_argnums=(0, 1) if self._donate else ()),
            param_names, feed_names, True)


class CompiledProgram:
    """Parity shim: compilation happens in Executor.run's jit cache anyway."""

    def __init__(self, program, build_strategy=None):
        self._program = program if isinstance(program, Program) else program
        self.build_strategy = build_strategy

    def with_data_parallel(self, loss_name=None, build_strategy=None, places=None):
        return self


class BuildStrategy:
    def __init__(self):
        self.fuse_all_reduce_ops = True
        self.fuse_elewise_add_act_ops = True
        self.enable_inplace = True


class ExecutionStrategy:
    def __init__(self):
        self.num_threads = 1
        self.num_iteration_per_drop_scope = 100
