"""Distributed train-step engine: the pjit execution path.

This is the TPU-native replacement for the reference's whole static-graph distributed
machinery (meta-optimizers rewriting programs + InterpreterCore + NCCL rings, SURVEY.md §3.4):
the forward, backward, grad sync, clip, and optimizer update become ONE jitted XLA program
over the hcg mesh. Parallelism is expressed as shardings:

- dp / sharding(ZeRO data axis): batch dims sharded; XLA turns the mean-loss grad into an
  allreduce (the Reducer/fuse_all_reduce_ops analogue — one fused collective per step).
- mp (tensor parallel): parameters carry PartitionSpec dist_attrs from the mp_layers;
  GSPMD inserts the c_identity/c_allreduce/c_concat collectives the reference codes by hand.
- sharding stage1/2 (ZeRO-1/2): optimizer states sharded over the sharding axis — the
  weight update runs 1/N-sized per device and XLA all-gathers updated params
  (= reference GroupShardedOptimizerStage2, group_sharded_optimizer_stage2.py:48).
- sp: sequence dims of activations sharded; attention gathers as needed.
- parameters are donated: the update is in-place in HBM (buffer donation ≙ the
  reference's in-place optimizer ops).
"""
from __future__ import annotations

import math
import time
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core import compile_cache as _compile_cache
from ..core import flags as _flags
from ..core import monitor as _monitor
from ..core.exec_registry import ExecutableRegistry
from ..core import random as random_mod
from ..core.tensor import Tensor
from ..jit import functional_call
from ..observability import exec_introspect as _obs_exec
from ..observability import exporter as _obs_exporter
from ..observability import flight_recorder as _obs_flight
from ..observability import health as _obs_health
from ..observability import metrics as _obs_metrics
from ..observability import tracer as _obs_tracer
from ..observability.step_telemetry import StepTelemetry
from ..optimizer import functional as opt_funct
from . import elastic as _elastic
from . import grad_comm as _gc
from . import prefetcher as _pf
from .mesh import HybridCommunicateGroup, get_hybrid_communicate_group

# jit-path observability (core.monitor registry): every compile of a step
# program is counted (engine.jit_compiles / jit_recompiles / jit_compile_ms,
# now driven through ExecutableRegistry.note_compiles with
# engine_counters=True); a compile on a step function that ALREADY had an
# executable is a recompile — the shape/dtype-churn alarm the reference
# surfaces via its cache-miss logs.
_NAN_LOSS_STEPS = _monitor.stat("engine.nan_loss_steps")


def _jit_cache_size(fn) -> int:
    try:
        return fn._cache_size()
    except Exception:
        return -1


def _divides(n, d):
    return d > 0 and n % d == 0


def model_input_count(n_batch_args, num_model_inputs=None):
    """How many leading batch args feed the model when a loss_fn is present
    (the rest are labels for loss_fn). Shared by TrainStepEngine and
    auto_parallel.Engine so the convention cannot drift: default is
    all-but-last (min 1); num_model_inputs overrides for e.g. multi-input
    self-supervised models.

    BREAKING (round 1 -> 2, ADVICE r1): previously the model received EVERY
    batch arg and loss_fn only the outputs; now the last arg is the label and
    loss_fn receives (outputs..., labels). Callers on the old convention must
    pass num_model_inputs=n_batch_args."""
    if num_model_inputs is not None:
        if not 1 <= num_model_inputs <= n_batch_args:
            raise ValueError(
                f"num_model_inputs={num_model_inputs} out of range for "
                f"{n_batch_args} batch args")
        return num_model_inputs
    return max(1, n_batch_args - 1)


def _param_spec(p, shape, hcg) -> P:
    """The param's declared PartitionSpec with every mesh axis of degree 1
    dropped. Sharding over a one-device axis is no sharding, and the
    pure-data-parallel paths (deferred reduce, ZeRO, FSDP) recognise a
    replicated param by its spec: a GPT built from the mp layers names 'mp'
    on every matmul weight and would otherwise never reach them at mp=1."""
    if getattr(p, "dist_attr", None) is None:
        return P()

    def live(entry):
        names = entry if isinstance(entry, tuple) else (entry,)
        names = tuple(a for a in names
                      if a is not None and hcg.degrees.get(a, 2) > 1)
        return names[0] if len(names) == 1 else (names or None)

    entries = [live(e) for e in tuple(p.dist_attr)]
    return P(*entries) if any(e is not None for e in entries) else P()


def _opt_state_spec(param_spec: P, shape, hcg, use_sharding: bool) -> P:
    """Shard optimizer state over the 'sharding' axis in the first divisible unsharded
    dim (ZeRO-1 weight-update sharding, arXiv:2004.13336 style)."""
    entries = list(param_spec) + [None] * (len(shape) - len(param_spec))
    if not use_sharding:
        return P(*entries) if any(e is not None for e in entries) else P()
    deg = hcg.degrees["sharding"]
    if deg <= 1:
        return P(*entries) if any(e is not None for e in entries) else P()
    for i, (e, s) in enumerate(zip(entries, shape)):
        if e is None and _divides(s, deg):
            entries[i] = "sharding"
            break
    return P(*entries)


def _default_input_spec(shape, hcg) -> P:
    batch_axes = tuple(a for a in ("dp", "sharding") if hcg.degrees[a] > 1)
    first = batch_axes if len(batch_axes) > 1 else (batch_axes[0] if batch_axes else None)
    entries = [first]
    if len(shape) >= 2 and hcg.degrees["sp"] > 1 and _divides(shape[1], hcg.degrees["sp"]):
        entries.append("sp")
    return P(*entries)


class TrainStepEngine:
    """Fused distributed train step.

    model: an nn.Layer whose forward returns the scalar loss given the batch.
           Alternatively pass loss_fn: with >= 2 batch args the model consumes
           all but the last and loss_fn(model_outputs..., labels) combines
           them (auto_parallel.Engine convention); with a single batch arg the
           model consumes it and loss_fn(model_outputs...) is self-supervised.
    optimizer: a paddle_tpu.optimizer.Optimizer (its functional rule is reused).
    """

    @_obs_tracer.in_boundary("engine.init")
    def __init__(self, model, optimizer, loss_fn: Optional[Callable] = None,
                 hcg: Optional[HybridCommunicateGroup] = None, strategy=None,
                 input_specs: Optional[List[P]] = None, donate: bool = True,
                 num_model_inputs: Optional[int] = None,
                 microbatches: int = 1, zero_update: bool = False,
                 fsdp: bool = False):
        self.model = model
        self.optimizer = optimizer
        self.loss_fn = loss_fn
        self.num_model_inputs = num_model_inputs
        self.hcg = hcg or get_hybrid_communicate_group() or HybridCommunicateGroup()
        self.mesh: Mesh = self.hcg.mesh
        self.strategy = strategy
        self.input_specs = input_specs
        self._donate = donate
        use_sharding = bool(strategy and getattr(strategy, "sharding", False)) or \
            self.hcg.degrees["sharding"] > 1

        state = model.state_dict(include_non_persistable_buffer=True)
        self._param_names = [n for n, t in state.items() if not t.stop_gradient]
        self._buffer_names = [n for n, t in state.items() if t.stop_gradient]
        self._state_refs = state

        # build sharded global arrays for params + opt state
        self.param_specs = {}
        self.params = {}
        for n in self._param_names:
            p = state[n]
            spec = _param_spec(p, p.shape, self.hcg)
            self.param_specs[n] = spec
            self.params[n] = jax.device_put(p._data, NamedSharding(self.mesh, spec))
        self.buffers = {n: state[n]._data for n in self._buffer_names}

        rule = optimizer._rule
        # offload (GroupShardedOptimizerStage2(offload=True), reference
        # group_sharded_optimizer_stage2.py:48): optimizer state lives in host
        # memory between steps — XLA streams it to HBM for the update and back,
        # freeing per-device HBM at the cost of host<->device traffic.
        # (pinned_host: the TPU v5e and the CPU client both expose it)
        self._opt_memory_kind = ("pinned_host"
                                 if getattr(optimizer, "_offload", False) else None)
        self.opt_specs = {}
        self.opt_state = {}
        for n in self._param_names:
            st = opt_funct.init_state(rule, self.params[n])
            spec = _opt_state_spec(self.param_specs[n], state[n].shape, self.hcg,
                                   use_sharding)
            self.opt_specs[n] = spec
            self.opt_state[n] = tuple(
                jax.device_put(s, self._opt_sharding(spec)) for s in st)

        # ONE keyed ExecutableRegistry replaces the step/accum/scan fn
        # caches (keys ("train.step",), ("train.accum",)+config,
        # ("train.run_steps", fixed)); unbounded — the train working set is
        # a handful of pinned executables per topology. The legacy
        # attribute views (_step_fn, _accum_fns, _exec_stash) stay as
        # properties over it.
        self._execs = ExecutableRegistry(name="train")
        # microbatch gradient accumulation (distributed/grad_comm.py): K
        # splits the global batch inside ONE compiled program — one dispatch
        # and one deferred fused gradient all-reduce per optimizer step.
        # Mutable until the first accumulated step; fns cached per config.
        self.microbatches = max(1, int(microbatches))
        self._grad_residual = None     # error-feedback state, lazily built
        self._gspmd_warned = False
        # ZeRO weight-update sharding (grad_comm.make_zero_accum_step):
        # requested per-engine or via FLAGS_zero_update; the optimizer state
        # converts one-way into flat f32 1/N shards on the first sharded
        # step (self.opt_state becomes None; _gather_zero_opt reconstructs)
        self.zero_update = bool(zero_update)
        self._zero_opt = None          # tuple of flat [n_pad] f32 slot shards
        self._zero_warned = False
        self._zero_reason = "unset"    # cached fallback reason (None = ok)
        # Full FSDP (grad_comm.make_fsdp_accum_step): params AND opt state
        # live only as per-layer flat f32 1/N shards between steps after the
        # first sharded step (self.params/self.opt_state become None;
        # _gather_fsdp_params/_gather_fsdp_opt reconstruct the dict forms).
        # Same eligibility gate as zero_update; supersedes it when both set.
        self.fsdp = bool(fsdp)
        self._fsdp_params = None       # tuple of per-bucket [pad] f32 shards
        self._fsdp_opt = None          # tuple (per slot) of per-bucket shards
        self._fsdp_warned = False
        self._fsdp_cache = None        # (nrep, chunk) -> bucket layout
        self._param_dtypes = None      # captured at fsdp engagement
        self._batch_shardings = None   # resolved lazily from the first batch
        self._pending_h2d = None       # (h2d_ms, depth) staged by prefetch()
        self.prefetcher = None         # last DevicePrefetcher built by prefetch()
        self._scan_batch_shardings = {}  # fixed_batch -> shardings
        self._step_count = optimizer._step_count
        self._key = jax.random.key(random_mod.default_generator().initial_seed() or 0)
        self.last_loss = None
        self._lr_cache = (None, None)  # (python value, device scalar)
        # PADDLE_TPU_TELEMETRY_DIR auto-attaches a JSONL sink; otherwise
        # telemetry stays None and the step path pays nothing for it
        self.telemetry = StepTelemetry.from_env()
        if self.telemetry is not None and self.telemetry.flops_per_token is None:
            self.telemetry.flops_per_token = 6 * self._n_params()
        # PADDLE_TPU_METRICS_PORT / PADDLE_TPU_FLIGHT_DIR opt-ins: one
        # getenv each when unset, zero per-step cost while off
        _obs_exporter.ensure_started_from_env()
        _obs_flight.ensure_from_env()
        # FLAGS_health_monitor / PADDLE_TPU_HEALTH_DIR: in-program training
        # health stats as an aux output of the compiled step. None (the
        # default) keeps the step program byte-identical to pre-health builds
        self._health = _obs_health.from_env_or_flags(
            {n: tuple(self._state_refs[n].shape) for n in self._param_names})
        # FLAGS_ckpt_dir / PADDLE_TPU_CKPT_DIR: elastic checkpointing
        # (distributed/elastic.py) — async crash-safe snapshots every
        # FLAGS_ckpt_interval steps. None (the default) costs one flag read
        # here and one None-check per step
        self._ckpt = _elastic.from_flags()

    def _n_params(self) -> int:
        return int(sum(
            int(np.prod(self._state_refs[n].shape) or 1)
            for n in self._param_names))

    def enable_telemetry(self, sink=None, path=None,
                         flops_per_token: Optional[int] = None,
                         peak_flops: Optional[float] = None,
                         collect_live_buffers: bool = False) -> StepTelemetry:
        """Attach per-step telemetry. Default flop model is parameter-only
        (6*N per token); pass flops_per_token from
        observability.transformer_flops_per_token for the full bench.py
        accounting with the attention term. collect_live_buffers=True adds
        a per-record live-array census + high-water — the donation proof on
        backends where PJRT exposes no memory stats."""
        from ..observability.step_telemetry import JsonlSink

        if sink is None and path is not None:
            sink = JsonlSink(path)
        self.telemetry = StepTelemetry(
            sink=sink,
            flops_per_token=(flops_per_token if flops_per_token is not None
                             else 6 * self._n_params()),
            peak_flops=peak_flops,
            collect_live_buffers=collect_live_buffers)
        return self.telemetry

    def disable_telemetry(self) -> None:
        if self.telemetry is not None:
            self.telemetry.close()
        self.telemetry = None

    # ---- training-health telemetry (observability/health.py) ----
    def enable_health(self, interval: Optional[int] = None,
                      spike_factor: Optional[float] = None, sink=None,
                      path: Optional[str] = None, ring_capacity: int = 64):
        """Attach the in-program TrainingHealthMonitor: grad/weight/update
        norms + non-finite localization computed as an aux output of the
        SAME compiled step (zero extra dispatches), fetched to host every
        `interval` steps as ONE packed f32 [4P] transfer. Invalidates the
        cached step executables (the program's output arity changes)."""
        from ..observability.step_telemetry import JsonlSink

        if sink is None and path is not None:
            sink = JsonlSink(path)
        self._health = _obs_health.TrainingHealthMonitor(
            {n: tuple(self._state_refs[n].shape) for n in self._param_names},
            interval=interval, spike_factor=spike_factor, sink=sink,
            ring_capacity=ring_capacity)
        self._invalidate_step_fns()
        return self._health

    def disable_health(self) -> None:
        if self._health is not None:
            self._health.close()
        self._health = None
        self._invalidate_step_fns()

    # ---- elastic checkpointing (distributed/elastic.py) ----
    def enable_checkpointing(self, dirname: str, interval: Optional[int] = None,
                             keep: Optional[int] = None,
                             async_save: Optional[bool] = None,
                             rollback_on_nonfinite: Optional[bool] = None,
                             resume: bool = False):
        """Attach a CheckpointManager: async crash-safe snapshots of
        params / optimizer state (including ZeRO flat shards) / RNG / step
        every `interval` optimizer steps, committed by atomic rename with
        checksummed manifests, newest `keep` retained. ``resume=True``
        restores the newest valid checkpoint from `dirname` right now (a
        preempted job's restart line), silently starting fresh when the
        directory holds none. Unset kwargs fall back to the FLAGS_ckpt_*
        defaults. Does NOT touch the compiled step (the snapshot is pure
        host-side capture), so no recompile."""
        if self._ckpt is not None:
            self._ckpt.close()
        self._ckpt = _elastic.CheckpointManager(
            dirname,
            interval=(_flags.flag("ckpt_interval") if interval is None
                      else interval),
            keep=_flags.flag("ckpt_keep") if keep is None else keep,
            async_save=(_flags.flag("ckpt_async") if async_save is None
                        else async_save),
            rollback_on_nonfinite=(
                _flags.flag("ckpt_rollback") if rollback_on_nonfinite is None
                else rollback_on_nonfinite))
        if resume:
            try:
                self._ckpt.restore(self)
            except FileNotFoundError:
                pass  # nothing saved yet: a fresh run, not an error
        return self._ckpt

    def disable_checkpointing(self) -> None:
        if self._ckpt is not None:
            self._ckpt.close()
        self._ckpt = None

    # ---- legacy executable-cache views over the ExecutableRegistry ------
    @property
    def _step_fn(self):
        entry = self._execs.entry_for(("train.step",))
        return entry.fn if entry is not None else None

    @_step_fn.setter
    def _step_fn(self, fn) -> None:
        if fn is None:
            self._execs.discard("train.step")
        else:
            self._execs.put(("train.step",), fn, label="train.step",
                            pin=True)

    @property
    def _accum_fns(self):
        """Legacy view: {(k, dtype, use_residual, chunk, health_on, zero):
        fn} — the config tuple is the registry key minus its program id."""
        return {e.key[1:]: e.fn for e in self._execs.entries()
                if e.key[0] == "train.accum"}

    @property
    def _exec_stash(self):
        """label -> (jitted fn, abstract args), owned by the registry."""
        return self._execs.stash_map()

    def exec_registry(self) -> ExecutableRegistry:
        """This engine's ExecutableRegistry (step/accum/scan executables)."""
        return self._execs

    def _invalidate_step_fns(self) -> None:
        """Drop cached step executables + their introspection stash — the
        next step() recompiles with the new output signature."""
        self._execs.discard("train.step")
        self._execs.discard("train.accum")
        self._execs.clear_stash()

    def reform_mesh(self, new_hcg: HybridCommunicateGroup) -> None:
        """Live in-memory mesh reformation (elastic autoscaling).

        Re-forms this engine onto ``new_hcg``'s mesh without a disk bounce:
        params and optimizer state are host-gathered from the old mesh
        (flat ZeRO slot shards at their true ``[:n]`` prefix — the same
        segment_layout-ordered vector elastic.py's checkpoint reslice
        uses), every device placement is rebuilt against the new topology,
        and only then does the engine commit. Any failure before the
        commit point leaves the engine fully on the OLD mesh, so the
        caller's ``restore_latest`` fallback still has a coherent engine
        to restore into.

        Bit-equality contract: the host values placed here are exactly the
        bytes a synchronous checkpoint at this boundary would hold, and the
        target shardings are exactly what a fresh engine + restore onto
        ``new_hcg`` would build — so the continued loss curve is
        bit-identical to the checkpoint-restore path on the same topology
        change (tests/test_elastic_live.py pins this for both the
        replicated and ZeRO optimizer layouts).

        The ZeRO flat buffer re-pads to the NEW replica count: pad elements
        are zeros by construction and stay zero through every whitelisted
        update rule, so growing/shrinking the pad tail never perturbs real
        state.
        """
        new_mesh = new_hcg.mesh
        use_sharding = bool(self.strategy and
                            getattr(self.strategy, "sharding", False)) or \
            new_hcg.degrees["sharding"] > 1

        # ---- host gather off the OLD mesh (owned copies) ----
        fsdp_live = self._fsdp_params is not None
        host_zero = None
        if fsdp_live:
            # decode the per-layer bucket shards into the replicated host
            # view — exactly the bytes a synchronous checkpoint at this
            # boundary would hold — then re-encode below against the NEW
            # replica count (the flat param shards reslice, like ZeRO's)
            host_params = {n: np.array(v, copy=True)
                           for n, v in self._gather_fsdp_params().items()}
            host_opt = {n: tuple(np.array(s, copy=True) for s in slots)
                        for n, slots in self._gather_fsdp_opt().items()}
        else:
            host_params = {n: np.array(self.params[n], copy=True)
                           for n in self._param_names}
            host_opt = None
            if self.opt_state is not None:
                host_opt = {n: tuple(np.array(s, copy=True)
                                     for s in self.opt_state[n])
                            for n in self._param_names}
            if self._zero_opt is not None:
                n_elems = self._n_grad_elems()
                host_zero = [np.array(f, copy=True)[:n_elems]
                             for f in self._zero_opt]

        # ---- rebuild placements against the NEW mesh (temporaries) ----
        new_param_specs = {}
        new_params = {}
        for n in self._param_names:
            p = self._state_refs[n]
            spec = _param_spec(p, p.shape, new_hcg)
            new_param_specs[n] = spec
            if not fsdp_live:     # fsdp re-encodes shards, never replicates
                new_params[n] = jax.device_put(
                    host_params[n], NamedSharding(new_mesh, spec))
        new_opt_specs = {
            n: _opt_state_spec(new_param_specs[n],
                               self._state_refs[n].shape, new_hcg,
                               use_sharding)
            for n in self._param_names}

        def _opt_sh(spec):
            if self._opt_memory_kind:
                return NamedSharding(new_mesh, spec,
                                     memory_kind=self._opt_memory_kind)
            return NamedSharding(new_mesh, spec)

        new_opt_state = None
        if host_opt is not None and not fsdp_live:
            new_opt_state = {
                n: tuple(jax.device_put(s, _opt_sh(new_opt_specs[n]))
                         for s in host_opt[n])
                for n in self._param_names}

        new_zero = None
        if host_zero is not None:
            batch_axes = tuple(a for a in ("dp", "sharding")
                               if new_hcg.degrees[a] > 1)
            nrep_new = _gc.replica_count(new_mesh, batch_axes)
            n_elems = self._n_grad_elems()
            n_pad_new = _gc.zero_pad_elems(n_elems, nrep_new,
                                           _gc.chunk_size())
            spec = P(batch_axes if len(batch_axes) > 1
                     else (batch_axes[0] if batch_axes else None))
            sh = NamedSharding(new_mesh, spec)
            flats = []
            for f in host_zero:
                buf = np.zeros((n_pad_new,), np.float32)
                buf[:n_elems] = f
                flats.append(jax.device_put(buf, sh))
            new_zero = tuple(flats)

        new_fsdp_params = new_fsdp_opt = None
        if fsdp_live:
            batch_axes = tuple(a for a in ("dp", "sharding")
                               if new_hcg.degrees[a] > 1)
            nrep_new = _gc.replica_count(new_mesh, batch_axes)
            buckets_new = _gc.fsdp_buckets(
                {n: tuple(self._state_refs[n].shape)
                 for n in self._param_names},
                nrep_new, _gc.chunk_size(), layer_key=self._fsdp_layer_key())
            spec = P(batch_axes if len(batch_axes) > 1
                     else (batch_axes[0] if batch_axes else None))
            new_fsdp_params, new_fsdp_opt = self._encode_fsdp_state(
                host_params, host_opt, buckets_new,
                NamedSharding(new_mesh, spec))

        # surface transfer failures (OOM, detached device) BEFORE commit
        for arr in new_params.values():
            arr.block_until_ready()
        if new_opt_state is not None:
            for slots in new_opt_state.values():
                for s in slots:
                    s.block_until_ready()
        if new_zero is not None:
            for f in new_zero:
                f.block_until_ready()
        if new_fsdp_params is not None:
            for f in new_fsdp_params:
                f.block_until_ready()
            for slot in new_fsdp_opt:
                for f in slot:
                    f.block_until_ready()

        # ---- commit + drop every mesh-derived cache ----
        self.hcg = new_hcg
        self.mesh = new_mesh
        self.param_specs = new_param_specs
        self.params = None if fsdp_live else new_params
        self.opt_specs = new_opt_specs
        self.opt_state = new_opt_state
        self._zero_opt = new_zero
        self._fsdp_params = new_fsdp_params
        self._fsdp_opt = new_fsdp_opt
        self._invalidate_step_fns()
        self._execs.discard("train.run_steps")
        self._scan_batch_shardings = {}
        self._batch_shardings = None
        # error-feedback residual is per-replica accumulator state tied to
        # the old replica count; reformation restarts it at zero (same as
        # the checkpoint-restore path, which never persists it)
        self._grad_residual = None
        self._pending_h2d = None
        self._lr_cache = (None, None)
        self._zero_reason = "unset"
        self._zero_warned = False
        self._fsdp_cache = None
        self._fsdp_warned = False
        self._gspmd_warned = False

    # ---- compiled-executable introspection (observability/exec_introspect) --
    def _stash_exec(self, label: str, fn, call_args) -> None:
        """First call per label: remember (jitted fn, abstract args) so
        introspect_executables() can AOT-lower the same program later, and
        auto-capture now when FLAGS_exec_introspect is on. Abstract
        ShapeDtypeStructs replace the arrays (no live-buffer retention);
        PRNG keys stay concrete (extended dtypes don't round-trip avals)."""
        def aval(a):
            try:
                if jax.dtypes.issubdtype(a.dtype, jax.dtypes.prng_key):
                    return a
            except Exception:
                pass
            # weak_type rides along: the recompile-hazard analysis pass reads
            # it off the stashed signature (a weak lr would retrace per call)
            return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                        weak_type=getattr(a, "weak_type",
                                                          False))

        self._execs.stash(label, fn, call_args, donate=(), aval_fn=aval)

    def introspect_executables(self, force: bool = False) -> Dict[str, dict]:
        """Capture XLA memory_analysis()/cost_analysis() for every train
        executable this engine has dispatched (label -> stats dict; also
        mirrored into registry gauges exec.<label>.* when metrics are
        active). Costs one extra AOT compile per uncaptured label."""
        out = {}
        for label, (fn, avals) in list(self._exec_stash.items()):
            out[label] = _obs_exec.capture_jit(
                label, fn, avals, force=force,
                extra=self._introspect_extra(label))
        return out

    def _introspect_extra(self, label: str):
        """Per-label annotations merged into exec_introspect stats: fsdp
        train programs carry the resolved gather-prefetch depth and the
        analytic live-gathered window bytes, so the
        exec.train.fsdp_*.fsdp_window_bytes gauge lands next to the
        measured temp bytes it bounds (mem_report cross-checks the two)."""
        if not label.startswith("train.fsdp"):
            return None
        depth = self._fsdp_prefetch()
        return {"fsdp_prefetch": depth,
                "fsdp_window_bytes": _gc.fsdp_window_bytes(
                    self._fsdp_layout(), depth),
                "fsdp_ahead_bytes": _gc.fsdp_prefetch_ahead_bytes(
                    self._fsdp_layout(), depth)}

    # ---- static analysis (paddle_tpu.analysis) ----------------------------
    def _analysis_state_bytes(self, include_opt: bool = True) -> int:
        """Bytes of the donation-eligible carried state ONE device holds —
        what the per-device program's alias bytes are measured against (a
        param sharded over 'mp' contributes its shard, not its global
        size)."""
        tree = (self.params, self.opt_state) if include_opt else self.params
        return sum(
            int(np.prod(a.sharding.shard_shape(a.shape)))
            * np.dtype(a.dtype).itemsize
            for a in jax.tree_util.tree_leaves(tree) if hasattr(a, "shape"))

    def default_contracts(self) -> list:
        """The contracts this engine's own executables are expected to meet,
        derived from its configuration: hygiene (no host transfers, no
        constant bloat, no recompile hazards) on every train label, donation
        coverage when donation is on, and — on pure-dp meshes with real
        replicas — the collective shapes each path promises (one fused accum
        all-reduce, the ZeRO reduce-scatter/all-gather decomposition, the
        quantized-gather int8 path, combining-backend GSPMD step shapes)."""
        from .. import analysis as _an

        cs = [_an.ProgramContract(label="train.*", name="train-hygiene")]
        if self._donate:
            full = self._analysis_state_bytes()
            for pat in ("train.step", "train.run_steps", "train.accum_*"):
                cs.append(_an.ProgramContract(
                    label=pat, donated_bytes=full, name="train-donation"))
            # ZeRO donates full params but only this shard's opt state
            cs.append(_an.ProgramContract(
                label="train.zero_*",
                donated_bytes=self._analysis_state_bytes(include_opt=False),
                name="zero-donation"))
        ndp = self.hcg.degrees["dp"] * self.hcg.degrees["sharding"]
        if ndp > 1 and self._dp_pure():
            # ByGlobalNorm clip adds one scalar norm psum to the fused reduce
            clip_hi = 2 if self.optimizer._grad_clip is not None else 1
            # with a resolved prefetch window the f32/bf16 fsdp programs
            # additionally promise the overlap-ahead schedule: each bucket's
            # all-gather defined before the previous bucket's dominant
            # consumer (ISSUE 20's schedule-order pass)
            sched = ("all-gather-ahead" if self._fsdp_prefetch() >= 2
                     else None)
            # the K-microbatch scan is the one loop these programs promise;
            # at K = 1 there is nothing to scan over
            loops = (1, None) if self.microbatches > 1 else None
            cs += [
                _an.ProgramContract(
                    "train.accum_*_f32",
                    collectives={"all-reduce": (1, clip_hi)},
                    while_loops=loops, name="accum-fused-reduce"),
                _an.ProgramContract(
                    "train.accum_*_bf16*",
                    collectives={"all-reduce": (1, clip_hi)},
                    while_loops=loops, comm_dtype="bf16",
                    name="accum-fused-reduce-bf16"),
                _an.ProgramContract(
                    "train.accum_*_int8*",
                    collectives={"all-gather": (1, None),
                                 "reduce-scatter": 0},
                    while_loops=loops, comm_dtype="int8",
                    name="accum-quantized-gather"),
                _an.ProgramContract(
                    "train.zero_*",
                    collectives={"reduce-scatter": 1, "all-gather": (1, 2),
                                 "all-reduce": (0, clip_hi - 1),
                                 "all-to-all": 0},
                    while_loops=loops, name="zero-decomposition"),
                # fsdp: exactly L per-bucket weight gathers + ONE grad
                # reduce-scatter, zero full-buffer all-reduces, K-independent
                # (int8 swaps the scatter for two EQuARX all-to-alls)
                _an.ProgramContract(
                    "train.fsdp_*_f32",
                    collectives={"all-gather": len(self._fsdp_layout()),
                                 "reduce-scatter": 1,
                                 "all-reduce": (0, clip_hi - 1),
                                 "all-to-all": 0},
                    while_loops=loops, schedule_order=sched,
                    name="fsdp-decomposition"),
                _an.ProgramContract(
                    "train.fsdp_*_bf16*",
                    collectives={"all-gather": len(self._fsdp_layout()),
                                 "reduce-scatter": 1,
                                 "all-reduce": (0, clip_hi - 1),
                                 "all-to-all": 0},
                    while_loops=loops, schedule_order=sched,
                    name="fsdp-decomposition-bf16"),
                _an.ProgramContract(
                    "train.fsdp_*_int8*",
                    collectives={"all-gather": len(self._fsdp_layout()),
                                 "reduce-scatter": 0,
                                 "all-to-all": 2,
                                 "all-reduce": (0, clip_hi - 1)},
                    while_loops=loops, name="fsdp-quantized"),
                _an.ProgramContract(
                    "train.step", requires_combining=True,
                    collectives={"all-reduce": (1, 4)},
                    name="step-fused-reduce"),
                _an.ProgramContract(
                    "train.run_steps", requires_combining=True,
                    collectives={"all-reduce": (1, 4)}, while_loops=1,
                    name="run-steps-one-loop"),
            ]
        return cs

    def analyze(self, contracts=None, dump: Optional[bool] = None):
        """Run the static-analysis pass suite over every executable this
        engine has dispatched (see paddle_tpu.analysis). Dispatch-free:
        programs are AOT-lowered from the stashed abstract signatures, never
        executed. Returns an AnalysisReport; violations bump the
        analysis.* counters and (FLAGS_analysis_flight_dump) flight-dump."""
        from .. import analysis as _an

        progs = _an.programs_from_stash(self._exec_stash)
        if contracts is None:
            contracts = self.default_contracts()
        return _an.PassManager().run(progs, contracts, dump=dump)

    def _obs_step_tail(self, fr, mreg, rec, t0, t1, h2d_ms, compiled, loss,
                       hist="train.step_ms"):
        """Shared observability tail for step/_accum_step/run_steps: feed
        the metrics histograms and tee the step record into the flight
        recorder ring. Both fr and mreg are usually None (one check each in
        the callers); loss is only fetched when a recorder needs it."""
        if mreg is not None:
            mreg.histogram(hist).observe((t1 - t0) * 1e3)
            if h2d_ms:
                mreg.histogram("train.h2d_ms").observe(h2d_ms)
            if compiled:
                mreg.histogram("train.compile_ms").observe((t1 - t0) * 1e3)
        if fr is not None:
            if rec is None:
                rec = {"event": "train_step", "step": self._step_count,
                       "wall_time_s": t1 - t0,
                       "loss": float(jax.device_get(loss)),
                       "h2d_ms": h2d_ms, "compiled": compiled}
            fr.record(rec)
            lv = rec.get("loss")
            if lv is not None and not math.isfinite(lv):
                # diverged step: bump the counter and capture a post-mortem
                # dump whose ring tail ends with this very record
                _NAN_LOSS_STEPS.increase()
                fr.on_nan_inf("train_loss", {"step": self._step_count})

    @staticmethod
    def _batch_stats(arrays, lead_axes=0):
        """(samples, tokens) per dispatch from the first batch array: the
        leading dim is the sample axis. Tokens are only counted for integer
        id batches ([b, s] LM inputs) — dim 1 of a float feature matrix is
        features, not sequence, and must not inflate tokens/s."""
        if not arrays:
            return None, None
        shape = arrays[0].shape[lead_axes:]
        if not shape:
            return None, None
        samples = int(shape[0])
        tokens = None
        if len(shape) >= 2 and np.issubdtype(np.dtype(arrays[0].dtype),
                                             np.integer):
            tokens = samples * int(shape[1])
        return samples, tokens

    def _opt_sharding(self, spec):
        """NamedSharding for one optimizer-state leaf; host-memory-resident
        when the optimizer requested offload."""
        if self._opt_memory_kind:
            return NamedSharding(self.mesh, spec,
                                 memory_kind=self._opt_memory_kind)
        return NamedSharding(self.mesh, spec)

    # ---- step function construction ----
    def _build_compute_loss(self):
        """(params, key, *batch) -> scalar loss: the EXACT forward trace the
        fused step differentiates (sp scope, amp autocast, buffers, loss_fn
        convention). Shared by _raw_step and analysis_loss so the planner's
        policy-aware residual accounting can never trace a different program
        than the one that trains."""
        model = self.model
        loss_fn = self.loss_fn
        num_model_inputs = self.num_model_inputs
        buffer_names = self._buffer_names
        buffers = self.buffers

        import contextlib

        from ..ops.pallas._common import mesh_scope as _pallas_mesh_scope
        from .meta_parallel.sequence_parallel import sequence_parallel_scope

        sp_deg = self.hcg.degrees["sp"]
        # default matches DistributedStrategy.sep_impl: Ulysses wins on the
        # XLA cost model at moderate seq; ring for seq >> 100k
        sp_impl = getattr(self.strategy, "sep_impl", "ulysses") \
            if self.strategy else "ulysses"
        mesh = self.mesh

        # strategy.amp: autocast the whole traced forward (the analogue of the
        # static amp_optimizer's program rewrite — here the cast happens at
        # trace time through the dispatch-level autocast). float16 is forced to
        # bfloat16: the fused step has no loss scaling, and bf16's f32 exponent
        # range makes scaling unnecessary — fp16 without scaling would silently
        # under/overflow.
        amp_cfg = getattr(self.strategy, "amp_configs", None) \
            if self.strategy is not None and getattr(self.strategy, "amp", False) else None

        def _amp_ctx():
            if amp_cfg is None:
                return contextlib.nullcontext()
            from ..amp import amp_guard_from_configs

            return amp_guard_from_configs(amp_cfg, force_bf16=True)

        def compute_loss(ps, key, *batch):
            state = dict(ps)
            for bn in buffer_names:
                state[bn] = buffers[bn]
            sp_ctx = (sequence_parallel_scope(mesh, "sp", sp_impl)
                      if sp_deg > 1 else contextlib.nullcontext())
            with sp_ctx, _amp_ctx(), random_mod.trace_key_scope(key), \
                    _pallas_mesh_scope(mesh):
                inputs = [Tensor(b, stop_gradient=True) for b in batch]
                if loss_fn is None:
                    out = functional_call(model, state, *inputs)
                else:
                    n_in = model_input_count(len(inputs), num_model_inputs)
                    out = functional_call(model, state, *inputs[:n_in])
                    outs = out if isinstance(out, (tuple, list)) else (out,)
                    out = loss_fn(*outs, *inputs[n_in:])
            loss = out[0] if isinstance(out, (tuple, list)) else out
            return loss._data if isinstance(loss, Tensor) else loss

        return compute_loss

    def analysis_loss(self, *batch):
        """Pure params -> scalar loss over a fixed batch, tracing the same
        program step() differentiates. For trace-level analyses only (e.g.
        the planner's jax saved_residuals remat accounting) — nothing is
        compiled or executed, training state is untouched."""
        compute = self._build_compute_loss()
        arrays = self._to_arrays(batch)
        key = jax.random.key(0)
        return lambda params: compute(params, key, *arrays)

    def _raw_step(self, health_stats=None):
        update = opt_funct.make_tree_update(
            self.optimizer, {n: self._state_refs[n] for n in self._param_names})
        clip = self.optimizer._grad_clip
        compute = self._build_compute_loss()

        # grads are pinned to the opt-state specs when ZeRO is active (plain
        # partition specs — the offload memory kind must NOT ride along:
        # grads live in HBM, only the persistent state is host-resident)
        zero_specs = (self.opt_specs
                      if self.hcg.degrees["sharding"] > 1 else None)
        param_specs_c = self.param_specs
        mesh = self.mesh

        def step(params, opt_state, lr, step_i, key, *batch):
            loss, grads = jax.value_and_grad(
                lambda ps: compute(ps, key, *batch))(params)
            raw_grads = grads  # pre-clip: what health attribution must see
            if zero_specs is not None:
                # ZeRO stage-1/2 boundary (reference group_sharded_optimizer_
                # stage2.py:48 semantics), in TWO chained constraints:
                # 1. grad at the PARAM spec — stops the optimizer-state
                #    sharding from propagating backward INTO the grad
                #    computation. Un-pinned, GSPMD pushes e.g. the embedding
                #    m/v spec ("mp","sharding") onto the wte grad
                #    scatter-add, which then demands its [b,s,h] update
                #    operand hidden-sharded — a batch->hidden reshard the
                #    partitioner can only do by full rematerialization
                #    (VERDICT r3 #4). At the param spec the scatter keeps
                #    batch-sharded updates and emits partial grads + psum.
                # 2. grad at the OPT spec — the explicit ZeRO transition,
                #    a composable subdivide that lowers to
                #    reduce-scatter/dynamic-slice, after which the update
                #    runs on the shard and only new params all-gather.
                with jax.named_scope("grad_sync"):
                    grads = {n: jax.lax.with_sharding_constraint(
                        g, NamedSharding(mesh, param_specs_c[n]))
                        for n, g in grads.items()}
                    grads = {n: jax.lax.with_sharding_constraint(
                        g, NamedSharding(mesh, zero_specs[n]))
                        for n, g in grads.items()}
            with jax.named_scope("grad_clip"):
                grads = opt_funct.clip_grads(grads, clip)
            with jax.named_scope("optimizer"):
                new_params, new_opt = update(params, grads, opt_state, lr,
                                             step_i)
            if health_stats is None:
                return loss, new_params, new_opt
            return loss, new_params, new_opt, health_stats(
                raw_grads, params, new_params)

        return step

    def _build(self, batch_avals):
        health = self._health
        step = self._raw_step(
            health.make_packed_stats() if health is not None else None)
        param_shardings = {n: NamedSharding(self.mesh, s) for n, s in self.param_specs.items()}
        # the jitted step is all-device; offload transfers happen at the
        # python boundary in step() (jax 0.9 dropped in-jit memory transfers)
        opt_shardings = {
            n: tuple(NamedSharding(self.mesh, self.opt_specs[n])
                     for _ in self.opt_state[n])
            for n in self._param_names}
        batch_shardings = self._shardings_for(batch_avals)
        scalar = NamedSharding(self.mesh, P())
        out_sh = (scalar, param_shardings, opt_shardings)
        if health is not None:
            out_sh += (scalar,)  # packed f32 [4P] health buffer, replicated

        return jax.jit(
            step,
            in_shardings=(param_shardings, opt_shardings, scalar, scalar, scalar)
            + batch_shardings,
            out_shardings=out_sh,
            donate_argnums=(0, 1) if self._donate else (),
        )

    def _build_scan(self, batch_avals, fixed_batch):
        """K train steps fused into ONE compiled program via lax.scan.

        The analogue of the reference's fleet_executor running a whole section
        of iterations per dispatch (fleet_executor/compute_interceptor.cc's
        LoopCounter / max_run_times) instead of one step per Executor.run —
        on TPU it also collapses K PJRT executes into one. With
        fixed_batch=False, batch arrays carry a leading
        [K] axis and the scan consumes one slice per step; with
        fixed_batch=True the same single batch feeds every step (scan
        xs=None — one device copy, not K). Per-step learning rates arrive as
        a [K] f32 array (schedules stay host-side).
        """
        step = self._raw_step()

        def multi(params, opt_state, lrs, step0, keys, *batch):
            # keys: [K] array of per-step subkeys, split HOST-side with the
            # exact split sequence step() uses — so dropout streams (and thus
            # losses) match a loop of K step() calls bit-for-bit
            def body(carry, xs):
                p, o, i = carry
                sub = xs[0]
                loss, p, o = step(p, o, lrs[i], step0 + i, sub,
                                  *(batch if fixed_batch else xs[1:]))
                return (p, o, i + jnp.int32(1)), loss

            (params, opt_state, _), losses = jax.lax.scan(
                body, (params, opt_state, jnp.int32(0)),
                (keys,) if fixed_batch else (keys,) + tuple(batch))
            return losses, params, opt_state

        param_shardings = {n: NamedSharding(self.mesh, s)
                           for n, s in self.param_specs.items()}
        opt_shardings = {
            n: tuple(NamedSharding(self.mesh, self.opt_specs[n])
                     for _ in self.opt_state[n])
            for n in self._param_names}
        if self.input_specs is not None:
            per_step = self.input_specs
        else:
            lead = 0 if fixed_batch else 1
            per_step = [_default_input_spec(a.shape[lead:], self.hcg)
                        for a in batch_avals]
        batch_shardings = tuple(
            NamedSharding(self.mesh, s if fixed_batch else P(None, *s))
            for s in per_step)
        scalar = NamedSharding(self.mesh, P())

        self._scan_batch_shardings[fixed_batch] = batch_shardings
        return jax.jit(
            multi,
            in_shardings=(param_shardings, opt_shardings, scalar, scalar,
                          scalar) + batch_shardings,
            out_shardings=(scalar, param_shardings, opt_shardings),
            donate_argnums=(0, 1) if self._donate else (),
        )

    # ---- microbatch gradient accumulation (grad_comm) ----
    def _batch_axes(self):
        return tuple(a for a in ("dp", "sharding")
                     if self.hcg.degrees[a] > 1)

    def _dp_pure(self) -> bool:
        """True when the mesh is pure data-parallel (dp and/or ZeRO sharding
        only) and every param is replicated — the shard_map deferred-reduce
        fast path (ONE fused gradient all-reduce independent of K)."""
        if any(self.hcg.degrees[a] > 1 for a in ("mp", "sp", "ep", "pp")):
            return False
        return all(all(e is None for e in tuple(s))
                   for s in self.param_specs.values())

    def _grad_comm_config(self):
        """(k, dtype, use_residual, chunk, zero) resolved from the engine +
        flags. The accumulation path engages when K > 1, a low-precision
        gradient collective is requested, or the ZeRO weight-update
        sharding is on; otherwise step() stays on the original
        (bit-identical) fused step."""
        k = max(1, int(self.microbatches))
        dtype = _gc.comm_dtype()
        if not self._dp_pure():
            if dtype != "f32" and not self._gspmd_warned:
                import warnings

                warnings.warn(
                    f"FLAGS_grad_comm_dtype={dtype} applies only to pure "
                    f"data-parallel meshes; topology {self.hcg.topology()} "
                    f"uses GSPMD collectives (f32)")
                self._gspmd_warned = True
            dtype = "f32"
        use_residual = (dtype != "f32" and self._dp_pure()
                        and _gc.error_feedback())
        return k, dtype, use_residual, _gc.chunk_size(), self._zero_on()

    # ---- ZeRO weight-update sharding (arXiv:2004.13336) ----
    # optimizer rules whose update is a uniform elementwise function of
    # (param, grad, state) — safe to run on an arbitrary contiguous slice
    # of the flat buffer. lamb/lars need per-parameter trust ratios.
    _ZERO_RULES = frozenset({"sgd", "momentum", "adam", "adamw", "adamax",
                             "adagrad", "adadelta", "rmsprop"})

    def _zero_requested(self) -> bool:
        return bool(self.zero_update or _flags.flag("zero_update"))

    def _zero_fallback_reason(self) -> Optional[str]:
        """None when the weight-update sharding can engage; otherwise a
        human-readable reason. Cached — every input is engine-lifetime
        static (mesh topology, optimizer rule/kwargs/clip, offload)."""
        if self._zero_reason != "unset":
            return self._zero_reason
        from ..nn.clip import ClipGradByGlobalNorm, ClipGradByValue

        opt = self.optimizer
        reason = None
        if not self._dp_pure():
            reason = (f"topology {self.hcg.topology()} is not pure "
                      "data-parallel; running the GSPMD accumulation path")
        elif not self._param_names:
            reason = "no trainable parameters"
        elif opt._rule not in self._ZERO_RULES:
            reason = (f"optimizer rule {opt._rule!r} is not uniform-"
                      "elementwise (needs per-parameter norms)")
        elif any(opt._rule_kwargs(self._state_refs[n]) !=
                 opt._rule_kwargs(self._state_refs[self._param_names[0]])
                 for n in self._param_names):
            reason = ("per-parameter rule kwargs differ (e.g. weight-decay "
                      "exclusions): the flat shard update needs ONE "
                      "uniform rule")
        elif not (opt._grad_clip is None or isinstance(
                opt._grad_clip, (ClipGradByGlobalNorm, ClipGradByValue))):
            reason = (f"grad clip {type(opt._grad_clip).__name__} needs "
                      "per-parameter norms")
        elif self._opt_memory_kind:
            reason = ("optimizer offload keeps the replicated host-"
                      "resident state")
        self._zero_reason = reason
        return reason

    def _zero_on(self) -> bool:
        """True when this step runs the ZeRO weight-update-sharded program
        (requested AND compatible). Incompatible configs warn ONCE and run
        the replicated (or GSPMD) update. Yields to fsdp — the fully
        sharded path subsumes the weight-update sharding."""
        if self._fsdp_on():
            return False
        if not self._zero_requested():
            return False
        reason = self._zero_fallback_reason()
        if reason is None:
            return True
        if not self._zero_warned:
            import warnings

            warnings.warn("zero_update requested but falling back to the "
                          f"replicated update: {reason}")
            self._zero_warned = True
        return False

    def _zero_n_slots(self) -> int:
        """Optimizer-state slots per parameter for the active rule (0 for
        sgd, 1 for momentum/adagrad, 2 for adam/adamw, ...)."""
        return len(opt_funct.init_state(self.optimizer._rule,
                                        np.zeros((1,), np.float32)))

    def _zero_layout(self):
        """(n, n_pad, shard, nrep) of the flat parameter/optimizer-state
        vector: n grad elements padded to a multiple of nrep*chunk, each
        replica owning the contiguous [r*shard, (r+1)*shard) slice."""
        nrep = _gc.replica_count(self.mesh, self._batch_axes())
        n = self._n_grad_elems()
        n_pad = _gc.zero_pad_elems(n, nrep, _gc.chunk_size())
        return n, n_pad, n_pad // max(1, nrep), nrep

    def _make_flat_update(self):
        """The ZeRO twin of opt_funct.make_tree_update: ONE uniform
        elementwise rule over flat f32 [shard] vectors. Uniformity of the
        per-param kwargs is guaranteed upstream by _zero_fallback_reason;
        pad slots (zero param, zero grad, zero state) stay exactly zero
        through every whitelisted rule."""
        rule = opt_funct.RULES[self.optimizer._rule]
        needs_step = self.optimizer._rule in opt_funct._NEEDS_STEP
        kw0 = dict(self.optimizer._rule_kwargs(
            self._state_refs[self._param_names[0]]))

        def flat_update(p_shard, g_shard, opt_shards, lr, step_i):
            kw = dict(kw0)
            if needs_step:
                kw["step"] = step_i
            new_p, new_state = rule(p_shard, g_shard, tuple(opt_shards),
                                    lr=lr, **kw)
            return new_p, tuple(new_state)

        return flat_update

    def _ensure_zero_opt(self):
        """Lazy ONE-WAY conversion of the replicated opt-state dict into
        flat f32 1/N shards (segment_layout / sorted-name order, zero pad
        tail). After the first sharded step self.opt_state is None — the
        flat shards ARE the state; _gather_zero_opt() reconstructs the
        dict form for checkpoints/debugging."""
        n, n_pad, shard, nrep = self._zero_layout()
        if self._zero_opt is not None:
            if self._zero_opt and self._zero_opt[0].shape != (n_pad,):
                raise ValueError(
                    "the flat sharded optimizer state was built for a "
                    f"different layout ({self._zero_opt[0].shape[0]} != "
                    f"{n_pad} elements) — FLAGS_grad_comm_chunk or the "
                    "mesh changed after the first ZeRO step; rebuild the "
                    "engine")
            return self._zero_opt
        sh = self._residual_sharding()
        names = sorted(self._param_names)
        flats = []
        for j in range(self._zero_n_slots()):
            buf = np.zeros((n_pad,), np.float32)
            off = 0
            for nm in names:
                size = int(np.prod(self._state_refs[nm].shape) or 1)
                buf[off:off + size] = np.asarray(
                    self.opt_state[nm][j], np.float32).reshape(-1)
                off += size
            flats.append(jax.device_put(buf, sh))
        self._zero_opt = tuple(flats)
        self.opt_state = None  # one-way: the flat shards are the state now
        return self._zero_opt

    def _gather_zero_opt(self):
        """Reconstruct the replicated {name: (slot, ...)} opt-state dict
        from the flat shards (host gather; checkpoint/debug convenience).
        Returns self.opt_state unchanged when ZeRO never engaged."""
        if self._zero_opt is None:
            return self.opt_state
        flats = [np.asarray(f) for f in self._zero_opt]
        out = {}
        off = 0
        for nm in sorted(self._param_names):
            shape = tuple(self._state_refs[nm].shape)
            size = int(np.prod(shape) or 1)
            out[nm] = tuple(f[off:off + size].reshape(shape)
                            for f in flats)
            off += size
        return out

    def zero_memory_model(self):
        """Analytic optimizer-state memory of the ZeRO path: replicated
        bytes per device vs flat-shard bytes per device (~1/N). The
        measured counterpart is introspect_executables() argument bytes."""
        n, n_pad, shard, nrep = self._zero_layout()
        slots = self._zero_n_slots()
        return {
            "opt_slots": slots,
            "replicas": nrep,
            "n_grad_elems": n,
            "n_pad": n_pad,
            "replicated_opt_bytes": slots * n * 4,
            "sharded_opt_bytes_per_device": slots * shard * 4,
        }

    # ---- FSDP: fully sharded parameters (arXiv:2004.13336, all the way) ----
    def _fsdp_requested(self) -> bool:
        return bool(self.fsdp or _flags.flag("fsdp"))

    def _fsdp_on(self) -> bool:
        """True when this step runs the fully-sharded program (requested
        AND compatible — the eligibility gate is exactly ZeRO's: pure-dp
        mesh, uniform elementwise rule, global-norm/value clip, no
        offload). Incompatible configs warn ONCE and run the replicated
        (or GSPMD) update. Supersedes zero_update when both are set."""
        if not self._fsdp_requested():
            return False
        reason = self._zero_fallback_reason()
        if reason is None:
            return True
        if not self._fsdp_warned:
            import warnings

            warnings.warn("fsdp requested but falling back to the "
                          f"replicated update: {reason}")
            self._fsdp_warned = True
        return False

    def _fsdp_layer_key(self):
        """The model's bucket-granularity hook (``fsdp_layer_key(name)``)
        or None for grad_comm.default_layer_key (one bucket per module)."""
        return getattr(self.model, "fsdp_layer_key", None)

    def _fsdp_layout(self):
        """Per-layer bucket metadata of the flat sorted-name parameter
        vector for the current mesh (cached per (nrep, chunk)): each
        bucket is a contiguous run of names sharing a layer key, padded
        to a multiple of nrep*chunk — these are the per-layer all-gather
        boundaries and the shard shapes of the resident state."""
        nrep = _gc.replica_count(self.mesh, self._batch_axes())
        chunk = _gc.chunk_size()
        if self._fsdp_cache is not None and \
                self._fsdp_cache[0] == (nrep, chunk):
            return self._fsdp_cache[1]
        buckets = _gc.fsdp_buckets(
            {n: tuple(self._state_refs[n].shape)
             for n in self._param_names},
            nrep, chunk, layer_key=self._fsdp_layer_key())
        self._fsdp_cache = ((nrep, chunk), buckets)
        return buckets

    def _fsdp_prefetch(self) -> int:
        """Resolved gather-prefetch window depth: FLAGS_fsdp_prefetch
        clamped against the current bucket layout so live-gathered bytes
        never exceed the two largest adjacent buckets (the double-buffer
        bound). Recomputed per step — reform_mesh() re-buckets, so the
        windowed step fns rebuild at the new topology's clamp."""
        return _gc.fsdp_prefetch_depth(self._fsdp_layout(),
                                       int(_flags.flag("fsdp_prefetch")))

    def fsdp_memory_model(self):
        """Analytic param+opt residency of the fsdp path: replicated
        bytes vs per-bucket flat-shard bytes per device (~1/N for BOTH
        params and optimizer state — ZeRO only shards the latter), plus
        the per-step wire bytes (L bucket weight gathers + one grad
        reduce-scatter). The measured counterpart is
        introspect_executables() argument bytes (tools/mem_report.py)."""
        buckets = self._fsdp_layout()
        nrep = _gc.replica_count(self.mesh, self._batch_axes())
        slots = self._zero_n_slots()
        n = self._n_grad_elems()
        shard_elems = [b["shard"] for b in buckets]
        rs_b, ag_b, per_layer = _gc.fsdp_payload_bytes(
            shard_elems, nrep, _gc.comm_dtype(), _gc.chunk_size())
        depth = self._fsdp_prefetch()
        return {
            "prefetch": depth,
            "window_bytes": _gc.fsdp_window_bytes(buckets, depth),
            "window_bytes_jit": _gc.fsdp_window_bytes(buckets, 0),
            "ahead_bytes": _gc.fsdp_prefetch_ahead_bytes(buckets, depth),
            "replicas": nrep,
            "n_grad_elems": n,
            "opt_slots": slots,
            "buckets": [{"key": b["key"], "n": b["n"], "pad": b["pad"],
                         "shard": b["shard"], "ag_bytes": ab}
                        for b, ab in zip(buckets, per_layer)],
            "replicated_param_bytes": n * 4,
            "sharded_param_bytes_per_device": sum(shard_elems) * 4,
            "replicated_opt_bytes": slots * n * 4,
            "sharded_opt_bytes_per_device": slots * sum(shard_elems) * 4,
            "rs_bytes": rs_b,
            "ag_bytes": ag_b,
        }

    def _encode_fsdp_state(self, params_src, opt_src, buckets, sh):
        """Encode replicated host-view params (+ opt-state dict) into the
        per-bucket flat f32 [pad] buffers placed with sharding ``sh``
        (sorted-name order within each bucket, zero pad tail). Returns
        (per-bucket param tuple, per-slot tuple of per-bucket tuples)."""
        n_slots = self._zero_n_slots()
        p_out = []
        o_cols = [[] for _ in range(n_slots)]
        for b in buckets:
            pbuf = np.zeros((b["pad"],), np.float32)
            obufs = [np.zeros((b["pad"],), np.float32)
                     for _ in range(n_slots)]
            off = 0
            for nm in b["names"]:
                size = int(np.prod(self._state_refs[nm].shape) or 1)
                pbuf[off:off + size] = np.asarray(
                    params_src[nm], np.float32).reshape(-1)
                if opt_src is not None:
                    for j in range(n_slots):
                        obufs[j][off:off + size] = np.asarray(
                            opt_src[nm][j], np.float32).reshape(-1)
                off += size
            p_out.append(jax.device_put(pbuf, sh))
            for j in range(n_slots):
                o_cols[j].append(jax.device_put(obufs[j], sh))
        return tuple(p_out), tuple(tuple(col) for col in o_cols)

    def _ensure_fsdp_state(self):
        """Lazy ONE-WAY conversion of the replicated params + opt state
        into per-bucket flat f32 1/N shards. After the first fsdp step
        self.params AND self.opt_state are None — the bucket shards ARE
        the state; _gather_fsdp_params()/_gather_fsdp_opt() reconstruct
        the replicated views for checkpoints/sync_to_model."""
        buckets = self._fsdp_layout()
        if self._fsdp_params is not None:
            if len(self._fsdp_params) != len(buckets) or any(
                    f.shape != (b["pad"],)
                    for f, b in zip(self._fsdp_params, buckets)):
                raise ValueError(
                    "the flat sharded parameter state was built for a "
                    "different bucket layout — FLAGS_grad_comm_chunk or "
                    "the mesh changed after the first fsdp step; rebuild "
                    "the engine")
            return self._fsdp_params, self._fsdp_opt
        self._param_dtypes = {n: np.dtype(self.params[n].dtype)
                              for n in self._param_names}
        opt_src = self._gather_zero_opt()  # dict view (handles prior ZeRO)
        self._fsdp_params, self._fsdp_opt = self._encode_fsdp_state(
            {n: np.asarray(self.params[n]) for n in self._param_names},
            opt_src, buckets, self._residual_sharding())
        self.params = None   # one-way: the bucket shards are the state now
        self.opt_state = None
        self._zero_opt = None
        return self._fsdp_params, self._fsdp_opt

    def _gather_fsdp_params(self):
        """Reconstruct the replicated {name: array} param dict from the
        bucket shards (host gather; checkpoint/sync convenience). Returns
        self.params unchanged when fsdp never engaged."""
        if self._fsdp_params is None:
            return self.params
        dts = self._param_dtypes or {}
        out = {}
        for b, f in zip(self._fsdp_layout(), self._fsdp_params):
            flat = np.asarray(f)
            off = 0
            for nm in b["names"]:
                shape = tuple(self._state_refs[nm].shape)
                size = int(np.prod(shape) or 1)
                out[nm] = flat[off:off + size].reshape(shape).astype(
                    dts.get(nm, np.float32), copy=False)
                off += size
        return out

    def _gather_fsdp_opt(self):
        """Replicated {name: (slot, ...)} opt-state dict decoded from the
        bucket shards; falls through to the ZeRO/replicated forms when
        fsdp never engaged."""
        if self._fsdp_params is None:
            return self._gather_zero_opt()
        cols = [[np.asarray(f) for f in col] for col in self._fsdp_opt]
        out = {}
        for bi, b in enumerate(self._fsdp_layout()):
            off = 0
            for nm in b["names"]:
                shape = tuple(self._state_refs[nm].shape)
                size = int(np.prod(shape) or 1)
                out[nm] = tuple(col[bi][off:off + size].reshape(shape)
                                for col in cols)
                off += size
        return out

    def _build_fsdp_accum(self, batch_avals, k, dtype, use_residual, chunk):
        """Jit the fully-sharded accumulation step: parameters enter AND
        leave as per-bucket flat f32 [pad] buffers sharded 1/N over the
        data axes (exactly like the ZeRO opt slots), each bucket
        all-gathers just before use inside the step, and ONE
        reduce-scatter lands the grads on the owning shard for the
        shard-local clip+update. No trailing parameter gather — that is
        the argument-bytes win over _build_zero_accum."""
        compute = self._build_compute_loss()
        health = self._health
        dts = self._param_dtypes or {}
        param_templates = {
            n: jax.ShapeDtypeStruct(
                tuple(self._state_refs[n].shape),
                self.params[n].dtype if self.params is not None
                else dts.get(n, np.dtype(np.float32)))
            for n in self._param_names}
        buckets = self._fsdp_layout()
        step = _gc.make_fsdp_accum_step(
            compute_loss=compute, flat_update=self._make_flat_update(),
            clip=self.optimizer._grad_clip, mesh=self.mesh,
            batch_axes=self._batch_axes(), k=k, dtype=dtype, chunk=chunk,
            use_residual=use_residual, param_templates=param_templates,
            buckets=buckets, prefetch=self._fsdp_prefetch(),
            health_partial=(health.make_sharded_stats()
                            if health is not None else None))
        batch_shardings = self._shardings_for(batch_avals)
        shard_sh = self._residual_sharding()
        p_sh = tuple(shard_sh for _ in buckets)
        opt_sh = tuple(p_sh for _ in range(self._zero_n_slots()))
        scalar = NamedSharding(self.mesh, P())
        in_sh = (p_sh, opt_sh)
        out_sh = (scalar, p_sh, opt_sh)
        donate = (0, 1)
        if use_residual:
            in_sh += (shard_sh,)
            out_sh += (shard_sh,)
            donate = (0, 1, 2)
        if health is not None:
            out_sh += (shard_sh,)  # [nrep, 4P] per-replica rows ride LAST
        return jax.jit(
            step,
            in_shardings=in_sh + (scalar, scalar, scalar) + batch_shardings,
            out_shardings=out_sh,
            donate_argnums=donate if self._donate else (),
        )

    def _build_zero_accum(self, batch_avals, k, dtype, use_residual, chunk):
        """Jit the ZeRO weight-update-sharded accumulation step: same scan
        as _build_accum, but the post-scan reduction is reduce-scatter ->
        shard-local clip+update -> all-gather of updated weights, and the
        optimizer state enters/leaves as flat [n_pad] f32 slot buffers
        sharded 1/N over the data axes."""
        compute = self._build_compute_loss()
        health = self._health
        param_templates = {
            n: jax.ShapeDtypeStruct(tuple(self._state_refs[n].shape),
                                    self.params[n].dtype)
            for n in self._param_names}
        step = _gc.make_zero_accum_step(
            compute_loss=compute, flat_update=self._make_flat_update(),
            clip=self.optimizer._grad_clip, mesh=self.mesh,
            batch_axes=self._batch_axes(), k=k, dtype=dtype, chunk=chunk,
            use_residual=use_residual, param_templates=param_templates,
            health_partial=(health.make_sharded_stats()
                            if health is not None else None))
        batch_shardings = self._shardings_for(batch_avals)
        param_shardings = {n: NamedSharding(self.mesh, s)
                           for n, s in self.param_specs.items()}
        shard_sh = self._residual_sharding()   # 1-D [n_pad] split over d0
        opt_shardings = tuple(shard_sh for _ in range(self._zero_n_slots()))
        scalar = NamedSharding(self.mesh, P())
        in_sh = (param_shardings, opt_shardings)
        out_sh = (scalar, param_shardings, opt_shardings)
        donate = (0, 1)
        if use_residual:
            res_sh = self._residual_sharding()
            in_sh += (res_sh,)
            out_sh += (res_sh,)
            donate = (0, 1, 2)
        if health is not None:
            out_sh += (scalar,)  # packed health buffer rides LAST
        return jax.jit(
            step,
            in_shardings=in_sh + (scalar, scalar, scalar) + batch_shardings,
            out_shardings=out_sh,
            donate_argnums=donate if self._donate else (),
        )

    def _n_grad_elems(self) -> int:
        return int(sum(int(np.prod(self._state_refs[n].shape) or 1)
                       for n in self._param_names))

    def _residual_sharding(self):
        axes = self._batch_axes()
        spec = P(axes if len(axes) > 1 else (axes[0] if axes else None))
        return NamedSharding(self.mesh, spec)

    def _ensure_residual(self):
        if self._grad_residual is None:
            nrep = _gc.replica_count(self.mesh, self._batch_axes())
            self._grad_residual = jax.device_put(
                np.zeros((nrep, self._n_grad_elems()), np.float32),
                self._residual_sharding())
        return self._grad_residual

    def _build_accum(self, batch_avals, k, dtype, use_residual, chunk):
        """Jit the K-microbatch accumulation step. The dp-pure fast path
        runs the scan + ONE deferred collective under shard_map
        (grad_comm.make_accum_step); hybrid meshes take the GSPMD
        accumulation scan fallback."""
        compute = self._build_compute_loss()
        update = opt_funct.make_tree_update(
            self.optimizer, {n: self._state_refs[n]
                             for n in self._param_names})
        clip = self.optimizer._grad_clip
        zero_specs = (self.opt_specs
                      if self.hcg.degrees["sharding"] > 1 else None)
        batch_shardings = self._shardings_for(batch_avals)
        health = self._health
        health_stats = (health.make_packed_stats()
                        if health is not None else None)
        if self._dp_pure():
            step = _gc.make_accum_step(
                compute_loss=compute, update=update, clip=clip,
                mesh=self.mesh, batch_axes=self._batch_axes(), k=k,
                dtype=dtype, chunk=chunk, use_residual=use_residual,
                param_specs=self.param_specs, zero_specs=zero_specs,
                health_stats=health_stats)
        else:
            step = _gc.make_accum_step_gspmd(
                compute_loss=compute, update=update, clip=clip,
                mesh=self.mesh, k=k,
                batch_specs=[s.spec for s in batch_shardings],
                param_specs=self.param_specs, zero_specs=zero_specs,
                health_stats=health_stats)
        param_shardings = {n: NamedSharding(self.mesh, s)
                           for n, s in self.param_specs.items()}
        opt_shardings = {
            n: tuple(NamedSharding(self.mesh, self.opt_specs[n])
                     for _ in self.opt_state[n])
            for n in self._param_names}
        scalar = NamedSharding(self.mesh, P())
        in_sh = (param_shardings, opt_shardings)
        out_sh = (scalar, param_shardings, opt_shardings)
        donate = (0, 1)
        if use_residual:
            res_sh = self._residual_sharding()
            in_sh += (res_sh,)
            out_sh += (res_sh,)
            donate = (0, 1, 2)  # the residual is carried state: donate it
        if health is not None:
            out_sh += (scalar,)  # packed health buffer rides LAST
        return jax.jit(
            step,
            in_shardings=in_sh + (scalar, scalar, scalar) + batch_shardings,
            out_shardings=out_sh,
            donate_argnums=donate if self._donate else (),
        )

    def _accum_step(self, arrays, span) -> Tensor:
        """One optimizer step over K in-program microbatches: the grad_comm
        twin of step() (same plumbing contract: telemetry, compile
        accounting, donation-safe rebind of params/opt state). `span` is
        step()'s open `engine.step`, ended here when the enqueue returns."""
        tr = _obs_tracer.get_tracer()
        k, dtype, use_residual, chunk, zero = self._grad_comm_config()
        self._check_batch(arrays)
        nrep = _gc.replica_count(self.mesh, self._batch_axes())
        for a in arrays:
            if a.ndim and a.shape[0] % (nrep * k) != 0:
                raise ValueError(
                    f"batch dim {a.shape[0]} is not divisible by "
                    f"microbatches*replicas = {k}*{nrep}; pad or resize "
                    f"the batch (topology: {self.hcg.topology()})")
        from ..core import autotune
        autotune.set_step(self._step_count + 1)
        health_on = self._health is not None
        fsdp = self._fsdp_on()
        # fsdp appends rather than widening the tuple so non-fsdp keys stay
        # identical to the PR 18 registry layout (pinned by test_zero_update);
        # the resolved prefetch depth rides the same append so flipping
        # FLAGS_fsdp_prefetch rebuilds the windowed step fn
        fsdp_pf = self._fsdp_prefetch() if fsdp else 0
        cache_key = (k, dtype, use_residual, chunk, health_on, zero) + \
            ((True, fsdp_pf) if fsdp else ())
        label = (f"train.fsdp_k{k}_{dtype}" if fsdp
                 else f"train.zero_k{k}_{dtype}" if zero
                 else f"train.accum_k{k}_{dtype}") + \
            ("_res" if use_residual else "")
        build = (self._build_fsdp_accum if fsdp
                 else self._build_zero_accum if zero
                 else self._build_accum)
        entry = self._execs.get_or_build(
            ("train.accum",) + cache_key,
            lambda: build(arrays, k, dtype, use_residual, chunk),
            label=label, pin=True)
        fn = entry.fn
        staged, self._pending_h2d = self._pending_h2d, None
        with tr.boundary("engine.place_batch"):
            arrays, h2d_ms = self._place_batch(
                arrays, self._batch_shardings,
                timed=self.telemetry is not None and staged is None)
        prefetch_depth = None
        if staged is not None:
            h2d_ms, prefetch_depth = staged
        self._step_count += 1
        self.optimizer._step_count = self._step_count
        lr_val = self.optimizer.get_lr()
        if self._lr_cache[0] != lr_val:
            self._lr_cache = (lr_val, jnp.float32(lr_val))
        lr = self._lr_cache[1]
        self._key, sub = jax.random.split(self._key)
        tele = self.telemetry
        fr = _obs_flight.get()
        mreg = _obs_metrics.active_registry()
        n0 = _jit_cache_size(fn)
        p0 = _compile_cache.misses() if n0 == 0 else -1
        t0 = time.perf_counter()
        try:
            with tr.boundary("engine.dispatch"):
                if fsdp:
                    p_in, opt_in = self._ensure_fsdp_state()
                else:
                    p_in = self.params
                    opt_in = (self._ensure_zero_opt() if zero
                              else self._opt_to_hbm(self.opt_state))
                call_args = (p_in, opt_in) + (
                    (self._ensure_residual(),) if use_residual else ()) + (
                    lr, jnp.int32(self._step_count), sub) + tuple(arrays)
                self._stash_exec(label, fn, call_args)
                outs = fn(*call_args)
            if use_residual:
                loss, new_p, new_opt, self._grad_residual = outs[:4]
            else:
                loss, new_p, new_opt = outs[:3]
            if fsdp:
                self._fsdp_params = tuple(new_p)
            else:
                self.params = new_p
            hbuf = outs[-1] if health_on else None
            n1 = _jit_cache_size(fn)
            span.args = {"step": self._step_count, "compiled": n1 > n0 >= 0,
                         "microbatches": k, "grad_comm_dtype": dtype,
                         "zero_update": zero, "fsdp": fsdp}
            span.end()          # enqueue returned: the span never syncs
            if tele is not None or fr is not None or mreg is not None:
                jax.block_until_ready(loss)
        except Exception as e:
            if fr is not None:
                fr.dump("train_step_exception",
                        {"step": self._step_count, "error": repr(e)})
            raise
        t1 = time.perf_counter()
        compiled = self._execs.note_compiles(
            entry, n_before=n0, n_after=n1, wall_s=t1 - t0,
            persistent_before=p0, engine_counters=True) > 0
        if fsdp:
            # L per-bucket weight gathers + one grad reduce-scatter; the
            # health partials ride a sharded output (no collective bytes)
            rs_b, ag_b = ((0, 0) if nrep <= 1 else _gc.fsdp_payload_bytes(
                [b["shard"] for b in self._fsdp_layout()], nrep, dtype,
                chunk)[:2])
            comm_bytes = rs_b + ag_b
            _gc.RS_BYTES.increase(rs_b)
            _gc.AG_BYTES.increase(ag_b)
        elif zero:
            rs_b, ag_b = ((0, 0) if nrep <= 1 else _gc.zero_payload_bytes(
                self._n_grad_elems(), nrep, dtype, chunk,
                4 * len(self._param_names) if health_on else 0))
            comm_bytes = rs_b + ag_b
            _gc.RS_BYTES.increase(rs_b)
            _gc.AG_BYTES.increase(ag_b)
        else:
            comm_bytes = (_gc.payload_bytes(self._n_grad_elems(), dtype,
                                            chunk) if nrep > 1 else 0)
        _gc.STEPS.increase()
        _gc.MICROBATCHES.increase(k)
        _gc.BYTES_MOVED.increase(comm_bytes)
        if dtype != "f32":
            _gc.LOWP_STEPS.increase()
        if fsdp:
            self._fsdp_opt = tuple(tuple(col) for col in new_opt)
        elif zero:
            self._zero_opt = tuple(new_opt)
        else:
            self.opt_state = self._opt_to_home(new_opt)
        if hbuf is not None:
            if fsdp:
                # per-replica [nrep, 4P] segment partials: the cross-shard
                # sum happens HERE (host-side) instead of as an in-program
                # all-reduce, and only on fetch steps — off-interval steps
                # skip the D2H entirely
                hbuf = (np.asarray(hbuf).sum(axis=0, dtype=np.float32)
                        if self._health.wants(self._step_count) else None)
            self._health.on_step(self._step_count, hbuf)
        self.last_loss = Tensor(loss)
        rec = None
        if tele is not None:
            samples, tokens = self._batch_stats(arrays)
            rec = tele.record_step(
                step=self._step_count, wall_time=t1 - t0, samples=samples,
                tokens=tokens, loss=float(jax.device_get(loss)),
                h2d_ms=h2d_ms, prefetch_depth=prefetch_depth,
                microbatches=k, grad_comm_dtype=dtype,
                grad_comm_bytes=comm_bytes,
                extra=({"fsdp": True, "fsdp_prefetch": fsdp_pf,
                        "fsdp_window_bytes": _gc.fsdp_window_bytes(
                            self._fsdp_layout(), fsdp_pf)} if fsdp
                       else {"zero_update": True} if zero else None))
        if fr is not None or mreg is not None:
            self._obs_step_tail(fr, mreg, rec, t0, t1, h2d_ms, compiled, loss)
        if self._ckpt is not None:
            self._ckpt.on_step(self, self._step_count, loss)
        return self.last_loss

    # ---- shared step plumbing ----
    def _shardings_for(self, arrays):
        """Per-position batch shardings (input_specs, or the default
        dp/sharding/sp layout from the first batch's shapes). Cached — the
        same tuple serves _build, step() placement, and the prefetcher."""
        if self._batch_shardings is None:
            if self.input_specs is not None:
                self._batch_shardings = tuple(
                    NamedSharding(self.mesh, s) for s in self.input_specs)
            else:
                self._batch_shardings = tuple(
                    NamedSharding(self.mesh,
                                  _default_input_spec(a.shape, self.hcg))
                    for a in arrays)
        return self._batch_shardings

    def _place_batch(self, arrays, shardings, timed=False):
        """Sharded host->device placement that SKIPS arrays already placed
        with a matching sharding (a prefetched batch pays no second
        device_put). Returns (arrays, h2d issue ms | None)."""
        t0 = time.perf_counter() if timed else None
        arrays = [a if _pf.is_placed(a, s) else jax.device_put(a, s)
                  for a, s in zip(arrays, shardings)]
        if timed:
            return arrays, (time.perf_counter() - t0) * 1000.0
        return arrays, None

    def _check_batch(self, arrays, lead_axes=0):
        """The dp*sharding divisibility guard, shared by step()/run_steps()."""
        batch_axes = self.hcg.degrees["dp"] * self.hcg.degrees["sharding"]
        for a in arrays:
            if a.ndim > lead_axes and a.shape[lead_axes] % batch_axes != 0:
                raise ValueError(
                    f"batch dim {a.shape[lead_axes]} is not divisible by "
                    f"dp*sharding = {batch_axes}; pad or resize the batch "
                    f"(topology: {self.hcg.topology()})")

    @staticmethod
    def _to_arrays(batch):
        return [b._data if isinstance(b, Tensor) else jnp.asarray(b)
                for b in batch]

    def _opt_to_hbm(self, opt_state):
        """Offload mode: stream host-resident optimizer state to HBM for the
        update (async device_put pipelines with dispatch). No-op otherwise."""
        if not self._opt_memory_kind:
            return opt_state
        return {
            n: tuple(jax.device_put(s, NamedSharding(self.mesh,
                                                     self.opt_specs[n]))
                     for s in st) for n, st in opt_state.items()}

    def _opt_to_home(self, opt_state):
        """Offload mode: move the fresh optimizer state back to host memory."""
        if not self._opt_memory_kind:
            return opt_state
        return {
            n: tuple(jax.device_put(s, self._opt_sharding(self.opt_specs[n]))
                     for s in st) for n, st in opt_state.items()}

    # ---- public API ----
    def run_steps(self, *batch, steps: Optional[int] = None):
        """Run K fused train steps in one dispatch; returns losses [K].

        Either pass batch arrays with a leading [K] step axis, or single-step
        arrays plus steps=K to reuse the same batch every step (benchmark /
        overfit loops; the batch is uploaded ONCE, not K times). Loss history
        comes back as one f32 array.

        Orthogonal to `microbatches`: run_steps fuses K OPTIMIZER STEPS into
        one dispatch (each over its full batch); the grad_comm accumulation
        path fuses K microbatches into ONE optimizer step. run_steps always
        runs the plain per-step program regardless of engine.microbatches.

        Health telemetry (enable_health) does NOT ride this path: the scan
        yields only losses, so per-step health stats would multiply the
        program's outputs by K. Use step()/_accum_step for monitored runs.

        zero_update does NOT compose either — the scan carries the
        replicated opt-state dict while the ZeRO path owns flat 1/N
        shards; silently running the replicated update here would diverge
        from step() semantics, so an active zero_update raises instead
        (pinned by tests/test_zero_update.py).
        """
        arrays = self._to_arrays(batch)
        if self._fsdp_on():
            raise ValueError(
                "run_steps (the fused K-step scan lane) does not compose "
                "with fsdp: the scan carries the replicated params/opt-"
                "state dicts while the fsdp path owns per-layer flat 1/N "
                "shards per data replica. Use step() (one dispatch per "
                "optimizer step, L bucket all-gathers + one reduce-"
                "scatter) or disable fsdp for this engine.")
        if self._zero_on():
            raise ValueError(
                "run_steps (the fused K-step scan lane) does not compose "
                "with zero_update: the scan carries the replicated "
                "opt-state dict while the ZeRO path owns flat 1/N shards "
                "per data replica. Use step() (one dispatch per optimizer "
                "step, one reduce-scatter + one all-gather) or disable "
                "zero_update for this engine.")
        fixed = steps is not None
        self._check_batch(arrays, lead_axes=0 if fixed else 1)
        k = steps if fixed else arrays[0].shape[0]
        if k < 1:
            raise ValueError(f"run_steps needs at least one step, got K={k}")
        from ..core import autotune
        autotune.set_step(self._step_count + k)
        scan_entry = self._execs.get_or_build(
            ("train.run_steps", fixed),
            lambda: self._build_scan(arrays, fixed),
            label="train.run_steps", pin=True)
        arrays, h2d_ms = self._place_batch(
            arrays, self._scan_batch_shardings[fixed],
            timed=self.telemetry is not None)
        # host-side schedule bookkeeping, mirroring step(): one lr per step
        step0 = self._step_count + 1
        lrs = []
        for _ in range(k):
            self._step_count += 1
            self.optimizer._step_count = self._step_count
            lrs.append(self.optimizer.get_lr())
        lrs = jnp.asarray(lrs, jnp.float32)
        # one subkey per step, advancing self._key exactly as K step() calls
        subs = []
        for _ in range(k):
            self._key, sub = jax.random.split(self._key)
            subs.append(sub)
        fn = scan_entry.fn
        tele = self.telemetry
        fr = _obs_flight.get()
        mreg = _obs_metrics.active_registry()
        n0 = _jit_cache_size(fn)
        p0 = _compile_cache.misses() if n0 == 0 else -1
        t0 = time.perf_counter()
        try:
            call_args = (self.params, self._opt_to_hbm(self.opt_state), lrs,
                         jnp.int32(step0), jnp.stack(subs)) + tuple(arrays)
            self._stash_exec("train.run_steps", fn, call_args)
            losses, self.params, new_opt = fn(*call_args)
            if tele is not None or fr is not None or mreg is not None:
                jax.block_until_ready(losses)  # honest wall: drain the K steps
        except Exception as e:
            if fr is not None:
                fr.dump("run_steps_exception",
                        {"step0": step0, "steps": k, "error": repr(e)})
            raise
        t1 = time.perf_counter()
        compiled = self._execs.note_compiles(
            scan_entry, n_before=n0, n_after=_jit_cache_size(fn),
            wall_s=t1 - t0, persistent_before=p0, engine_counters=True) > 0
        tr = _obs_tracer.get_tracer()
        if tr.enabled:
            tr.record_complete("engine.run_steps", t0, t1,
                               {"steps": k, "step0": step0,
                                "compiled": compiled})
        self.opt_state = self._opt_to_home(new_opt)
        self.last_loss = Tensor(losses[-1])
        rec = None
        if tele is not None:
            samples, tokens = self._batch_stats(
                arrays, lead_axes=0 if fixed else 1)
            rec = tele.record_step(
                step=self._step_count, wall_time=t1 - t0,
                samples=samples * k if samples else None,
                tokens=tokens * k if tokens else None,
                loss=float(jax.device_get(losses[-1])),
                h2d_ms=h2d_ms,
                extra={"steps_fused": k})
        if fr is not None or mreg is not None:
            self._obs_step_tail(fr, mreg, rec, t0, t1, h2d_ms, compiled,
                                losses[-1], hist="train.run_steps_ms")
        if self._ckpt is not None:
            # K fused steps = one hook call; window makes an interval that
            # fell INSIDE the scan still checkpoint at the scan boundary
            self._ckpt.on_step(self, self._step_count, losses[-1], window=k)
        return Tensor(losses)

    def warm_scan(self, *batch, steps: int):
        """Compile + device-warm the K-step scan program WITHOUT advancing
        training state: run_steps executes on copies (its donation consumes
        the originals; the copies made here survive and are restored). Use
        before timing a run_steps region so compile cost stays outside it."""
        saved = (jax.tree_util.tree_map(jnp.copy, self.params),
                 jax.tree_util.tree_map(jnp.copy, self.opt_state),
                 self._step_count, self._key, self.last_loss)
        tele, self.telemetry = self.telemetry, None  # warm run is not a step:
        #                         a compile-heavy record would poison the stream
        try:
            losses = self.run_steps(*batch, steps=steps)
            float(losses[-1].item())  # drain: the warm execution must not
            #                           queue into a caller's timed region
        finally:
            (self.params, self.opt_state, self._step_count, self._key,
             self.last_loss) = saved
            self.optimizer._step_count = self._step_count
            self.telemetry = tele

    def step(self, *batch) -> Tensor:
        """One optimizer step. The span `engine.step` runs from here to the
        return of the enqueue, never over a sync (the loss is a future),
        with children `engine.place_batch` (host to device) and
        `engine.dispatch` (arguments and the call); all three are
        engine-boundary spans (observability/tracer.py `boundary`), always
        recorded. `compiled` in its arguments tells a step that compiled
        or loaded its program from one that only dispatched."""
        with _obs_tracer.get_tracer().boundary("engine.step") as span:
            # _step ends the span itself, when the enqueue returns; leaving
            # the block ends it only if the step raised before that
            return self._step(batch, span)

    def _step(self, batch, span) -> Tensor:
        tr = _obs_tracer.get_tracer()
        arrays = self._to_arrays(batch)
        if (self.microbatches > 1 or _gc.comm_dtype() != "f32"
                or self._zero_on() or self._fsdp_on()):
            # grad_comm path: K in-program microbatches + one deferred fused
            # gradient all-reduce (and/or low-precision collectives, and/or
            # the ZeRO weight-update sharding). The default (K=1, f32, no
            # zero_update) stays below on the original step program —
            # bit-identical to pre-grad_comm behavior.
            return self._accum_step(arrays, span)
        self._check_batch(arrays)
        from ..core import autotune
        autotune.set_step(self._step_count + 1)
        step_entry = self._execs.get_or_build(
            ("train.step",), lambda: self._build(arrays),
            label="train.step", pin=True)
        # place batch according to specs (host->device with the right
        # sharding); arrays staged by prefetch() arrive already placed and
        # skip the put — their H2D stats were captured at issue time
        staged, self._pending_h2d = self._pending_h2d, None
        with tr.boundary("engine.place_batch"):
            arrays, h2d_ms = self._place_batch(
                arrays, self._batch_shardings,
                timed=self.telemetry is not None and staged is None)
        if staged is not None:
            h2d_ms, prefetch_depth = staged
        else:
            prefetch_depth = None
        self._step_count += 1
        self.optimizer._step_count = self._step_count  # keep ckpt/resume consistent
        lr_val = self.optimizer.get_lr()
        if self._lr_cache[0] != lr_val:  # constant-lr steps reuse the device scalar
            self._lr_cache = (lr_val, jnp.float32(lr_val))
        lr = self._lr_cache[1]
        self._key, sub = jax.random.split(self._key)
        fn = step_entry.fn
        tele = self.telemetry
        fr = _obs_flight.get()
        mreg = _obs_metrics.active_registry()
        n0 = _jit_cache_size(fn)
        # persistent-cache snapshot only around a first compile, when the fn
        # has no executable yet (recompiles from shape churn stay
        # unclassified)
        p0 = _compile_cache.misses() if n0 == 0 else -1
        health_on = self._health is not None
        t0 = time.perf_counter()
        try:
            with tr.boundary("engine.dispatch"):
                call_args = (self.params, self._opt_to_hbm(self.opt_state),
                             lr, jnp.int32(self._step_count),
                             sub) + tuple(arrays)
                self._stash_exec("train.step", fn, call_args)
                outs = fn(*call_args)
            loss, self.params, new_opt = outs[:3]
            hbuf = outs[-1] if health_on else None
            n1 = _jit_cache_size(fn)
            span.args = {"step": self._step_count, "compiled": n1 > n0 >= 0}
            span.end()          # enqueue returned: the span never syncs
            if tele is not None or fr is not None or mreg is not None:
                jax.block_until_ready(loss)  # honest wall over async dispatch
        except Exception as e:
            if fr is not None:
                fr.dump("train_step_exception",
                        {"step": self._step_count, "error": repr(e)})
            raise
        t1 = time.perf_counter()
        compiled = self._execs.note_compiles(
            step_entry, n_before=n0, n_after=n1,
            wall_s=t1 - t0, persistent_before=p0, engine_counters=True) > 0
        self.opt_state = self._opt_to_home(new_opt)
        if hbuf is not None:
            self._health.on_step(self._step_count, hbuf)
        self.last_loss = Tensor(loss)
        rec = None
        if tele is not None:
            samples, tokens = self._batch_stats(arrays)
            rec = tele.record_step(
                step=self._step_count, wall_time=t1 - t0, samples=samples,
                tokens=tokens, loss=float(jax.device_get(loss)),
                h2d_ms=h2d_ms, prefetch_depth=prefetch_depth)
        if fr is not None or mreg is not None:
            self._obs_step_tail(fr, mreg, rec, t0, t1, h2d_ms, compiled, loss)
        if self._ckpt is not None:
            self._ckpt.on_step(self, self._step_count, loss)
        return self.last_loss

    train_batch = step

    def prefetch(self, loader, depth: int = 2):
        """Iterate `loader` as device-placed batches: the sharded H2D for the
        next `depth` batches is issued while the current step's program is
        still executing (JAX async dispatch), so transfer overlaps compute.

            for batch in engine.prefetch(loader):
                engine.step(*batch)

        step() skips its own device_put for the pre-placed arrays (one
        transfer per batch total) and records the prefetcher's per-batch
        h2d_ms / prefetch_depth in StepTelemetry. The loader may yield
        Tensors or raw arrays; batch layout must match step(*batch)."""
        pf = _pf.DevicePrefetcher(self._shardings_for, depth=depth)
        self.prefetcher = pf

        def arrays_iter():
            for batch in loader:
                if not isinstance(batch, (tuple, list)):
                    batch = (batch,)
                arrays = self._to_arrays(batch)
                self._check_batch(arrays)
                yield arrays

        def placed_iter():
            for placed in pf.iterate(arrays_iter()):
                self._pending_h2d = (pf.last_h2d_ms, pf.last_depth)
                yield placed

        return placed_iter()

    def sync_to_model(self):
        """Write engine-owned (possibly sharded) params back into the eager Layer."""
        params = (self.params if self.params is not None
                  else self._gather_fsdp_params())
        for n in self._param_names:
            # np.asarray gathers a sharded global array to host, then re-uploads dense
            self._state_refs[n]._data = jnp.asarray(np.asarray(params[n]))
        return self.model

    def state_dict(self):
        params = (self.params if self.params is not None
                  else self._gather_fsdp_params())
        out = {}
        for n in self._param_names:
            out[n] = Tensor(jnp.asarray(np.asarray(params[n])))
        for n in self._buffer_names:
            out[n] = Tensor(self.buffers[n])
        return out


def parallelize(model, optimizer, loss_fn=None, hcg=None, strategy=None, **kw):
    """Sugar: fleet-style entry returning a ready TrainStepEngine."""
    return TrainStepEngine(model, optimizer, loss_fn=loss_fn, hcg=hcg,
                           strategy=strategy, **kw)
