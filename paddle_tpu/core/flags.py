"""Runtime flag registry.

Mirrors the reference's gflags surface (`paddle/fluid/platform/flags.cc`,
`PADDLE_DEFINE_EXPORTED_*`, settable from env as FLAGS_* and from Python via paddle.set_flags).
TPU-natively there is no C++ gflags; a plain registry with env bootstrapping gives the same
contract (`FLAGS_check_nan_inf=1 python train.py` and `paddle_tpu.set_flags({...})`).
"""
from __future__ import annotations

import os
from typing import Any, Dict

_REGISTRY: Dict[str, Any] = {}


def define_flag(name: str, default, help_: str = ""):
    env = os.environ.get("FLAGS_" + name)
    if env is not None:
        if isinstance(default, bool):
            default = env.lower() in ("1", "true", "yes", "on")
        elif isinstance(default, int):
            default = int(env)
        elif isinstance(default, float):
            default = float(env)
        else:
            default = env
    _REGISTRY[name] = default


_on_change = []
_explicitly_set: set = set()  # flags a user/test set via set_flags (vs defaults)


def was_set(name: str) -> bool:
    """True when the flag was explicitly assigned through set_flags — lets a
    default-on flag (use_flash_attention) distinguish 'deliberately enabled'
    from 'never touched' for test-only paths like interpret-mode routing."""
    return name.removeprefix("FLAGS_") in _explicitly_set


def on_change(callback):
    """Register callback(flag_name) fired whenever a flag value changes —
    caches keyed on flag values (dispatch rule cache) subscribe here so an
    unlisted flag can never serve a stale trace."""
    _on_change.append(callback)


def set_flags(flags: Dict[str, Any]):
    for k, v in flags.items():
        k = k.removeprefix("FLAGS_")
        if k not in _REGISTRY:
            raise KeyError(f"unknown flag {k!r}; known: {sorted(_REGISTRY)}")
        changed = _REGISTRY[k] != v
        _REGISTRY[k] = v
        _explicitly_set.add(k)
        if changed:
            for cb in _on_change:
                cb(k)


def get_flags(names):
    if isinstance(names, str):
        names = [names]
    return {("FLAGS_" + n.removeprefix("FLAGS_")): _REGISTRY[n.removeprefix("FLAGS_")] for n in names}


def flag(name: str):
    return _REGISTRY[name]


# Core flags (analogues of platform/flags.cc entries that matter on TPU).
define_flag("check_nan_inf", False, "check every op output for nan/inf (debug)")
define_flag("benchmark", False, "synchronize after each op for timing")
define_flag("allocator_strategy", "xla", "kept for parity; XLA/PJRT owns device memory")
define_flag("eager_op_jit", True, "jit-cache per-op computations in dygraph")
define_flag("tpu_matmul_precision", "default", "default|high|highest for MXU matmuls")
define_flag("use_flash_attention", True, "route attention to the Pallas flash kernel on TPU")
define_flag("seed", 0, "global random seed")
define_flag("apply_ir_passes", True, "run CSE/DCE/fuse passes before lowering static programs")
define_flag("use_autotune", False, "enable kernel autotune (pallas block-size search + cache)")
define_flag("enable_unused_var_check", False, "warn when an op kernel never reads a declared input")
# use_pallas_lm_loss / pallas_lm_loss_block_n / use_pallas_layernorm were
# RETIRED in round 5: the kernels stay as direct-call library
# ops in ops/pallas/, but nothing routes to them and no flag re-enables that.
define_flag("fused_ce_chunk", 2048,
            "rows per scan step of the chunked fused LM-head cross-entropy "
            "(ops/fused.py). Each chunk re-reads the [V, H] head weight from "
            "HBM, so larger chunks trade transient logits memory "
            "(chunk x vocab f32) for fewer weight reads")
define_flag("pallas_interpret_ok", False, "allow pallas kernels in interpret mode on CPU (tests)")
define_flag("eager_fast_path", True,
            "shape/dtype-keyed dispatch fast lane: steady-state eager ops "
            "skip the per-call closure freeze / AMP resolution / debug-check "
            "probes when AMP and the debug flags are off (single cached-rule "
            "hit). Purely an overhead cut — results are bit-identical to the "
            "slow path, which remains the first-call and fallback route")
define_flag("eager_fusion", False,
            "opt-in eager micro-fusion: chains of cacheable elementwise ops "
            "are recorded lazily and compiled as ONE jitted composite when a "
            "result is forced (MPK-style dispatch collapsing). Off by "
            "default: evaluation becomes deferred for whitelisted ops, which "
            "changes op-granular timing/tracing semantics")
define_flag("decode_jit_cache_size", 16,
            "max cached decode executables per model for generate()/"
            "generate_beam() (LRU over sampling-config keys). Evictions "
            "count in core.monitor decode.cache_evictions; new entries in "
            "decode.jit_compiles. <= 0 disables the bound")
define_flag("grad_comm_dtype", "f32",
            "gradient all-reduce precision for the grad_comm path "
            "(distributed/grad_comm.py): f32 (default — bit-identical to "
            "the plain fused step), bf16 (half the wire bytes), or int8 "
            "(EQuARX-style chunk-scaled quantized collective, ~4x fewer "
            "bytes). Applies on pure data-parallel meshes; hybrid (mp/sp) "
            "topologies ignore it and reduce in f32")
define_flag("grad_comm_error_feedback", False,
            "carry the local quantization error of the low-precision "
            "gradient collective into the next step (error-feedback "
            "residual). Removes the bias of repeated bf16/int8 rounding at "
            "the cost of one f32 gradient-sized buffer per data replica")
define_flag("grad_comm_chunk", 1024,
            "elements per scaling block of the int8 gradient collective: "
            "each chunk ships one f32 absmax scale with its int8 payload "
            "(smaller chunks track gradient dynamic range better, larger "
            "chunks amortize scale overhead)")
define_flag("zero_update", False,
            "ZeRO-style cross-replica weight-update sharding on the fused "
            "gradient path (arXiv:2004.13336, distributed/grad_comm.py "
            "make_zero_accum_step): the post-scan reduction decomposes into "
            "reduce-scatter -> shard-local clip+optimizer update -> "
            "all-gather of updated weights, and the optimizer state lives "
            "as flat f32 1/N shards per data replica. Pure data-parallel "
            "meshes with uniform elementwise optimizer rules only; "
            "incompatible configs warn once and run the replicated (or "
            "GSPMD) update. Also per-engine: TrainStepEngine("
            "zero_update=True)")
define_flag("fsdp", False,
            "fully sharded data parallelism on the fused gradient path "
            "(arXiv:2004.13336 taken past the optimizer state; "
            "distributed/grad_comm.py make_fsdp_accum_step): parameters "
            "live ONLY as contiguous per-layer flat f32 1/N shards between "
            "steps, each layer's weights all-gather just before their "
            "forward/backward use inside the compiled step, gradients "
            "reduce-scatter back onto the owning shard, and the uniform "
            "elementwise optimizer rule runs shard-locally — param AND "
            "opt-state residency drop to ~1/N with no trailing parameter "
            "gather. Same eligibility gate as zero_update (pure "
            "data-parallel meshes, uniform rules); ineligible configs warn "
            "once and run the replicated (or GSPMD) path. Supersedes "
            "zero_update when both are set. Also per-engine: "
            "TrainStepEngine(fsdp=True)")
define_flag("fsdp_prefetch", 2,
            "gather-prefetch window depth of the fsdp forward pass "
            "(distributed/grad_comm.py make_fsdp_accum_step): with depth d "
            ">= 2, bucket L's gathered weights are released through a "
            "value-identity select pin tied to the all-gathers for "
            "buckets L+1..L+d-1, so every valid schedule issues the next "
            "bucket's gather before the current bucket's compute consumes "
            "its params (double-buffered at the default 2), the ahead "
            "buffers stay resident across the microbatch scan (the "
            "measurable live-window bytes), and the backward pass mirrors "
            "the window in descending bucket order. 0 disables the window "
            "(just-in-time gathers). The depth is clamped so live-gathered "
            "bytes never exceed the two largest adjacent buckets. Pins are "
            "identity on values: every depth is bit-equal to depth 0 (and "
            "to the replicated trajectory)")
define_flag("health_monitor", False,
            "compute training-health statistics (global + per-parameter "
            "grad/weight norms, update-to-weight ratios, non-finite "
            "localization) IN-PROGRAM as an auxiliary output of the compiled "
            "train step (observability/health.py). Zero extra dispatches; "
            "the device->host fetch is gated to FLAGS_health_interval. Also "
            "enabled by PADDLE_TPU_HEALTH_DIR (which adds a health.jsonl "
            "sink). Read at engine construction")
define_flag("health_interval", 10,
            "steps between device->host fetches of the packed health-stats "
            "buffer (ONE transfer of one f32 [4P] array per fetch). The "
            "stats are computed every step regardless — only the host "
            "readback, registry feed, and JSONL write are gated")
define_flag("health_spike_factor", 10.0,
            "grad-norm spike threshold: a fetched global grad norm above "
            "factor*EMA(grad_norm) bumps health.spikes and triggers a "
            "flight-recorder dump (reason health_grad_spike). <= 0 disables "
            "spike detection")
define_flag("exec_introspect", False,
            "capture XLA memory_analysis()/cost_analysis() for every step/"
            "prefill/decode executable the engines compile "
            "(observability/exec_introspect.py: registry gauges "
            "exec.<label>.* + tools/mem_report.py rows). Costs ONE extra "
            "AOT compile per program (the jit cache is not reused by the "
            "introspection lowering) — a diagnostic flag, off by default")
define_flag("ckpt_dir", os.environ.get("PADDLE_TPU_CKPT_DIR", ""),
            "elastic checkpoint directory (also settable as "
            "PADDLE_TPU_CKPT_DIR). Non-empty: every TrainStepEngine attaches "
            "a distributed/elastic.py CheckpointManager at construction — "
            "async crash-safe snapshots every FLAGS_ckpt_interval steps, "
            "newest-valid restore with corruption fallback. Empty = off "
            "(engine.enable_checkpointing() still works per-engine)")
define_flag("ckpt_interval", 100,
            "optimizer steps between automatic checkpoints when "
            "FLAGS_ckpt_dir / enable_checkpointing is active. An interval "
            "that fires while the previous async save is still writing "
            "skips (ckpt.skipped counter) rather than stalling the step")
define_flag("ckpt_keep", 3,
            "retention: committed checkpoints beyond the newest N are "
            "GC'd after each successful save (ckpt.gc_removed counter)")
define_flag("ckpt_async", True,
            "overlap checkpoint serialization with training: capture is a "
            "device-to-host copy on the step thread, hashing/fsync/commit "
            "run on a background writer behind a depth-1 queue. False = "
            "synchronous saves (step blocks until the commit rename)")
define_flag("ckpt_rollback", False,
            "opt-in auto-rollback: a non-finite training loss triggers a "
            "flight-recorder dump and restores the newest valid checkpoint "
            "in place of the diverged state (ckpt.rollbacks counter). "
            "Costs one loss fetch per step while enabled")
define_flag("compile_cache_dir",
            os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)))), ".jax_cache"),
            "persistent XLA compilation cache directory; the default is a "
            "fixed path inside the checkout. JAX_COMPILATION_CACHE_DIR, "
            "when set, places the cache instead and this flag cannot move "
            "it. Empty = off (core/compile_cache.py)")
define_flag("analysis_flight_dump", False,
            "when engine.analyze()/hlo_lint finds contract violations and a "
            "flight recorder is installed, dump the ring naming the "
            "offending label + pass (analysis/manager.py)")
define_flag("elastic_lease_s", 5.0,
            "membership heartbeat lease duration in seconds "
            "(distributed/membership.py). A worker whose lease key is older "
            "than this is treated as departed at the next coordinator poll "
            "(elastic.lease_expiries counter); heartbeats refresh at a third "
            "of the lease so one missed beat never evicts")
define_flag("elastic_check_interval", 1,
            "optimizer steps between ElasticCoordinator membership polls "
            "when driving through coordinator.on_step(). 1 = re-form at the "
            "very next step boundary after a join/leave lands")
define_flag("elastic_drain_timeout_s", 30.0,
            "serving-replica drain bound: a SIGTERM'd ServingEngine stops "
            "admission and runs active slots to completion for at most this "
            "long before retiring (elastic.drain_ms histogram)")
define_flag("kv_page_tokens", 64,
            "tokens per KV-cache page for the paged serving layout "
            "(serving/kv_pages.py). Smaller pages waste fewer bytes on the "
            "last partial page per sequence and share finer-grained "
            "prefixes; larger pages shrink the page table and the gather. "
            "Must divide nothing — any positive value works; prefix reuse "
            "only shares whole pages")
define_flag("kv_cache_dtype", "auto",
            "paged KV-cache storage dtype: 'auto' stores pages in the "
            "attention compute dtype, 'bf16' casts pages to bfloat16, "
            "'int8' stores EQuARX-style chunk-scaled int8 pages (one f32 "
            "absmax/127 scale per (page, token, head), dequantized inside "
            "the attention read). Only the paged layout honors this")
