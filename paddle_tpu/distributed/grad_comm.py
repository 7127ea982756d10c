"""Gradient communication: in-program microbatch accumulation with ONE
deferred fused all-reduce, plus opt-in low-precision gradient collectives.

The reference framework's biggest data-parallel lever is the Reducer
(`paddle/fluid/imperative/reducer.cc`): gradients are bucketed into flat
buffers, the per-bucket all-reduce is issued once backward finishes, and
with gradient accumulation the reduce is DEFERRED to the last microbatch
(`fuse_all_reduce_ops` + `_enable_backward_accumulate`). This module is the
XLA-native equivalent, built from three composable pieces:

1. **In-program microbatch accumulation** — the global batch is reshaped to
   [K, B/K] and a `lax.scan` runs forward+backward per microbatch inside ONE
   compiled program, accumulating gradients into a flat f32 buffer. The
   activation peak scales with the microbatch (the scan body is compiled
   once), and there is exactly one dispatch per optimizer step.
2. **Deferred, bucketed reduction** — the per-microbatch `psum` the GSPMD
   partitioner would emit is replaced by a single collective over the
   flattened gradient buffer AFTER the accumulation scan. The data-parallel
   region runs under `shard_map` (manual collectives), so the deferral is
   structural — the compiled HLO carries exactly one gradient all-reduce
   regardless of K (pinned by tests/test_hlo_perf_gates.py).
3. **Opt-in low-precision collectives** (`FLAGS_grad_comm_dtype`):
   - ``f32`` (default): bit-exact f32 all-reduce, one [N+1] buffer (the
     scalar loss rides in the same collective).
   - ``bf16``: the buffer is reduced in bfloat16 — half the wire bytes.
   - ``int8``: EQuARX-style chunk-scaled quantization (arXiv:2506.17615):
     per-chunk absmax scales, int8 payload gathered over the data axis and
     reduced in f32 locally — ~4x fewer wire bytes than f32.
   ``FLAGS_grad_comm_error_feedback=1`` carries the local quantization error
   into the next step (error-feedback residual, 1-bit-Adam style), removing
   the bias of repeated rounding at the cost of one f32 gradient-sized
   buffer per replica.

Topology scope: the shard_map fast path covers pure data-parallel meshes
(dp and/or ZeRO `sharding` axes; every param replicated). Hybrid meshes
(mp/sp > 1) fall back to a GSPMD accumulation scan — still one dispatch and
a microbatch-sized activation peak, but the partitioner re-emits one fused
reduce per microbatch and the precision knob is ignored (f32).
"""
from __future__ import annotations

import math
from typing import Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core import flags as _flags
from ..core import monitor as _monitor

# grad_comm.* observability: steps through this subsystem, microbatches
# executed, and the collective payload bytes per device (analytic — the
# bytes handed to the wire-facing collective, the number that shrinks when
# the precision knob drops below f32).
STEPS = _monitor.stat("grad_comm.steps")
MICROBATCHES = _monitor.stat("grad_comm.microbatches")
BYTES_MOVED = _monitor.stat("grad_comm.bytes_moved")
LOWP_STEPS = _monitor.stat("grad_comm.lowp_steps")
# ZeRO weight-update-sharded steps: analytic per-device bytes handed to the
# reduce-scatter (gradients down) and the all-gather (updated weights back)
RS_BYTES = _monitor.stat("grad_comm.rs_bytes")
AG_BYTES = _monitor.stat("grad_comm.ag_bytes")

_CANON = {"f32": "f32", "float32": "f32", "fp32": "f32",
          "bf16": "bf16", "bfloat16": "bf16", "int8": "int8"}


def comm_dtype() -> str:
    """Canonical FLAGS_grad_comm_dtype value: 'f32' | 'bf16' | 'int8'."""
    v = str(_flags.flag("grad_comm_dtype")).lower()
    if v not in _CANON:
        raise ValueError(
            f"FLAGS_grad_comm_dtype={v!r} — expected one of "
            f"{sorted(set(_CANON))}")
    return _CANON[v]


def error_feedback() -> bool:
    return bool(_flags.flag("grad_comm_error_feedback"))


def chunk_size() -> int:
    c = int(_flags.flag("grad_comm_chunk"))
    if c <= 0:
        raise ValueError(f"FLAGS_grad_comm_chunk={c} must be positive")
    return c


def payload_bytes(n_grads: int, dtype: str, chunk: int) -> int:
    """Per-device bytes handed to the gradient collective for one optimizer
    step. f32/bf16 carry the loss scalar in the same buffer; int8 ships the
    quantized payload plus one f32 scale per chunk (+ the loss)."""
    if dtype == "f32":
        return (n_grads + 1) * 4
    if dtype == "bf16":
        return (n_grads + 1) * 2
    n_chunks = -(-n_grads // chunk)
    return n_chunks * chunk * 1 + (n_chunks + 1) * 4


def zero_pad_elems(n_grads: int, nrep: int, chunk: int) -> int:
    """Padded flat-buffer length for the ZeRO update path: a multiple of
    nrep*chunk, so every replica owns an equal contiguous shard AND the int8
    chunk grid tiles it exactly. Always leaves at least ONE spare pad slot —
    the f32/bf16 paths ride the loss scalar through the reduce-scatter in
    slot n_grads (the bit-exactness trick vs the replicated psum).
    dtype-independent on purpose — the sharded optimizer state keeps ONE
    shape across f32/bf16/int8 steps."""
    unit = max(1, nrep) * max(1, chunk)
    return -(-(n_grads + 1) // unit) * unit


def zero_payload_bytes(n_grads: int, nrep: int, dtype: str, chunk: int,
                       health_elems: int = 0) -> Tuple[int, int]:
    """(reduce_scatter_bytes, all_gather_bytes) per device per step for the
    ZeRO update path — the local contribution handed to each collective,
    the payload_bytes convention. The all-gather slab carries the updated
    f32 weight shard + the loss scalar + the health partials (when on)."""
    n_pad = zero_pad_elems(n_grads, nrep, chunk)
    shard = n_pad // max(1, nrep)
    if dtype == "f32":
        rs = n_pad * 4
    elif dtype == "bf16":
        rs = n_pad * 2
    else:  # int8 payload + one f32 scale per chunk, both via all-to-all
        rs = n_pad * 1 + (n_pad // chunk) * 4
    ag = (shard + 1 + health_elems) * 4
    return rs, ag


# ---------------------------------------------------------------- quantize --

def _quantize_int8(x, chunk):
    """Chunk-scaled int8 quantization (EQuARX block scaling): returns
    (q [C, chunk] int8, scales [C] f32). Zero-padded to a chunk multiple;
    the pad quantizes to exact zeros."""
    n = x.shape[0]
    pad = (-n) % chunk
    xp = jnp.pad(x, (0, pad)).reshape(-1, chunk)
    scale = jnp.max(jnp.abs(xp), axis=1) / 127.0
    safe = jnp.maximum(scale, jnp.float32(1e-30))
    q = jnp.clip(jnp.round(xp / safe[:, None]), -127, 127).astype(jnp.int8)
    return q, scale


def _dequantize_int8(q, scale, n):
    return (q.astype(jnp.float32) * scale[..., None]).reshape(
        q.shape[:-2] + (-1,))[..., :n]


def _reduce_local(flat, loss, axes, dtype, chunk, residual):
    """The ONE deferred gradient collective, inside the manual (shard_map)
    region. flat: [N] f32 local partial mean-grads; loss: local mean loss.
    Returns (reduced mean grads [N], mean loss, new residual [N] | None).
    With no collective axes (single-replica mesh) this degrades to the
    identity (plus quantize/dequantize for the low-precision dtypes, so the
    numerics a multi-replica run sees stay testable on one device)."""
    nrep = 1
    for ax in axes:
        nrep *= jax.lax.psum(1, ax)
    if residual is not None:
        flat = flat + residual
    if dtype == "f32":
        buf = jnp.concatenate([flat, loss[None]])
        if axes:
            buf = jax.lax.psum(buf, axes)
        return buf[:-1] / nrep, buf[-1] / nrep, None
    if dtype == "bf16":
        b = flat.astype(jnp.bfloat16)
        new_res = flat - b.astype(jnp.float32) if residual is not None else None
        buf = jnp.concatenate([b, loss.astype(jnp.bfloat16)[None]])
        if axes:
            buf = jax.lax.psum(buf, axes)
        buf = buf.astype(jnp.float32)
        return buf[:-1] / nrep, buf[-1] / nrep, new_res
    # int8: quantize the local partial, gather payload+scales over the data
    # axes, dequantize-and-sum in f32 (a quantized all-reduce built from
    # all-gather — per-replica scales survive the trip, matching EQuARX's
    # block-scaled exchange). The loss scalar rides in the f32 scales buffer.
    n = flat.shape[0]
    q, scale = _quantize_int8(flat, chunk)
    new_res = (flat - _dequantize_int8(q, scale, n)
               if residual is not None else None)
    aux = jnp.concatenate([scale, loss[None]])
    if axes:
        gq = jax.lax.all_gather(q, axes)            # [nrep, C, chunk]
        gaux = jax.lax.all_gather(aux, axes)        # [nrep, C+1]
        red = jnp.sum(_dequantize_int8(gq, gaux[:, :-1], n), axis=0)
        loss_sum = jnp.sum(gaux[:, -1])
        return red / nrep, loss_sum / nrep, new_res
    return _dequantize_int8(q, scale, n), loss, new_res


# ---------------------------------------------------------- step builders --

def _spec_axes(axes: Sequence[str]):
    """PartitionSpec dim-0 entry for a tuple of batch axes."""
    axes = tuple(axes)
    if not axes:
        return None
    return axes if len(axes) > 1 else axes[0]


def replica_count(mesh: Mesh, axes: Sequence[str]) -> int:
    n = 1
    for ax in axes:
        n *= mesh.shape[ax]
    return int(n)


def make_accum_step(*, compute_loss: Callable, update: Callable, clip,
                    mesh: Mesh, batch_axes: Sequence[str], k: int,
                    dtype: str, chunk: int, use_residual: bool,
                    param_specs: Optional[Dict[str, P]] = None,
                    zero_specs: Optional[Dict[str, P]] = None,
                    health_stats: Optional[Callable] = None):
    """Build the microbatch-accumulation train step for a pure-dp mesh.

    Returns step(params, opt_state[, residual], lr, step_i, key, *batch) ->
    (loss, new_params, new_opt[, new_residual][, health]). The data-parallel
    region (accumulation scan + the one deferred collective) runs under
    shard_map; clip and the optimizer update run outside it under GSPMD, so
    ZeRO opt-state sharding composes unchanged (the grads are pinned to the
    param spec then the opt spec exactly as the single-shot step does).

    health_stats (observability/health.py make_packed_stats): optional
    in-program stats fn (grads, params, new_params) -> f32 [4P], appended
    as the LAST output. It receives the PRE-clip reduced mean grads — i.e.
    slices of the flat gradient buffer the collective just carried — so
    per-parameter attribution rides the flat-buffer segment map for free
    (no extra collectives, no extra dispatch).
    """
    axes = tuple(a for a in batch_axes if mesh.shape[a] > 1)
    d0 = _spec_axes(axes)

    def _local(params, key, residual, *lbatch):
        # lbatch: per-replica shards [B/nrep, ...] -> [k, B/(nrep*k), ...]
        mbs = tuple(b.reshape((k, b.shape[0] // k) + b.shape[1:])
                    for b in lbatch)
        zero_flat, unravel = ravel_pytree(
            {n: jnp.zeros(v.shape, jnp.float32) for n, v in params.items()})
        shard_key = key
        for ax in axes:  # decorrelate dropout streams across data replicas
            shard_key = jax.random.fold_in(shard_key,
                                           jax.lax.axis_index(ax))

        def body(carry, mb):
            acc, i = carry
            sub = jax.random.fold_in(shard_key, i)
            loss, g = jax.value_and_grad(
                lambda ps: compute_loss(ps, sub, *mb))(params)
            gflat, _ = ravel_pytree(g)
            return (acc + gflat.astype(jnp.float32), i + jnp.int32(1)), loss

        (acc, _), losses = jax.lax.scan(body, (zero_flat, jnp.int32(0)), mbs)
        res_in = residual[0] if residual is not None else None
        with jax.named_scope("grad_sync"):
            red, loss, res_out = _reduce_local(acc / k, losses.mean(), axes,
                                               dtype, chunk, res_in)
        if residual is not None:
            return unravel(red), loss, res_out[None]
        return unravel(red), loss

    def _dp_region(params, key, residual, batch):
        if not axes:
            return _local(params, key, residual, *batch)
        n_extra = 3 if residual is not None else 2
        in_specs = ((P(), P()) + ((P(d0),) if residual is not None else ())
                    + tuple(P(d0) for _ in batch))
        out_specs = ((P(), P(), P(d0)) if residual is not None
                     else (P(), P()))

        def region(params, key, *rest):
            if residual is not None:
                return _local(params, key, rest[0], *rest[1:])
            return _local(params, key, None, *rest)

        fn = jax.shard_map(region, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
        if residual is not None:
            return fn(params, key, residual, *batch)
        return fn(params, key, *batch)

    def _finish(params, opt_state, grads, lr, step_i):
        raw_grads = grads  # pre-clip: what health attribution must see
        if zero_specs is not None:
            # ZeRO boundary, same two-constraint chain as the single-shot
            # step (distributed/engine.py _raw_step): grads at the param
            # spec, then at the opt spec (the reduce-scatter transition)
            grads = {n: jax.lax.with_sharding_constraint(
                g, NamedSharding(mesh, param_specs[n]))
                for n, g in grads.items()}
            grads = {n: jax.lax.with_sharding_constraint(
                g, NamedSharding(mesh, zero_specs[n]))
                for n, g in grads.items()}
        from ..optimizer import functional as opt_funct

        with jax.named_scope("grad_clip"):
            grads = opt_funct.clip_grads(grads, clip)
        with jax.named_scope("optimizer"):
            new_params, new_opt = update(params, grads, opt_state, lr,
                                         step_i)
        if health_stats is None:
            return new_params, new_opt, None
        return new_params, new_opt, health_stats(raw_grads, params,
                                                 new_params)

    if use_residual:
        def step(params, opt_state, residual, lr, step_i, key, *batch):
            grads, loss, new_res = _dp_region(params, key, residual, batch)
            new_params, new_opt, aux = _finish(params, opt_state, grads, lr,
                                               step_i)
            if aux is None:
                return loss, new_params, new_opt, new_res
            return loss, new_params, new_opt, new_res, aux

        return step

    def step(params, opt_state, lr, step_i, key, *batch):
        grads, loss = _dp_region(params, key, None, batch)
        new_params, new_opt, aux = _finish(params, opt_state, grads, lr,
                                           step_i)
        if aux is None:
            return loss, new_params, new_opt
        return loss, new_params, new_opt, aux

    return step


def _clip_shard(g, clip, axes):
    """Grad clip on the local 1/N shard of the flat mean-grad buffer.
    ByValue is elementwise; ByGlobalNorm needs the global sum of squares —
    ONE scalar psum (4 bytes on the wire), not a full-buffer all-reduce
    (note: the cross-replica summation order differs from the replicated
    per-parameter clip, so globally-clipped runs match to fp tolerance, not
    bit-exactly). ByNorm needs per-parameter norms and is rejected upstream
    (the engine falls back to the replicated update)."""
    from ..nn.clip import ClipGradByGlobalNorm, ClipGradByValue

    if clip is None:
        return g
    if isinstance(clip, ClipGradByGlobalNorm):
        sq = jnp.sum(jnp.square(g))
        if axes:
            sq = jax.lax.psum(sq, axes)
        gn = jnp.sqrt(sq)
        return g * (clip.clip_norm / jnp.maximum(gn, clip.clip_norm))
    if isinstance(clip, ClipGradByValue):
        return jnp.clip(g, clip.min, clip.max)
    raise ValueError(f"unsupported grad clip for the ZeRO update: {clip!r}")


def make_zero_accum_step(*, compute_loss: Callable, flat_update: Callable,
                         clip, mesh: Mesh, batch_axes: Sequence[str], k: int,
                         dtype: str, chunk: int, use_residual: bool,
                         param_templates: Dict[str, jax.ShapeDtypeStruct],
                         health_partial: Optional[Callable] = None):
    """ZeRO-style cross-replica weight-update sharding (arXiv:2004.13336).

    Same accumulation scan as make_accum_step, but the post-scan reduction
    decomposes into **reduce-scatter -> shard-local clip + optimizer update
    -> all-gather of updated weights**: each data replica owns the
    contiguous 1/nrep shard of the flat f32 parameter/optimizer-state
    vector at offset r*shard (r = row-major replica index over
    ``batch_axes``, shard = n_pad/nrep — the same sorted-name segment order
    as observability.health.segment_layout, pinned by tests), runs the
    update on only its shard, and the updated weight shards gather back to
    the replicated layout the model expects. Per optimizer step the
    compiled HLO carries exactly ONE reduce-scatter and ONE all-gather
    independent of K (f32/bf16; int8 replaces the reduce-scatter with two
    all-to-alls carrying the EQuARX chunk-scaled payload + f32 scales) and
    ZERO full-buffer all-reduces.

    flat_update(p_shard, g_shard, opt_shards, lr, step_i) ->
    (new_p_shard, new_opt_shards): ONE uniform elementwise rule over f32
    [shard] vectors (engine._make_flat_update guarantees uniformity). The
    loss scalar and the health partials ride the all-gather slab:
    health_partial (health.make_sharded_stats) sees the PRE-clip gradient
    shard plus a segment-id shard, and its [4P] partial sums are summed
    over replicas in-program — the packed buffer the host decodes is
    layout-identical to the replicated path's.

    Error feedback (use_residual, bf16/int8 only) carries the local
    quantization error of the SCATTERED payload: the residual is computed
    against the local pre-collective buffer, exactly as the replicated
    low-precision path does.

    Returns step(params, opt_shards[, residual], lr, step_i, key, *batch)
    -> (loss, new_params, new_opt_shards[, new_residual][, health])."""
    if use_residual and dtype == "f32":
        raise ValueError("error feedback needs a low-precision dtype")
    ride_loss = dtype != "int8"   # f32/bf16: loss rides the scatter buffer
    axes = tuple(a for a in batch_axes if mesh.shape[a] > 1)
    d0 = _spec_axes(axes)
    nrep = replica_count(mesh, axes)
    names = sorted(param_templates)
    shapes = {nm: tuple(param_templates[nm].shape) for nm in names}
    dtypes = {nm: param_templates[nm].dtype for nm in names}
    sizes = [int(np.prod(shapes[nm]) or 1) for nm in names]
    offs = np.concatenate([[0], np.cumsum(sizes)]).astype(np.int64)
    n = int(offs[-1])
    n_pad = zero_pad_elems(n, nrep, chunk)
    shard = n_pad // nrep
    # flat-index -> parameter-ordinal map for the sharded health partials;
    # pad slots land in segment P and are dropped by make_sharded_stats
    seg_ids = None
    if health_partial is not None:
        seg_ids = np.full((n_pad,), len(names), np.int32)
        for i, (o, s) in enumerate(zip(offs[:-1], sizes)):
            seg_ids[o:o + s] = i

    def _flatten(params):
        return jnp.concatenate(
            [params[nm].astype(jnp.float32).reshape(-1) for nm in names])

    def _unflatten(flat):
        return {nm: flat[offs[i]:offs[i + 1]].reshape(shapes[nm])
                .astype(dtypes[nm]) for i, nm in enumerate(names)}

    def _scatter(buf):
        """The ONE gradient reduce-scatter: [n_pad] local partial-mean
        grads -> ([shard] reduced MEAN grad shard, new residual | None).
        With no collective axes this degrades to the identity plus the
        quantize/dequantize roundtrip, mirroring _reduce_local."""
        if dtype == "f32":
            g = (jax.lax.psum_scatter(buf, axes, scatter_dimension=0,
                                      tiled=True) if axes else buf)
            return g / nrep, None
        if dtype == "bf16":
            b = buf.astype(jnp.bfloat16)
            res = ((buf - b.astype(jnp.float32))[:n]
                   if use_residual else None)
            g = (jax.lax.psum_scatter(b, axes, scatter_dimension=0,
                                      tiled=True) if axes else b)
            return g.astype(jnp.float32) / nrep, res
        # int8: quantized reduce-scatter built from all-to-all — replica i
        # keeps only the chunk rows of its own shard, every peer's scales
        # survive the trip (EQuARX block scaling), dequant-sum in f32
        q, scale = _quantize_int8(buf, chunk)      # [n_pad/chunk, chunk]
        res = ((buf - _dequantize_int8(q, scale, n_pad))[:n]
               if use_residual else None)
        qs = q.reshape((nrep, shard // chunk, chunk))
        ss = scale.reshape((nrep, shard // chunk))
        if axes:
            qs = jax.lax.all_to_all(qs, axes, split_axis=0, concat_axis=0)
            ss = jax.lax.all_to_all(ss, axes, split_axis=0, concat_axis=0)
        g = jnp.sum(qs.astype(jnp.float32) * ss[..., None], axis=0)
        return g.reshape(shard) / nrep, res

    def _local(params, lr, step_i, key, residual, opt, *lbatch):
        mbs = tuple(b.reshape((k, b.shape[0] // k) + b.shape[1:])
                    for b in lbatch)
        zero_flat, _ = ravel_pytree(
            {nm: jnp.zeros(v.shape, jnp.float32)
             for nm, v in params.items()})
        shard_key = key
        for ax in axes:  # decorrelate dropout streams across data replicas
            shard_key = jax.random.fold_in(shard_key,
                                           jax.lax.axis_index(ax))

        def body(carry, mb):
            acc, i = carry
            sub = jax.random.fold_in(shard_key, i)
            loss, g = jax.value_and_grad(
                lambda ps: compute_loss(ps, sub, *mb))(params)
            gflat, _ = ravel_pytree(g)
            return (acc + gflat.astype(jnp.float32), i + jnp.int32(1)), loss

        (acc, _), losses = jax.lax.scan(body, (zero_flat, jnp.int32(0)), mbs)
        flat = acc / k
        if residual is not None:
            flat = flat + residual[0]
        buf = jnp.pad(flat, (0, n_pad - n))
        if ride_loss:
            # f32/bf16: the local mean loss rides the reduce-scatter in pad
            # slot n (zero_pad_elems guarantees the spare) — the SAME
            # reduction+divide the grads take, so the final loss is
            # bit-identical to the replicated path's psum'd loss. int8 must
            # not quantize it; there it rides the gather slab in f32.
            buf = buf.at[n].set(losses.mean())
        with jax.named_scope("grad_sync"):
            g_shard, new_res = _scatter(buf)
        # own-shard offset: row-major replica index over the batch axes —
        # the order psum_scatter/all_gather tile in (pinned by tests)
        r = jnp.int32(0)
        for ax in axes:
            r = r * jnp.int32(mesh.shape[ax]) + jax.lax.axis_index(ax)
        if ride_loss:
            # extract the reduced loss from whichever replica owns slot n
            # (zero elsewhere: the gather-slab sum stays exact) and zero it
            # out of the grad shard before clip/update
            loss_mask = (r * jnp.int32(shard)
                         + jnp.arange(shard, dtype=jnp.int32)) == n
            loss_part = jnp.sum(jnp.where(loss_mask, g_shard, 0.0))
            g_shard = jnp.where(loss_mask, 0.0, g_shard)
        else:
            loss_part = losses.mean()
        p_shard = jax.lax.dynamic_slice(
            jnp.pad(_flatten(params), (0, n_pad - n)),
            (r * jnp.int32(shard),), (shard,))
        raw_g = g_shard                     # pre-clip: health attribution
        with jax.named_scope("grad_clip"):
            g_shard = _clip_shard(g_shard, clip, axes)
        with jax.named_scope("optimizer"):
            new_p_shard, new_opt = flat_update(p_shard, g_shard, tuple(opt),
                                               lr, step_i)
        extras = [loss_part[None]]
        if health_partial is not None:
            ids_shard = jax.lax.dynamic_slice(
                jnp.asarray(seg_ids), (r * jnp.int32(shard),), (shard,))
            extras.append(health_partial(raw_g, p_shard, new_p_shard,
                                         ids_shard))
        # ONE all-gather: [updated weight shard | loss | health partials],
        # decoded by reshaping to one row per replica. ride_loss rows carry
        # the already-reduced loss on the owner replica and exact zeros
        # elsewhere (summing is exact); int8 rows carry local mean losses.
        slab = jnp.concatenate([new_p_shard] + extras)
        if axes:
            with jax.named_scope("fsdp_gather"):
                rows = jax.lax.all_gather(slab, axes, tiled=True).reshape(
                    (nrep, slab.shape[0]))
            new_flat = rows[:, :shard].reshape(-1)[:n]
            loss = jnp.sum(rows[:, shard])
            if not ride_loss:
                loss = loss / nrep
            hbuf = (jnp.sum(rows[:, shard + 1:], axis=0)
                    if health_partial is not None else None)
        else:
            new_flat = new_p_shard[:n]
            loss = loss_part
            hbuf = extras[1] if health_partial is not None else None
        outs = (new_flat, loss, tuple(new_opt))
        if use_residual:
            outs += (new_res[None],)
        if health_partial is not None:
            outs += (hbuf,)
        return outs

    def _region_call(params, lr, step_i, key, residual, opt, batch):
        if not axes:
            return _local(params, lr, step_i, key, residual, opt, *batch)
        in_specs = ((P(), P(), P(), P())
                    + ((P(d0),) if use_residual else ())
                    + (P(d0),)                 # flat opt-state shards
                    + tuple(P(d0) for _ in batch))
        out_specs = (P(), P(), P(d0))
        if use_residual:
            out_specs += (P(d0),)
        if health_partial is not None:
            out_specs += (P(),)

        def region(params, lr, step_i, key, *rest):
            if use_residual:
                return _local(params, lr, step_i, key, rest[0], rest[1],
                              *rest[2:])
            return _local(params, lr, step_i, key, None, rest[0], *rest[1:])

        fn = jax.shard_map(region, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
        if use_residual:
            return fn(params, lr, step_i, key, residual, tuple(opt), *batch)
        return fn(params, lr, step_i, key, tuple(opt), *batch)

    if use_residual:
        def step(params, opt_shards, residual, lr, step_i, key, *batch):
            outs = _region_call(params, lr, step_i, key, residual,
                                opt_shards, batch)
            ret = (outs[1], _unflatten(outs[0]), outs[2], outs[3])
            if health_partial is not None:
                ret += (outs[4],)
            return ret

        return step

    def step(params, opt_shards, lr, step_i, key, *batch):
        outs = _region_call(params, lr, step_i, key, None, opt_shards, batch)
        ret = (outs[1], _unflatten(outs[0]), outs[2])
        if health_partial is not None:
            ret += (outs[3],)
        return ret

    return step


def default_layer_key(name: str) -> str:
    """Fallback per-layer fsdp bucket key: the parameter's owning module
    path (everything before the final attribute), so e.g. a Linear's weight
    and bias share one bucket. Models override by defining an
    ``fsdp_layer_key(name)`` method that groups at the granularity whose
    gather should hide under the previous layer's compute (models/gpt.py
    groups one transformer block per bucket)."""
    return name.rsplit(".", 1)[0] if "." in name else name


def fsdp_buckets(param_shapes: Dict[str, Sequence[int]], nrep: int,
                 chunk: int, layer_key: Optional[Callable] = None):
    """Per-layer bucket layout of the sorted-name flat parameter vector.

    Walks the names in sorted order (== ravel_pytree dict flatten order ==
    health.segment_layout) and cuts a bucket at every change of the layer
    key — buckets are maximal contiguous RUNS, so a key that reappears
    later in the order simply opens another bucket and every bucket stays a
    contiguous slice of the flat vector. Each bucket pads to a multiple of
    nrep*chunk (equal per-replica shards AND an exact int8 chunk grid);
    these are the per-layer all-gather boundaries of the fsdp step. Returns
    dicts: {key, names, off (flat offset of the first real element),
    n (real elements), pad (padded length), shard (pad // nrep)}."""
    key_fn = layer_key or default_layer_key
    unit = max(1, nrep) * max(1, chunk)
    buckets: list = []
    off = 0
    for nm in sorted(param_shapes):
        key = str(key_fn(nm))
        size = int(np.prod(tuple(param_shapes[nm])) or 1)
        if not buckets or key != buckets[-1]["key"]:
            buckets.append({"key": key, "names": [], "off": off, "n": 0})
        buckets[-1]["names"].append(nm)
        buckets[-1]["n"] += size
        off += size
    for b in buckets:
        b["pad"] = -(-b["n"] // unit) * unit
        b["shard"] = b["pad"] // max(1, nrep)
    return buckets


def fsdp_payload_bytes(shard_elems: Sequence[int], nrep: int, dtype: str,
                       chunk: int) -> Tuple[int, int, list]:
    """(reduce_scatter_bytes, all_gather_bytes, per_layer_ag_bytes) per
    device per step for the fsdp path — the local contribution handed to
    each collective, the payload_bytes convention. The gather leg is L
    per-bucket f32 weight-shard gathers (there is NO trailing full-
    parameter gather — that is the arg-bytes win over ZeRO); the scatter
    leg carries the bucket-padded grads plus one aux loss column per
    replica row (int8: the aux column rides the f32 scales exchange)."""
    nrep = max(1, nrep)
    s_total = int(sum(shard_elems))
    if dtype == "f32":
        rs = nrep * (s_total + 1) * 4
    elif dtype == "bf16":
        rs = nrep * (s_total + 1) * 2
    else:  # int8 payload + one f32 scale per chunk + the aux loss column
        rs = nrep * s_total * 1 + nrep * (s_total // chunk + 1) * 4
    per_layer = [int(s) * 4 for s in shard_elems]
    return rs, sum(per_layer), per_layer


def fsdp_window_bytes(buckets: Sequence[dict], depth: int) -> int:
    """Analytic live-gathered bytes of a depth-``depth`` fsdp prefetch
    window: the max over window positions of the summed FULL (padded, f32)
    gathered bucket bytes held live at once — while bucket i's compute
    runs, buckets i..i+depth-1 are gathered. Depth 0 and 1 both hold one
    bucket (just-in-time); the default depth 2 holds the worst adjacent
    pair. This is the bound the exec.train.fsdp_* window-bytes gauge
    reports and tools/mem_report.py checks against measured temp bytes."""
    gb = [int(b["pad"]) * 4 for b in buckets]
    if not gb:
        return 0
    d = max(1, min(int(depth), len(gb)))
    return max(sum(gb[i:i + d]) for i in range(len(gb) - d + 1))


def fsdp_prefetch_ahead_bytes(buckets: Sequence[dict], depth: int) -> int:
    """Analytic EXTRA resident bytes a depth-``depth`` window holds vs the
    just-in-time baseline: the raw gathered buffers of buckets 1..depth-1
    (f32, padded) stay live across the whole microbatch scan — the step fn
    pins them with a post-scan read, so this delta is exactly measurable
    as depth-d temp bytes minus depth-0 temp bytes on the SAME model
    (tools/mem_report.py hard-asserts it). For the canonical two-bucket
    report model this is the second bucket's gather size. 0 below depth
    2."""
    if int(depth) < 2:
        return 0
    return sum(int(b["pad"]) * 4 for b in buckets[1:int(depth)])


def fsdp_prefetch_depth(buckets: Sequence[dict], requested: int) -> int:
    """Clamp the requested gather-prefetch depth so the live window never
    exceeds the two largest adjacent gathered buckets (the double-buffer
    byte bound): the largest d <= requested whose fsdp_window_bytes fits
    under the depth-2 window. <= 0 stays 0 (just-in-time, no barriers)."""
    d = min(int(requested), max(1, len(buckets)))
    if d <= 0:
        return 0
    cap = fsdp_window_bytes(buckets, 2)
    while d > 2 and fsdp_window_bytes(buckets, d) > cap:
        d -= 1
    return d


def make_fsdp_accum_step(*, compute_loss: Callable, flat_update: Callable,
                         clip, mesh: Mesh, batch_axes: Sequence[str], k: int,
                         dtype: str, chunk: int, use_residual: bool,
                         param_templates: Dict[str, jax.ShapeDtypeStruct],
                         buckets: Sequence[dict], prefetch: int = 0,
                         health_partial: Optional[Callable] = None):
    """Fully sharded data parallelism (arXiv:2004.13336 taken the rest of
    the way): parameters arrive as per-layer flat f32 SHARDS and leave the
    same way — no replicated copy exists between steps.

    Inside the compiled step, each bucket's weight shard is all-gathered
    just before the forward/backward consumes it (L independent per-layer
    gathers issued up front, so XLA's scheduler can hide each one under a
    neighbouring bucket's compute), the accumulation scan runs against the
    gathered view, and the post-scan reduction is ONE reduce-scatter over
    the bucket-shard-major permutation of the flat gradient buffer — each
    replica receives exactly the mean-grad slices for the shards it owns.
    Clip + the uniform elementwise optimizer rule then run per bucket on
    shard-local state and the updated shards are simply RETURNED: unlike
    the ZeRO step there is no trailing parameter all-gather, which is what
    drops per-device parameter residency to ~1/nrep. Per optimizer step
    the HLO carries exactly L all-gathers + 1 reduce-scatter (f32/bf16;
    int8 swaps the reduce-scatter for two all-to-alls of EQuARX payload +
    scales) and ZERO full-buffer all-reduces, independent of K.

    Bit-exactness vs the replicated trajectory at f32 rides on the same
    property the ZeRO step pinned: psum_scatter(tiled)'s per-element
    reduction order matches psum, and the permutation only relabels
    positions. The loss rides an aux column every replica writes
    identically into every destination row, so the scattered sum IS the
    global sum. Health partials can't ride a gather slab here (there is
    none, and they need the post-update shard), so each replica emits its
    [4P] segment partial as a sharded [nrep, 4P] output the engine sums
    host-side — zero extra collectives.

    With ``prefetch`` depth d >= 2 the gathers run under an overlap-ahead
    window: bucket i's gathered view is released through a value-identity
    select pin tied to the all-gathers for buckets i+1..i+d-1, so every
    consumer of bucket i carries a REAL data dependency on the next
    window's gathers — any valid schedule issues AG(i+1) before bucket i's
    compute (double-buffered at d=2), which is exactly what the
    schedule-order analysis contract reads out of the optimized HLO. The
    backward pass mirrors the window on the per-bucket cotangents in
    DESCENDING bucket order (bucket i's grads release together with
    buckets i-1..i-d+1's). The depth is clamped by fsdp_prefetch_depth so
    live-gathered bytes never exceed the two largest adjacent buckets.
    Both pins are identity on values: every depth is bit-equal to depth 0.

    Returns step(p_shards, opt_shards[, residual], lr, step_i, key, *batch)
    -> (loss, new_p_shards, new_opt_shards[, new_residual][, health])."""
    if use_residual and dtype == "f32":
        raise ValueError("error feedback needs a low-precision dtype")
    axes = tuple(a for a in batch_axes if mesh.shape[a] > 1)
    depth = fsdp_prefetch_depth(buckets, prefetch) if axes else 0
    d0 = _spec_axes(axes)
    nrep = replica_count(mesh, axes)
    names = sorted(param_templates)
    shapes = {nm: tuple(param_templates[nm].shape) for nm in names}
    dtypes = {nm: param_templates[nm].dtype for nm in names}
    sizes = {nm: int(np.prod(shapes[nm]) or 1) for nm in names}
    assert [nm for b in buckets for nm in b["names"]] == names
    n = sum(sizes.values())
    s_total = sum(b["shard"] for b in buckets)       # local elems per replica
    soffs = np.concatenate(
        [[0], np.cumsum([b["shard"] for b in buckets])]).astype(np.int64)
    poffs = np.concatenate(
        [[0], np.cumsum([b["pad"] for b in buckets])]).astype(np.int64)
    # flat-index -> parameter-ordinal map per replica row (bucket-shard
    # order); pad slots land in segment P and are dropped by the partial
    seg_ids = None
    if health_partial is not None:
        ordinal = {nm: i for i, nm in enumerate(names)}
        seg_ids = np.full((nrep, s_total), len(names), np.int32)
        for bi, b in enumerate(buckets):
            ids_b = np.full((b["pad"],), len(names), np.int32)
            o = 0
            for nm in b["names"]:
                ids_b[o:o + sizes[nm]] = ordinal[nm]
                o += sizes[nm]
            seg_ids[:, soffs[bi]:soffs[bi + 1]] = ids_b.reshape(
                nrep, b["shard"])

    def _gather_params(p_shards, step_i):
        """L per-bucket all-gathers -> the replicated param dict the
        forward/backward consumes. tiled=True concatenates replica shards
        in row-major replica order — the inverse of the reshape(nrep, shard)
        the scatter side uses, so the contiguous bucket reassembles.

        With prefetch depth >= 2 each gathered bucket is RELEASED through
        a value-identity select pin tied to the NEXT window's gathers:
        ``step_i >= INT32_MIN`` is true for every possible step index, but
        a runtime comparison cannot be constant-folded, so the (never
        taken) other branch makes AG(i+1..i+depth-1) REAL operands of
        bucket i's consumers — every valid schedule, including the
        sequential one the schedule-order contract reads out of the
        optimized HLO, must issue the next bucket's gather before the
        current bucket's compute. (A plain optimization_barrier does not
        survive here: XLA expands barriers before scheduling, so they
        leave no trace in the scheduled module.) Depth 0 emits the bare
        just-in-time gathers of PR 19.

        Returns (params, hold): `hold` is the list of raw gathered
        buffers the window keeps ahead of the first bucket's compute
        (fulls[1:depth]) — the caller pins them live across the
        microbatch scan, which is what makes the analytic window delta
        measurable in the executable's temp bytes."""
        fulls = [jax.lax.all_gather(pl, axes, tiled=True) if axes else pl
                 for pl in p_shards]
        hold = list(fulls[1:depth]) if depth >= 2 else []
        if depth >= 2:
            ok = step_i >= jnp.int32(-2 ** 31)
            pinned = []
            for i, f in enumerate(fulls):
                ahead = fulls[i + 1:i + depth]
                if ahead:
                    probe = sum(a[0] for a in ahead)
                    f = jnp.where(ok, f, jnp.broadcast_to(probe, f.shape))
                pinned.append(f)
            fulls = pinned
        params = {}
        for b, full in zip(buckets, fulls):
            o = 0
            for nm in b["names"]:
                params[nm] = (full[o:o + sizes[nm]].reshape(shapes[nm])
                              .astype(dtypes[nm]))
                o += sizes[nm]
        return params, hold

    @jax.custom_vjp
    def _window_mirror(params):
        return params

    def _window_mirror_fwd(params):
        return params, None

    def _window_mirror_bwd(_, ct):
        # backward twin of the gather window: the backward pass walks the
        # buckets in descending order, so bucket i's param cotangents are
        # released only together with buckets i-1..i-depth+1's — bucket
        # i-1's grad work is forced live under bucket i's grad consumption,
        # mirroring the forward prefetch. Identity on values.
        groups = [[ct[nm] for nm in b["names"]] for b in buckets]
        for i in range(len(groups) - 1, 0, -1):
            behind = [x for g in groups[max(0, i - depth + 1):i] for x in g]
            if behind:
                out = jax.lax.optimization_barrier(
                    tuple(groups[i]) + tuple(behind))
                groups[i] = list(out[:len(groups[i])])
        return ({nm: x for b, g in zip(buckets, groups)
                 for nm, x in zip(b["names"], g)},)

    _window_mirror.defvjp(_window_mirror_fwd, _window_mirror_bwd)

    def _rows(flat):
        """[n] grads in global (sorted-name) order -> [nrep, s_total]
        destination-major rows: row r holds replica r's shard of every
        bucket, in bucket order — the layout psum_scatter(tiled) scatters
        by."""
        segs = []
        for b in buckets:
            seg = jnp.pad(flat[b["off"]:b["off"] + b["n"]],
                          (0, b["pad"] - b["n"]))
            segs.append(seg.reshape(nrep, b["shard"]))
        return jnp.concatenate(segs, axis=1)

    def _scatter(flat, local_loss):
        """The ONE gradient reduce-scatter: [n] f32 local partial-mean
        grads -> ([s_total] reduced MEAN grad shards in bucket-shard order,
        reduced mean loss, new residual [n] | None). Every replica writes
        its local mean loss into the aux column of EVERY destination row,
        so each scattered slice carries the full cross-replica loss sum.
        With no collective axes this degrades to the identity plus the
        quantize/dequantize roundtrip, mirroring the ZeRO _scatter."""
        if dtype == "f32":
            buf = jnp.concatenate(
                [_rows(flat),
                 jnp.full((nrep, 1), local_loss, jnp.float32)],
                axis=1).reshape(-1)
            out = (jax.lax.psum_scatter(buf, axes, scatter_dimension=0,
                                        tiled=True) if axes else buf)
            return out[:s_total] / nrep, out[s_total] / nrep, None
        if dtype == "bf16":
            b16 = flat.astype(jnp.bfloat16)
            res = flat - b16.astype(jnp.float32) if use_residual else None
            buf = jnp.concatenate(
                [_rows(b16),
                 jnp.full((nrep, 1), local_loss, jnp.bfloat16)],
                axis=1).reshape(-1)
            out = (jax.lax.psum_scatter(buf, axes, scatter_dimension=0,
                                        tiled=True) if axes else buf)
            out = out.astype(jnp.float32)
            return out[:s_total] / nrep, out[s_total] / nrep, res
        # int8: quantized reduce-scatter from two all-to-alls over the
        # bucket-padded buffer (every bucket pad is a chunk multiple, so
        # the chunk grid tiles each bucket exactly); the f32 aux loss
        # column rides the scales exchange and dequant-sum reduces it
        padbuf = jnp.concatenate(
            [jnp.pad(flat[b["off"]:b["off"] + b["n"]],
                     (0, b["pad"] - b["n"])) for b in buckets])
        q, scale = _quantize_int8(padbuf, chunk)
        res = None
        if use_residual:
            err = padbuf - _dequantize_int8(q, scale, padbuf.shape[0])
            res = jnp.concatenate(
                [err[poffs[i]:poffs[i] + b["n"]]
                 for i, b in enumerate(buckets)])
        qs = jnp.concatenate(
            [q[poffs[i] // chunk:poffs[i + 1] // chunk]
             .reshape(nrep, b["shard"] // chunk, chunk)
             for i, b in enumerate(buckets)], axis=1)
        ss = jnp.concatenate(
            [scale[poffs[i] // chunk:poffs[i + 1] // chunk]
             .reshape(nrep, b["shard"] // chunk)
             for i, b in enumerate(buckets)], axis=1)
        ss = jnp.concatenate(
            [ss, jnp.full((nrep, 1), local_loss, jnp.float32)], axis=1)
        if axes:
            qs = jax.lax.all_to_all(qs, axes, split_axis=0, concat_axis=0)
            ss = jax.lax.all_to_all(ss, axes, split_axis=0, concat_axis=0)
        g = jnp.sum(qs.astype(jnp.float32) * ss[:, :s_total // chunk, None],
                    axis=0).reshape(s_total)
        return g / nrep, jnp.sum(ss[:, -1]) / nrep, res

    def _local(p_shards, lr, step_i, key, residual, opt, *lbatch):
        with jax.named_scope("fsdp_gather"):
            params, window_hold = _gather_params(p_shards, step_i)
        mbs = tuple(b.reshape((k, b.shape[0] // k) + b.shape[1:])
                    for b in lbatch)
        zero_flat, _ = ravel_pytree(
            {nm: jnp.zeros(v.shape, jnp.float32)
             for nm, v in params.items()})
        shard_key = key
        for ax in axes:  # decorrelate dropout streams across data replicas
            shard_key = jax.random.fold_in(shard_key,
                                           jax.lax.axis_index(ax))

        def body(carry, mb):
            acc, i = carry
            sub = jax.random.fold_in(shard_key, i)
            loss, g = jax.value_and_grad(
                lambda ps: compute_loss(
                    _window_mirror(ps) if depth >= 2 else ps, sub, *mb)
            )(params)
            gflat, _ = ravel_pytree(g)
            return (acc + gflat.astype(jnp.float32), i + jnp.int32(1)), loss

        (acc, _), losses = jax.lax.scan(body, (zero_flat, jnp.int32(0)), mbs)
        flat = acc / k
        if residual is not None:
            flat = flat + residual[0]
        with jax.named_scope("grad_sync"):
            g_all, loss, new_res = _scatter(flat, losses.mean())
        if window_hold:
            # keep the window's ahead-gathered buffers resident across the
            # microbatch scan: the dead select branch reads each buffer at
            # an index only known after the loss exists, so XLA cannot
            # hoist the read before the while loop or free the buffers
            # under it. This is what tools/mem_report.py measures as the
            # depth-0 -> depth-2 temp-byte delta (fsdp_prefetch_ahead_bytes
            # analytically). Identity on values: the pin branch never runs.
            idx = jnp.clip(jnp.asarray(loss * 0).astype(jnp.int32), 0, 0)
            probe = sum(jax.lax.dynamic_index_in_dim(f, idx, keepdims=False)
                        for f in window_hold)
            loss = jnp.where(step_i >= jnp.int32(-2 ** 31), loss,
                             probe.astype(loss.dtype))
        raw_g = g_all                       # pre-clip: health attribution
        with jax.named_scope("grad_clip"):
            g_all = _clip_shard(g_all, clip, axes)
        new_ps = []
        new_opt_cols = [[] for _ in opt]
        for i, b in enumerate(buckets):
            g_b = g_all[soffs[i]:soffs[i + 1]]
            opt_b = tuple(slot[i] for slot in opt)
            with jax.named_scope("optimizer"):
                new_p_b, new_opt_b = flat_update(p_shards[i], g_b, opt_b,
                                                 lr, step_i)
            new_ps.append(new_p_b)
            for j, col in enumerate(new_opt_b):
                new_opt_cols[j].append(col)
        outs = (loss, tuple(new_ps),
                tuple(tuple(col) for col in new_opt_cols))
        if use_residual:
            outs += (new_res[None],)
        if health_partial is not None:
            r = jnp.int32(0)
            for ax in axes:
                r = r * jnp.int32(mesh.shape[ax]) + jax.lax.axis_index(ax)
            ids = jax.lax.dynamic_slice(
                jnp.asarray(seg_ids), (r, jnp.int32(0)), (1, s_total))[0]
            hp = health_partial(raw_g, jnp.concatenate(list(p_shards)),
                                jnp.concatenate(new_ps), ids)
            outs += (hp[None],)             # [1, 4P] row per replica
        return outs

    def _region_call(p_shards, lr, step_i, key, residual, opt, batch):
        if not axes:
            return _local(p_shards, lr, step_i, key, residual, opt, *batch)
        in_specs = ((P(d0), P(), P(), P())  # per-bucket weight shards first
                    + ((P(d0),) if use_residual else ())
                    + (P(d0),)              # per-slot per-bucket opt shards
                    + tuple(P(d0) for _ in batch))
        out_specs = (P(), P(d0), P(d0))
        if use_residual:
            out_specs += (P(d0),)
        if health_partial is not None:
            out_specs += (P(d0),)           # per-replica health rows

        def region(p_shards, lr, step_i, key, *rest):
            if use_residual:
                return _local(p_shards, lr, step_i, key, rest[0], rest[1],
                              *rest[2:])
            return _local(p_shards, lr, step_i, key, None, rest[0],
                          *rest[1:])

        fn = jax.shard_map(region, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
        if use_residual:
            return fn(tuple(p_shards), lr, step_i, key, residual,
                      tuple(opt), *batch)
        return fn(tuple(p_shards), lr, step_i, key, tuple(opt), *batch)

    if use_residual:
        def step(p_shards, opt_shards, residual, lr, step_i, key, *batch):
            return _region_call(p_shards, lr, step_i, key, residual,
                                opt_shards, batch)

        return step

    def step(p_shards, opt_shards, lr, step_i, key, *batch):
        return _region_call(p_shards, lr, step_i, key, None, opt_shards,
                            batch)

    return step


def make_accum_step_gspmd(*, compute_loss: Callable, update: Callable, clip,
                          mesh: Mesh, k: int, batch_specs: Sequence[P],
                          param_specs: Optional[Dict[str, P]] = None,
                          zero_specs: Optional[Dict[str, P]] = None,
                          health_stats: Optional[Callable] = None):
    """Hybrid-mesh (mp/sp) fallback: GSPMD accumulation scan. Still ONE
    compiled dispatch per optimizer step with a microbatch-sized activation
    peak and an f32 accumulator, but the partitioner inserts its own fused
    gradient reduction per microbatch (K combined all-reduces, not 1) and
    the low-precision knob does not apply — the collectives are implicit.
    health_stats appends the packed f32 [4P] stats buffer as the last
    output, same contract as make_accum_step."""

    def step(params, opt_state, lr, step_i, key, *batch):
        mbs = []
        for b, spec in zip(batch, batch_specs):
            r = b.reshape((k, b.shape[0] // k) + b.shape[1:])
            mbs.append(jax.lax.with_sharding_constraint(
                r, NamedSharding(mesh, P(None, *spec))))
        zero_flat, unravel = ravel_pytree(
            {n: jnp.zeros(v.shape, jnp.float32) for n, v in params.items()})

        def body(carry, mb):
            acc, i = carry
            sub = jax.random.fold_in(key, i)
            loss, g = jax.value_and_grad(
                lambda ps: compute_loss(ps, sub, *mb))(params)
            gflat, _ = ravel_pytree(g)
            return (acc + gflat.astype(jnp.float32), i + jnp.int32(1)), loss

        (acc, _), losses = jax.lax.scan(body, (zero_flat, jnp.int32(0)),
                                        tuple(mbs))
        grads = unravel(acc / k)
        raw_grads = grads
        if zero_specs is not None:
            grads = {n: jax.lax.with_sharding_constraint(
                g, NamedSharding(mesh, param_specs[n]))
                for n, g in grads.items()}
            grads = {n: jax.lax.with_sharding_constraint(
                g, NamedSharding(mesh, zero_specs[n]))
                for n, g in grads.items()}
        from ..optimizer import functional as opt_funct

        with jax.named_scope("grad_clip"):
            grads = opt_funct.clip_grads(grads, clip)
        with jax.named_scope("optimizer"):
            new_params, new_opt = update(params, grads, opt_state, lr,
                                         step_i)
        if health_stats is None:
            return losses.mean(), new_params, new_opt
        return losses.mean(), new_params, new_opt, health_stats(
            raw_grads, params, new_params)

    return step
