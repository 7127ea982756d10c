"""Pallas TPU flash-attention kernel (forward + backward).

This is the TPU-native replacement for the reference's fused CUDA attention
(`paddle/fluid/operators/fused/fused_attention_op.cu`, `fmha` kernels): an
online-softmax tiled attention that never materializes the [s, s] score matrix,
keeping the working set in VMEM and the two matmuls per tile on the MXU.

Layout: [b, h, s, d] inside the kernels (batch*heads collapsed into one grid
dim). The public entry `flash_attention` takes paddle's [b, s, h, d].

Backward follows the FlashAttention-2 scheme: forward saves per-row
logsumexp; backward recomputes P tile-by-tile, with one kernel producing
dK/dV (kv-block outer loop) and one producing dQ (q-block outer loop).

On CPU (tests) the kernels run in Pallas interpret mode.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from ._common import I0 as _I0, NEG_INF, attention_partition, \
    interpret as _interpret, pick_block as _pick_block, vmem as _vmem


def supported(seq_q: int, seq_k: int, head_dim: int) -> bool:
    """Shapes the kernel handles; callers fall back to the XLA path otherwise.

    The picked block is the sublane dim of the q/k tiles, so it must be a
    multiple of 8 — _pick_block falls back to the raw length for
    primes/unaligned lengths, which Mosaic would reject at compile time.
    Eight rows is enough for bf16 too, although its native sublane tile is
    16: on libtpu 0.0.34 the three kernels compile at 8-row bf16 blocks
    (seq 136, 152) and agree with dense attention as closely as at 512
    (chip run, PR 21).
    """
    return (
        seq_q >= 8
        and seq_k >= 8
        and _pick_block(seq_q) % 8 == 0
        and _pick_block(seq_k) % 8 == 0
        and head_dim % 8 == 0
    )


# ---------------------------------------------------------------- forward ----

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, sm_scale, causal, block_q, block_k, kv_blocks):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # causal: a kv block strictly above the diagonal contributes nothing
    run = (qi + 1) * block_q > ki * block_k if causal else True

    @pl.when(run)
    def _compute():
        # matmul inputs stay in their storage dtype (bf16 under amp) so the MXU
        # runs at bf16 rate; accumulation is forced to f32 via
        # preferred_element_type — casting inputs to f32 here would quarter
        # matmul throughput on v5e for no accuracy gain over f32 accumulation.
        q = q_ref[0]  # [bq, d]
        k = k_ref[0]  # [bk, d]
        v = v_ref[0]  # [bk, d]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale  # [bq, bk] f32
        if causal:
            qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(qpos >= kpos, s, jnp.float32(NEG_INF))

        m_prev = m_scr[...][:, :1]                      # [bq, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)       # [bq, 1]
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                          # [bq, bk]
        alpha = jnp.exp(m_prev - m_new)                 # [bq, 1]
        l_new = alpha * l_scr[...][:, :1] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == kv_blocks - 1)
    def _finalize():
        l = l_scr[...][:, :1]
        # fully-masked rows -> zeros, not NaN. ones_like (not a python 1.0
        # literal): under jax_enable_x64 the weak literal promotes through
        # f64 and Mosaic has no f64->f32 cast — caught by the TPU-export gate
        l = jnp.where(l == 0.0, jnp.ones_like(l), l)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)
        # lse broadcast across the 128-lane dim (TPU block layout for row stats)
        lse_ref[0] = jnp.broadcast_to(m_scr[...][:, :1] + jnp.log(l), lse_ref.shape[1:])


def _fwd(q, k, v, sm_scale, causal, blocks=None):
    """q,k,v: [bh, s, d] -> (o [bh, sq, d], lse [bh, sq] f32)."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    bq, bk = blocks if blocks else (_pick_block(sq), _pick_block(sk))
    kv_blocks = sk // bk
    grid = (bh, sq // bq, kv_blocks)
    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal,
        block_q=bq, block_k=bk, kv_blocks=kv_blocks)
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, _I0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, _I0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, _I0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, _I0)),
            pl.BlockSpec((1, bq, 128), lambda b, i, j: (b, i, _I0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, sq, 128), jnp.float32),
        ],
        scratch_shapes=[_vmem((bq, 128)), _vmem((bq, 128)), _vmem((bq, d))],
        interpret=_interpret(),
        # the name reaches the device trace twice: as an element of the op
        # path (observability/device_trace.py reads kernels by it) and as
        # the instruction's name (recorded on the chip, PR 26)
        name="flash_fwd",
    )(q, k, v)
    return o, lse


# --------------------------------------------------------------- backward ----

def _bwd_dkdv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                     dk_ref, dv_ref, dk_scr, dv_scr,
                     *, sm_scale, causal, block_q, block_k, q_blocks):
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    run = (qi + 1) * block_q > ki * block_k if causal else True

    @pl.when(run)
    def _compute():
        # storage-dtype (bf16) matmul inputs + f32 accumulation, as in forward
        q = q_ref[0]                            # [bq, d]
        k = k_ref[0]                            # [bk, d]
        v = v_ref[0]                            # [bk, d]
        do = do_ref[0]                          # [bq, d]
        lse = lse_ref[0][:, :1]                 # [bq, 1]
        delta = delta_ref[0][:, :1]             # [bq, 1]

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        if causal:
            qpos = ki * 0 + qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(qpos >= kpos, s, jnp.float32(NEG_INF))
        p = jnp.exp(s - lse)                    # [bq, bk] f32
        dv_scr[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale
        dk_scr[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == q_blocks - 1)
    def _finalize():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_scr,
                   *, sm_scale, causal, block_q, block_k, kv_blocks):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    run = (qi + 1) * block_q > ki * block_k if causal else True

    @pl.when(run)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][:, :1]
        delta = delta_ref[0][:, :1]

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        if causal:
            qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(qpos >= kpos, s, jnp.float32(NEG_INF))
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale
        dq_scr[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == kv_blocks - 1)
    def _finalize():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _bwd(res, g, sm_scale, causal, blocks=None, g_lse=None):
    q, k, v, o, lse = res
    do = g
    bh, sq, d = q.shape
    sk = k.shape[1]
    bq, bk = blocks if blocks else (_pick_block(sq), _pick_block(sk))
    q_blocks, kv_blocks = sq // bq, sk // bk

    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    if g_lse is not None:
        # lse cotangent folds into delta: dS = P*(dP - delta) + P*g_lse
        #                                    = P*(dP - (delta - g_lse))
        delta = delta - g_lse.astype(jnp.float32)
    delta = jnp.broadcast_to(delta[..., None], (bh, sq, 128))  # lane-broadcast layout

    dkdv_kernel = functools.partial(
        _bwd_dkdv_kernel, sm_scale=sm_scale, causal=causal,
        block_q=bq, block_k=bk, q_blocks=q_blocks)
    dk, dv = pl.pallas_call(
        dkdv_kernel,
        grid=(bh, kv_blocks, q_blocks),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, j, i: (b, i, _I0)),   # q
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, _I0)),   # k
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, _I0)),   # v
            pl.BlockSpec((1, bq, d), lambda b, j, i: (b, i, _I0)),   # do
            pl.BlockSpec((1, bq, 128), lambda b, j, i: (b, i, _I0)),  # lse
            pl.BlockSpec((1, bq, 128), lambda b, j, i: (b, i, _I0)),  # delta
        ],
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, _I0)),
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, _I0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sk, d), k.dtype),
            jax.ShapeDtypeStruct((bh, sk, d), v.dtype),
        ],
        scratch_shapes=[_vmem((bk, d)), _vmem((bk, d))],
        interpret=_interpret(),
        name="flash_bwd_dkv",
    )(q, k, v, do, lse, delta)

    dq_kernel = functools.partial(
        _bwd_dq_kernel, sm_scale=sm_scale, causal=causal,
        block_q=bq, block_k=bk, kv_blocks=kv_blocks)
    dq = pl.pallas_call(
        dq_kernel,
        grid=(bh, q_blocks, kv_blocks),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, _I0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, _I0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, _I0)),
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, _I0)),
            pl.BlockSpec((1, bq, 128), lambda b, i, j: (b, i, _I0)),
            pl.BlockSpec((1, bq, 128), lambda b, i, j: (b, i, _I0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, _I0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[_vmem((bq, d))],
        interpret=_interpret(),
        name="flash_bwd_dq",
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ------------------------------------------------------------- public API ----

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_bhsd(q, k, v, sm_scale, causal, blocks):
    o, _ = _fwd(q, k, v, sm_scale, causal, blocks)
    return o


def _flash_fwd_rule(q, k, v, sm_scale, causal, blocks):
    o, lse = _fwd(q, k, v, sm_scale, causal, blocks)
    return o, (q, k, v, o, lse)


def _flash_bwd_rule(sm_scale, causal, blocks, res, g):
    return _bwd(res, g, sm_scale, causal, blocks)


_flash_bhsd.defvjp(_flash_fwd_rule, _flash_bwd_rule)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_bhsd_lse(q, k, v, sm_scale, causal, blocks):
    """Like _flash_bhsd but also returns the per-row logsumexp [bh, sq] —
    the residual ring attention needs to merge partial blocks; both outputs
    carry cotangents (lse's folds into delta in _bwd)."""
    o, lse = _fwd(q, k, v, sm_scale, causal, blocks)
    return o, lse[..., 0]


def _flash_lse_fwd_rule(q, k, v, sm_scale, causal, blocks):
    o, lse = _fwd(q, k, v, sm_scale, causal, blocks)
    return (o, lse[..., 0]), (q, k, v, o, lse)


def _flash_lse_bwd_rule(sm_scale, causal, blocks, res, g):
    g_o, g_lse = g
    return _bwd(res, g_o, sm_scale, causal, blocks, g_lse=g_lse)


_flash_bhsd_lse.defvjp(_flash_lse_fwd_rule, _flash_lse_bwd_rule)


def _attend(kernel, finish, out_specs, q, k, v, causal, sm_scale):
    """Run a [b*h, s, d] kernel entry on [b, s, h, d] operands — once per
    device under a scoped mesh (_common.mesh_scope): GSPMD cannot partition
    a Mosaic call, so each device gets its own batch and head shard through
    a shard_map. finish(kernel outputs, b, h, sq, d) restores the paddle
    layout; out_specs(q's PartitionSpec) gives the outputs' specs."""
    scale = float(1.0 / math.sqrt(q.shape[-1]) if sm_scale is None
                  else sm_scale)
    causal = bool(causal)

    def local(q, k, v):
        b, sq, h, d = q.shape

        def to_bhsd(x):  # [b, s, h, d] -> [b*h, s, d]
            return jnp.swapaxes(x, 1, 2).reshape(b * h, x.shape[1], d)

        blocks = _tuned_blocks(b * h, sq, k.shape[1], d, q.dtype, scale,
                               causal)
        return finish(kernel(to_bhsd(q), to_bhsd(k), to_bhsd(v), scale,
                             causal, tuple(blocks)), b, h, sq, d)

    part = attention_partition()
    if part is None:
        return local(q, k, v)
    mesh, spec, auto = part
    return jax.shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=out_specs(spec), axis_names=auto,
                         check_vma=False)(q, k, v)


def flash_attention_with_lse(q, k, v, causal=False, sm_scale=None):
    """q,k,v: [b, s, h, d]. Returns (out [b, sq, h, d], lse [b, h, sq] f32).

    The (out, lse) pair is what a ring-attention shard needs to merge partial
    KV-block results with online softmax (SURVEY §5.7); both are
    differentiable through the Pallas backward kernels.
    """
    return _attend(
        _flash_bhsd_lse,
        lambda out, b, h, sq, d: (
            jnp.swapaxes(out[0].reshape(b, h, sq, d), 1, 2),
            out[1].reshape(b, h, sq)),
        lambda spec: (spec, jax.sharding.PartitionSpec(spec[0], spec[2], None)),
        q, k, v, causal, sm_scale)


def _tuned_blocks(bh, sq, sk, d, dtype, sm_scale, causal):
    """Block-size choice via the kernel autotune cache (core/autotune.py — the
    phi AlgorithmsCache analogue). Tuning runs the forward kernel out-of-band
    on materialized random inputs, so it is legal mid-trace; when autotune is
    off this collapses to the static heuristic."""
    from ...core import autotune

    default = (_pick_block(sq), _pick_block(sk))
    key = (int(bh), int(sq), int(sk), int(d), str(dtype), bool(causal),
           jax.default_backend())
    if not autotune.enabled():
        # peek (non-counting): a disabled run must not skew hit-rate stats
        cached = autotune.cache().peek("flash_attention", key)
        return cached or default
    cached = autotune.cache().get("flash_attention", key)
    if cached is not None:
        return cached
    if not autotune.should_tune():  # closed window / multi-controller: no timing
        return default
    # 1024 joins the space only where the BACKWARD working set fits: the
    # tuned choice is shared with the bwd kernels (which the tuner also
    # compiles + times, see below), whose bodies hold ~4 score-sized f32 intermediates
    # (s/p/dp/ds) — so the guard budgets 4 * bq * bk * 4 B <= 8 MB of
    # v5e's 16 MB VMEM, admitting (512,1024)/(1024,512) but not
    # (1024,1024), whose ~16 MB bwd set would spill or fail Mosaic. At
    # the bench shape (seq 1024) the {128,256,512} space degenerated to
    # the heuristic's own choice — the tuned [512,512] equaled
    # pick_block's default, so the round-5 "autotune win" was run-to-run
    # variance; the 1024-rect blocks are the first candidates the
    # heuristic cannot reach.
    candidates = sorted({(q_, k_)
                         for q_ in (1024, 512, 256, 128)
                         for k_ in (1024, 512, 256, 128)
                         if sq % q_ == 0 and sk % k_ == 0
                         and 4 * q_ * k_ * 4 <= (8 << 20)}) or [default]
    if len(candidates) == 1:
        return candidates[0]

    rng = np.random.RandomState(0)
    qa = jnp.asarray(rng.randn(bh, sq, d), dtype=dtype)
    ka = jnp.asarray(rng.randn(bh, sk, d), dtype=dtype)
    va = jnp.asarray(rng.randn(bh, sk, d), dtype=dtype)

    # one jitted executable per candidate, shared by the warmup and timed calls
    # (a fresh lambda per call would re-compile and time the compiler instead).
    # The tuned choice binds the FA2 BACKWARD kernels too (the pick is reused
    # at training time), so each candidate is compiled AND timed through
    # value_and_grad: fwd + both bwd kernels. A block pair whose backward
    # fails Mosaic compile raises here and is skipped by pick() — it can no
    # longer win on forward time and then fail only at training time
    # (ADVICE r5 #1), and the argmin now optimizes the full train-step cost.
    def _make_fb(blocks):
        def loss(a, b, c):
            return jnp.sum(
                _flash_bhsd(a, b, c, sm_scale, causal, blocks)
                .astype(jnp.float32))

        return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))

    compiled = {blocks: _make_fb(blocks) for blocks in candidates}

    def run(blocks):
        # the grads drain both backward kernels
        jax.block_until_ready(compiled[blocks](qa, ka, va))

    return autotune.pick("flash_attention", key, candidates, run, default=default)


def flash_attention(q, k, v, causal: bool = False, sm_scale: float | None = None):
    """q,k,v: [b, s, h, d] (paddle layout). Returns [b, sq, h, d]."""
    return _attend(
        _flash_bhsd,
        lambda o, b, h, sq, d: jnp.swapaxes(o.reshape(b, h, sq, d), 1, 2),
        lambda spec: spec, q, k, v, causal, sm_scale)
