"""Pallas fused LM-head + softmax-cross-entropy (online over vocab tiles).

The chunked XLA version (ops/fused.py) avoids materializing the full
[tokens, vocab] logits but still writes each chunk's logits tile to HBM
between the matmul and the reduction. This kernel keeps every logits tile in
VMEM — flash-attention's online-softmax trick applied to the classifier:

    fwd:  per (row-block i, vocab-block j): s = h_i @ W_j^T (f32 acc);
          m/l online logsumexp accumulators; picked logit found in the tile
          that contains each row's label. loss = m + log(l) - picked.
    bwd:  recompute s tile-by-tile from (h, W, lse);
          p = exp(s - lse); dl = (p - onehot(label)) * g;
          dh kernel accumulates dl @ W_j over j (row-block outer),
          dW kernel accumulates dl^T @ h_i over i (vocab-block outer)
          — the same two-kernel split as flash attention's dq / dkdv.

HBM traffic per pass ~ reads of h and W only (W once per row-block), vs the
chunked version's additional logits-tile writes+reads. Saved residuals:
per-row logsumexp (f32 [tokens]).

W layout: [vocab, hidden] (tied-embedding layout). Rows must divide into
block_n, vocab into block_v — the public wrapper in ops/fused.py pads rows
and only routes here when `supported()` holds. CPU runs interpret mode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from ._common import I0 as _I0, NEG_INF, interpret as _interpret, \
    vmem as _vmem


def _pick(n: int, preferred: int) -> int:
    """Like _common.pick_block but with a 128 floor (lane-width tiles) and a
    0 'unsupported' sentinel consumed by supported()."""
    for b in (preferred, 512, 256, 128):
        if b <= preferred and n % b == 0 and b <= n:
            return b
    return 0


def _pick_rows(n: int) -> int:
    """Row blocks tile the 1D labels/loss/lse operands, whose XLA layout is
    (8 sublanes x 128 lanes) = 1024-element tiles — a smaller 1D block fails
    Mosaic layout verification on real TPU ("XLA layout {0:T(1024)} does not
    match Mosaic layout {0:T(512)}"), so 1024 is the floor, not 128."""
    return 1024 if n % 1024 == 0 and n >= 1024 else 0


def _check_block_n(v: int) -> int:
    """COMPUTE row-block size (the 2D h/s tiles). The 1D operands always use
    1024-element blocks (_pick_rows); when block_n < 1024 each 1D block is
    revisited 1024//block_n consecutive row-steps via an i//pack index map
    and pl.ds sub-slices. Mosaic compile time grows superlinearly in the
    vector-op count of the kernel body (~block_n x block_v tiles): the
    round-3 on-chip probe is what this knob exists for — at 1024x512 the
    forward alone exceeded 9.5 min of Mosaic compile."""
    v = int(v)
    if v not in (256, 512, 1024):
        raise ValueError(
            f"block_n must be 256, 512 or 1024 (the 1D operands tile at "
            f"1024 and the compute block must divide it); got {v}")
    return v


def supported(n_rows: int, vocab: int, hidden: int) -> bool:
    # vocab needs no divisibility: the wrapper pads W to a 512 multiple and the
    # kernels mask the padded columns to NEG_INF (a 50304 vocab would otherwise
    # fall to 128-wide blocks -> a 393-step inner grid and minutes of Mosaic
    # compile at bench shapes)
    return _pick_rows(n_rows) > 0 and vocab >= 128 and hidden % 128 == 0


def _row1d_index_map(pack: int):
    """Index map for the 1024-element 1D blocks revisited `pack` row-steps.
    pack == 1 avoids the traced floor_divide entirely: each index_map traces
    through several jnp layers, and at the default block the extra frames
    pushed the deeply nested export->grad->pallas stack over CPython's
    recursion limit under pytest."""
    if pack == 1:
        return lambda i, j: (i,)
    return lambda i, j: (i // pack,)


def _row1d_index_map_ji(pack: int):
    """Same but for (j, i)-ordered grids (the dW kernel)."""
    if pack == 1:
        return lambda j, i: (i,)
    return lambda j, i: (i // pack,)


# ---------------------------------------------------------------- forward ----

def _fwd_kernel(h_ref, w_ref, lab_ref, loss_ref, lse_ref, m_scr, l_scr, p_scr,
                *, block_n, block_v, v_blocks, v_true, pack):
    i = pl.program_id(0)
    j = pl.program_id(1)
    # 1D operands ride 1024-element blocks (their XLA tile); when the compute
    # block is smaller, each 1D block is revisited `pack` consecutive row
    # steps and this step touches only its ds sub-slice
    off = (i % pack) * block_n if pack > 1 else 0

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        p_scr[...] = jnp.zeros_like(p_scr)

    h = h_ref[...]                      # [bn, H] storage dtype
    w = w_ref[...]                      # [bv, H]
    s = jax.lax.dot_general(h, w, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [bn, bv]

    lab = lab_ref[pl.ds(off, block_n)]  # [bn] int32 (1D block: a [nb, bn]
    #                                     2D layout with [1, bn] blocks breaks
    #                                     Mosaic's (8, 128) block-tiling rule)
    col0 = j * block_v
    cols = col0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    if v_true is not None:              # W padded to a 512 multiple: padded
        #                                 columns must not enter the logsumexp
        s = jnp.where(cols < v_true, s, jnp.float32(NEG_INF))
    hit = cols == lab[:, None]          # row's label inside this tile?
    # each label lands in exactly one tile: accumulate its logit via sum
    # zeros_like, not a 0.0 literal: under jax_enable_x64 the weak literal
    # promotes through f64 and Mosaic has no f64->f32 cast
    p_scr[...] += jnp.sum(jnp.where(hit, s, jnp.zeros_like(s)), axis=1,
                          keepdims=True)

    m_prev = m_scr[...][:, :1]
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    l_scr[...] = (l_scr[...] * jnp.exp(m_prev - m_new)
                  + jnp.sum(jnp.exp(s - m_new), axis=1, keepdims=True))
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(j == v_blocks - 1)
    def _finalize():
        # the output block flushes when i crosses a pack boundary; each of the
        # pack visits fills its own sub-slice at its last vocab step
        lse = m_scr[...][:, :1] + jnp.log(l_scr[...][:, :1])
        loss_ref[pl.ds(off, block_n)] = (lse - p_scr[...][:, :1])[:, 0]
        lse_ref[pl.ds(off, block_n)] = lse[:, 0]


def _fwd(h2, w, labels, block_n, block_v, v_true=None):
    n, hdim = h2.shape
    v = w.shape[0]
    if w.dtype != h2.dtype:
        # one materialized cast (f32 master -> bf16 under amp): tiles then read
        # at half bandwidth; dW still accumulates f32 in scratch
        w = w.astype(h2.dtype)
    pack = 1024 // block_n
    grid = (n // block_n, v // block_v)
    kernel = functools.partial(_fwd_kernel, block_n=block_n, block_v=block_v,
                               v_blocks=v // block_v, v_true=v_true, pack=pack)
    row1d = _row1d_index_map(pack)
    loss, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, hdim), lambda i, j: (i, _I0)),
            pl.BlockSpec((block_v, hdim), lambda i, j: (j, _I0)),
            pl.BlockSpec((1024,), row1d),
        ],
        out_specs=[
            pl.BlockSpec((1024,), row1d),
            pl.BlockSpec((1024,), row1d),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n,), jnp.float32),
            jax.ShapeDtypeStruct((n,), jnp.float32),
        ],
        scratch_shapes=[_vmem((block_n, 128)), _vmem((block_n, 128)),
                        _vmem((block_n, 128))],
        interpret=_interpret(),
    )(h2, w, labels)
    return loss, lse


# --------------------------------------------------------------- backward ----

def _dh_kernel(h_ref, w_ref, lab_ref, lse_ref, g_ref, dh_ref, dh_scr,
               *, block_n, block_v, v_blocks, v_true, pack):
    i = pl.program_id(0)
    j = pl.program_id(1)
    off = (i % pack) * block_n if pack > 1 else 0

    @pl.when(j == 0)
    def _init():
        dh_scr[...] = jnp.zeros_like(dh_scr)

    h = h_ref[...]
    w = w_ref[...]
    s = jax.lax.dot_general(h, w, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    lab = lab_ref[pl.ds(off, block_n)]
    lse = lse_ref[pl.ds(off, block_n)]
    g = g_ref[pl.ds(off, block_n)]
    cols = j * block_v + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    if v_true is not None:  # padded columns: p -> 0, no gradient flow
        s = jnp.where(cols < v_true, s, jnp.float32(NEG_INF))
    p = jnp.exp(s - lse[:, None])
    dl = (p - (cols == lab[:, None])) * g[:, None]       # [bn, bv] f32
    dh_scr[...] += jax.lax.dot_general(
        dl.astype(w.dtype), w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(j == v_blocks - 1)
    def _finalize():
        dh_ref[...] = dh_scr[...].astype(dh_ref.dtype)


def _dw_kernel(h_ref, w_ref, lab_ref, lse_ref, g_ref, dw_ref, dw_scr,
               *, block_n, block_v, n_blocks, v_true, pack):
    j = pl.program_id(0)
    i = pl.program_id(1)
    off = (i % pack) * block_n if pack > 1 else 0

    @pl.when(i == 0)
    def _init():
        dw_scr[...] = jnp.zeros_like(dw_scr)

    h = h_ref[...]
    w = w_ref[...]
    s = jax.lax.dot_general(h, w, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    lab = lab_ref[pl.ds(off, block_n)]
    lse = lse_ref[pl.ds(off, block_n)]
    g = g_ref[pl.ds(off, block_n)]
    cols = j * block_v + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    if v_true is not None:  # padded columns contribute zero to dW rows >= v_true
        s = jnp.where(cols < v_true, s, jnp.float32(NEG_INF))
    p = jnp.exp(s - lse[:, None])
    dl = (p - (cols == lab[:, None])) * g[:, None]
    dw_scr[...] += jax.lax.dot_general(
        dl.astype(h.dtype), h, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)  # [bv, H]

    @pl.when(i == n_blocks - 1)
    def _finalize():
        dw_ref[...] = dw_scr[...].astype(dw_ref.dtype)


def _bwd(res, g, block_n, block_v, v_true=None):
    h2, w, labels, lse = res
    w_dtype = w.dtype
    if w.dtype != h2.dtype:
        w = w.astype(h2.dtype)
    n, hdim = h2.shape
    v = w.shape[0]
    pack = 1024 // block_n
    nb, vb = n // block_n, v // block_v
    g32 = g.astype(jnp.float32)

    row1d = _row1d_index_map(pack)
    dh = pl.pallas_call(
        functools.partial(_dh_kernel, block_n=block_n, block_v=block_v,
                          v_blocks=vb, v_true=v_true, pack=pack),
        grid=(nb, vb),
        in_specs=[
            pl.BlockSpec((block_n, hdim), lambda i, j: (i, _I0)),
            pl.BlockSpec((block_v, hdim), lambda i, j: (j, _I0)),
            pl.BlockSpec((1024,), row1d),
            pl.BlockSpec((1024,), row1d),
            pl.BlockSpec((1024,), row1d),
        ],
        out_specs=pl.BlockSpec((block_n, hdim), lambda i, j: (i, _I0)),
        out_shape=jax.ShapeDtypeStruct((n, hdim), h2.dtype),
        scratch_shapes=[_vmem((block_n, hdim))],
        interpret=_interpret(),
    )(h2, w, labels, lse, g32)

    row1d_ji = _row1d_index_map_ji(pack)
    dw = pl.pallas_call(
        functools.partial(_dw_kernel, block_n=block_n, block_v=block_v,
                          n_blocks=nb, v_true=v_true, pack=pack),
        grid=(vb, nb),
        in_specs=[
            pl.BlockSpec((block_n, hdim), lambda j, i: (i, _I0)),
            pl.BlockSpec((block_v, hdim), lambda j, i: (j, _I0)),
            pl.BlockSpec((1024,), row1d_ji),
            pl.BlockSpec((1024,), row1d_ji),
            pl.BlockSpec((1024,), row1d_ji),
        ],
        out_specs=pl.BlockSpec((block_v, hdim), lambda j, i: (j, _I0)),
        out_shape=jax.ShapeDtypeStruct((v, hdim), jnp.float32),
        scratch_shapes=[_vmem((block_v, hdim))],
        interpret=_interpret(),
    )(h2, w, labels, lse, g32)
    return dh, dw.astype(w_dtype)  # f32 scratch accumulation -> master dtype


# ------------------------------------------------------------- public API ----

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _lm_loss(h2, w, labels, block_n, block_v, v_true):
    loss, _ = _fwd(h2, w, labels, block_n, block_v, v_true)
    return loss


def _fwd_rule(h2, w, labels, block_n, block_v, v_true):
    loss, lse = _fwd(h2, w, labels, block_n, block_v, v_true)
    return loss, (h2, w, labels, lse)


def _bwd_rule(block_n, block_v, v_true, res, g):
    dh, dw = _bwd(res, g, block_n, block_v, v_true)
    dlab = np.zeros(res[2].shape, dtype=jax.dtypes.float0)
    return dh, dw, dlab


_lm_loss.defvjp(_fwd_rule, _bwd_rule)


def lm_head_cross_entropy(h2, w, labels, block_n=256):
    """h2 [N, H], w [V, H], labels [N] int32 (already ignore-masked to a safe
    index by the caller) -> per-row loss [N] f32. Caller guarantees
    supported(N, V, H). W is padded to a 512-multiple vocab internally (padded
    columns masked to NEG_INF; dW for them is zero and sliced off by autodiff
    of the pad). RETIRED from the training path (round 5): not routed by
    ops/fused.py; available as a direct-call library kernel only, and not
    compiled on the machine this tree now runs on.

    block_n hazard: 1024 is the documented Mosaic compile pathology at bench
    vocab (50304 -> the round-3 probe measured >9.5 min of Mosaic compile for
    the forward alone at 1024x512) — compile time grows superlinearly in the
    kernel body's tile count. The default is therefore 256, the value bench
    actually shipped; only raise it at small vocab after timing the compile."""
    n = h2.shape[0]
    v = w.shape[0]
    assert _pick_rows(n) == 1024  # callers pad rows to a 1024 multiple
    block_n = _check_block_n(block_n)
    vpad = (-v) % 512
    if vpad:
        w = jnp.concatenate(
            [w, jnp.zeros((vpad, w.shape[1]), w.dtype)], axis=0)
    block_v = _pick(w.shape[0], 512)
    return _lm_loss(h2, w, labels.astype(jnp.int32), block_n, block_v,
                    v if vpad else None)
