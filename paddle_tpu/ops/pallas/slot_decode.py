"""Pallas kernel for the attention core of a decode step over a `full` layer
of the slot cache (ops/slot_attention.py): one query a slot against the key
and value rows the slot holds, fetched to the slot's offset only.

    q        [slots, heads, width]         zeros in the pad, the rows' dtype
    k, v     [slots, rows, heads, width]   `SlotKV`'s arrays AS STORED
                                           (`stored_dims`' pad included):
                                           they stay in HBM; a reshape that
                                           merges rows and heads (no byte
                                           moves under the chip's tiling,
                                           heads a multiple of 8) is all
                                           that stands before them
    lengths  [slots] int32                 positions a slot holds, 1..rows
    ->       [slots, heads, width]         the rows' dtype

The skeleton is slot_rows.py's: the grid over slots, a slot's rows in
blocks of `block` positions through two VMEM buffers an array, only up to
its length, the next slot's first block fetched behind its last. Online
softmax in float32 in VMEM scratch, `p` cast to the rows' dtype before the
second product (the plain core's numerics). Only a slot's last block is
masked by `length`, and its value rows past the length are zeroed, so what
those rows hold, NaN included, reaches nothing.

The products are not latent_decode's. There one row served every head; here
each key head has its own row and ONE query: matrix-vector work, bound by
bytes. A block in VMEM is `[block x heads, width]`, position-major, a head a
row, and both products take it as their stationary operand as it lies there,
no relayout: `s = q @ block^T` -> [heads, block x heads] float32 is the score
of EVERY query head against every row; a constant bias keeps the diagonal
(row r of a block belongs to head r % heads) and sends the rest to -1e30, so
after the softmax `p` is the block-diagonal matrix whose product `p @ block_v`
[heads, width] is each head's own weighted sum. That is heads x the needed
FLOPs on the MXU, hidden behind the copies: on a v5e a layer at
Olmo-Hybrid's shape takes 0.81 ms (752 GB/s over what it fetches) for the
plain core's 1.52, at GPT-2 large's 0.063 for 0.36
(tools/slot_decode_bench.py; PERF.md, PR 38). A body that multiplied and
reduced on the vector unit as XLA's fusions do (`[block, heads, width]`
float32 against the query broadcast over the block, a lane reduction a row)
read 0.85 and 0.080 at its best blocks and was not kept.

HBM traffic: the rows held, to the block, of both arrays; q and the result
once.

`supported()` says which inputs take it; everything else keeps the plain
core (ops/slot_attention.py), which is also the reference in the tests.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...nn.kv_cache import SlotKV
from . import _common
from ._common import I0 as _I0, NEG_INF
from .slot_rows import stream_rows

# Positions a pair of copies and a pair of products take; a slot over-reads
# half a block. A stored position is 6 KB (GPT-2 large) or 8 KB (Olmo-Hybrid)
# an array. On a v5e a layer at Olmo-Hybrid's shape (contexts about 2,300 of
# 4,096) takes 0.96 ms at 32, 0.81 at 64, 0.82 at 128, 0.85 at 256, 0.89 at
# 512; at GPT-2 large's (about 180 of 1,024) 0.078, 0.063, 0.071, 0.088, 0.140
# (tools/slot_decode_bench.py; PERF.md, PR 38).
BLOCK_ROWS = 64


def supported(q_shape, cache) -> bool:
    """q [b, s, kv_heads, groups, d] against the handle `update` returned:
    a `SlotKV` (one offset a slot; a ring's wrap is a second bound), one
    query a slot (s == 1) and a key head (groups == 1: several would want a
    matmul a head), the arrays stored a multiple of 128 wide, of 8 heads
    and of the block in rows, in a single-device program (jax refuses a
    Mosaic kernel in a multi-device program outside a shard_map) on a
    backend that compiles kernels."""
    if type(cache) is not SlotKV or _common._target() is None:
        return False
    b, s, _, groups, _ = q_shape
    rows, heads, width = cache.k.shape[1:]
    return (s == 1 and groups == 1 and tuple(cache.offset.shape) == (b,)
            and cache.k.shape == cache.v.shape and cache.k.shape[0] == b
            and width % 128 == 0 and heads % 8 == 0
            and rows % BLOCK_ROWS == 0 and _common.single_device_program())


def _kernel(len_ref, q_ref, bias_ref, k_ref, v_ref, o_ref, kbuf, vbuf, sem,
            phase, m_scr, l_scr, acc_scr, *, block, heads, scale):
    n = block * heads                       # rows of a block, a head a row

    def init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def body(at, tail, masked, state):
        k, v = kbuf[at], vbuf[at]                             # [n, width]
        s = jax.lax.dot_general(q_ref[0], k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if masked:
            # a select, not the bias: a row past the length may hold NaN
            cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(cols < tail * heads, s, jnp.float32(NEG_INF))
            live = jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0)
            v = jnp.where(live < tail * heads, v, jnp.zeros_like(v))
        s = s + bias_ref[...]           # [heads, n]: a head's own rows stay
        m_prev = m_scr[...]                                   # [heads, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = alpha * acc_scr[...] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    stream_rows(len_ref, (k_ref, v_ref), (kbuf, vbuf), sem, phase,
                block=block, rows=n, init=init, body=body)
    o_ref[0] = (acc_scr[...] / l_scr[...]).astype(o_ref.dtype)


def slot_decode(q, k, v, lengths, scale: float):
    """q [slots, heads, width] against k, v [slots, rows, heads, width] up
    to lengths [slots] -> [slots, heads, width]: a head's softmax(q . k *
    scale) over its slot's first `length` rows (clipped to 1..rows, as
    `SlotKV.update` clips its write), times the value rows. The caller has
    asked `supported()`."""
    return _call(q, k, v, lengths, scale=float(scale), block=BLOCK_ROWS,
                 interpret=_common._target() == "interpret")


# jitted, so that a program's layers trace and lower ONE kernel between them
@functools.partial(jax.jit, static_argnames=("scale", "block", "interpret"))
def _call(q, k, v, lengths, *, scale, block, interpret):
    slots, rows, heads, width = k.shape
    n = block * heads
    lengths = jnp.clip(lengths.astype(jnp.int32), 1, rows)
    # row r of a block is head r % heads: the one lane a query head keeps
    own = (jnp.arange(n, dtype=jnp.int32)[None, :] % heads
           == jnp.arange(heads, dtype=jnp.int32)[:, None])
    bias = jnp.where(own, 0.0, NEG_INF).astype(jnp.float32)
    kernel = functools.partial(_kernel, block=block, heads=heads, scale=scale)
    head_block = pl.BlockSpec((1, heads, width), lambda b, n: (b, _I0, _I0))
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((slots, heads, width), k.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(slots,),
            in_specs=[
                head_block,
                pl.BlockSpec((heads, n), lambda b, n: (_I0, _I0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=head_block,
            scratch_shapes=[
                pltpu.VMEM((2, n, width), k.dtype),
                pltpu.VMEM((2, n, width), v.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((heads, 1), jnp.float32),
                pltpu.VMEM((heads, 1), jnp.float32),
                pltpu.VMEM((heads, width), jnp.float32),
            ]),
        # the buffers' phase and the copies in flight go from a slot to the
        # next: the grid runs in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # room for the blocks of 512 the bench tries; 64 take 3 MB
            vmem_limit_bytes=64 * 2 ** 20),
        interpret=interpret, name="slot_decode")(
            lengths, q, bias, k.reshape(slots, rows * heads, width),
            v.reshape(slots, rows * heads, width))
