"""Pallas kernel for the attention core of a block step over a `full` layer
of the slot cache (ops/slot_attention.py): a slot's block of `s` queries,
every one of which sees the same rows, against the key and value rows the
slot holds, fetched to the slot's offset only. The grouped-query sibling of
slot_decode.py, whose body serves one query a key head.

    q        [slots, kv_heads, m, width]   a key head's m = s x groups query
                                           rows, zeros in the pad, the rows'
                                           dtype
    k, v     [slots, rows, heads, width]   `SlotKV`'s arrays AS STORED
                                           (`stored_dims`' pad included,
                                           heads >= kv_heads): they stay in
                                           HBM; a reshape that merges rows
                                           and heads (a bitcast under the
                                           chip's tiling) is all that stands
                                           before them
    lengths  [slots] int32                 rows every query of a slot sees,
                                           1..rows
    ->       [slots, kv_heads, m, width]   the rows' dtype

The skeleton is slot_rows.py's: the grid over slots, a slot's rows in
blocks of `block` positions through two VMEM buffers an array (`[block x
heads, width]` position-major, as stored), only up to its length, the next
slot's first block fetched behind its last. Only a slot's last block is
masked by `length`, and its value rows past the length are zeroed, so what
those rows hold, NaN included, reaches nothing.

The products are not slot_decode's. Each key head has m query rows (32 for
SDAR's 4 positions x 8 groups), so a head takes its own rows out of the
block, `block` of them at a stride of `heads` (`_head_rows`: a strided
sublane load; for a packed dtype a load of the 32-bit words that hold two
heads, and a shift or a mask; a copy of one head's rows straight into a
head-major buffer is refused, since a head is part of a tile of the stored
array), and the heads multiply them on the MXU as one batched matmul a
product: `s = q @ k^T` [kv_heads, m, block] float32, online softmax in
float32 carried through the loop as values, `p` cast to the rows' dtype,
`p @ v` [kv_heads, m, width] accumulated in float32 (the plain core's
numerics). That is the FLOPs the queries need and no more; a
block-diagonal bias over every head's rows, slot_decode's way, would take
heads x them and heads x the exponentials. Batched, the heads' chains of
products and reductions interleave: a head at a time, with the softmax's
state in VMEM, took 0.263 ms a layer at its best block for the 0.225 here
(PERF.md §6).

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
# half a block. A stored position is 1 KB an array at SDAR's shape, and a
# block's products cost about a microsecond whatever its size: on a v5e a
# layer of SDAR's block step (64 slots of 3,072 rows, contexts about 1,020)
# takes 0.314 ms at 128, 0.233 at 256, 0.225 at 512 (737 GB/s over what it
# fetches) and 0.275 at 1,024, the plain core 0.573 (tools/
# slot_decode_bench.py --cell sdar; PERF.md §6).
BLOCK_ROWS = 512


def supported(q_shape, cache, block: int) -> bool:
    """q [b, s, kv_heads, groups, d] of a block step (s == `block`, every
    query of a slot sees the same rows; any number of query heads a key
    head) against the handle `update` returned: a `SlotKV` with one offset
    a slot, the arrays stored a multiple of 128 wide, of 1, 2, 4 or a
    multiple of 8 heads (the shapes whose rows merge with the heads at no
    cost) and of the block in rows, in a single-device program (jax
    refuses a Mosaic kernel in a multi-device program outside a shard_map)
    on a backend that compiles kernels."""
    if type(cache) is not SlotKV or _common._target() is None:
        return False
    b, s = q_shape[:2]
    rows, heads, width = cache.k.shape[1:]
    return (s == block and tuple(cache.offset.shape) == (b,)
            and cache.k.shape == cache.v.shape and cache.k.shape[0] == b
            and width % 128 == 0 and (heads in (1, 2, 4) or heads % 8 == 0)
            and rows % BLOCK_ROWS == 0 and _common.single_device_program())


def _head_rows(buf, h, heads, block):
    """Rows h, h + heads, ... of a block `buf` [block x heads, width] in
    VMEM: head h's `block` rows [block, width]. A 32-bit word of a packed
    dtype holds two rows of a column (2r in its low half): for bf16 the
    words of heads (h - h % 2, h - h % 2 + 1) are loaded at a stride of
    heads / 2 and head h's half is moved to a float32's high half, where a
    bf16 is a float32's leading bits, exactly."""
    if heads == 1:
        return buf[...]
    if buf.dtype.itemsize == 4:
        return buf[pl.ds(h, block, stride=heads), :]
    assert buf.dtype == jnp.bfloat16, buf.dtype
    words = buf.bitcast(jnp.uint32)[pl.ds(h // 2, block, stride=heads // 2), :]
    high = (words << 16) if h % 2 == 0 else (words & jnp.uint32(0xFFFF0000))
    return pltpu.bitcast(high, jnp.float32).astype(jnp.bfloat16)


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, kbuf, vbuf, sem, phase, *,
            block, heads, kv_heads, scale):
    m, width = q_ref.shape[2:]

    def init():
        return (jnp.full((kv_heads, m, 1), NEG_INF, jnp.float32),
                jnp.zeros((kv_heads, m, 1), jnp.float32),
                jnp.zeros((kv_heads, m, width), jnp.float32))

    def body(at, tail, masked, state):
        """The products of the block in buffers `at` for every key head at
        once (a batched matmul a product: the heads' chains interleave);
        `state` is the online softmax (max, sum, weighted values), carried
        as values."""
        # [kv_heads, block, width] each
        k, v = (jnp.stack([_head_rows(buf.at[at], h, heads, block)
                           for h in range(kv_heads)])
                for buf in (kbuf, vbuf))
        s = jax.lax.dot_general(q_ref[0], k, (((2,), (2,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32)
        s = s * scale                                 # [kv_heads, m, block]
        if masked:
            # a select: a row past the length may hold NaN
            cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
            s = jnp.where(cols < tail, s, jnp.float32(NEG_INF))
            live = jax.lax.broadcasted_iota(jnp.int32, (kv_heads, block, 1), 1)
            v = jnp.where(live < tail, v, jnp.zeros_like(v))
        m_prev, l_prev, acc_prev = state
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        return (m_new, alpha * l_prev + jnp.sum(p, axis=2, keepdims=True),
                alpha * acc_prev + jax.lax.dot_general(
                    p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
                    preferred_element_type=jnp.float32))

    _, l, acc = stream_rows(len_ref, (k_ref, v_ref), (kbuf, vbuf), sem, phase,
                            block=block, rows=block * heads, init=init,
                            body=body)
    o_ref[0] = (acc / l).astype(o_ref.dtype)


def block_decode(q, k, v, lengths, scale: float):
    """q [slots, kv_heads, m, width] against k, v [slots, rows, heads,
    width] up to lengths [slots] -> [slots, kv_heads, m, width]: each query
    row of key head h, softmax(q . k_h * scale) over its slot's first
    `length` rows (clipped to 1..rows, as `SlotKV.update` clips its write),
    times the value rows of head h. The caller has asked `supported()`."""
    return _call(q, k, v, lengths, scale=float(scale), block=BLOCK_ROWS,
                 interpret=_common._target() == "interpret")


# jitted, so that a program's layers trace and lower ONE kernel between them
@functools.partial(jax.jit, static_argnames=("scale", "block", "interpret"))
def _call(q, k, v, lengths, *, scale, block, interpret):
    slots, rows, heads, width = k.shape
    kv_heads, m = q.shape[1:3]
    n = block * heads
    lengths = jnp.clip(lengths.astype(jnp.int32), 1, rows)
    kernel = functools.partial(_kernel, block=block, heads=heads,
                               kv_heads=kv_heads, scale=scale)
    q_block = pl.BlockSpec((1, kv_heads, m, width),
                           lambda b, n: (b, _I0, _I0, _I0))
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(q.shape, k.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(slots,),
            in_specs=[
                q_block,
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=q_block,
            scratch_shapes=[
                pltpu.VMEM((2, n, width), k.dtype),
                pltpu.VMEM((2, n, width), v.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32),
            ]),
        # the buffers' phase and the copies in flight go from a slot to the
        # next: the grid runs in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 * 2 ** 20),
        interpret=interpret, name="block_decode")(
            lengths, q, k.reshape(slots, rows * heads, width),
            v.reshape(slots, rows * heads, width))
