"""Pallas kernel for the absorbed core of a latent-attention decode step
(ops/latent_attention.py): one query a slot against the latent rows the slot
holds, each row read ONCE for both products.

    q        [slots, heads, width]   `[q_l | q_r | zeros]`, the rows' dtype
    rows     [slots, rows, width]    the slot cache's array of a layer, as
                                     stored: it stays in HBM, no reshape,
                                     slice or transpose stands before it
    lengths  [slots] int32           positions a slot holds, 1..rows
    ->       [slots, heads, out]     the weighted sum of the rows' first
                                     `out` columns, the rows' dtype

The slot is the grid axis; `lengths` is scalar-prefetched. A slot's rows come
in blocks of `block` rows through two VMEM buffers (`make_async_copy`), only
up to `ceil(length / block)`: a loop of dynamic trip count, so a block past a
slot's length is never fetched and costs no grid step. The last block of a
slot starts the copy of the NEXT slot's first block, so the pipeline stays
full across the grid (the buffer's phase is carried in SMEM). While a block
is in VMEM it serves both products: `s = q @ block^T` [heads, block] float32
(heads on the sublanes: a matmul, the one row shared by all heads), online
softmax in float32, `p` cast to the rows' dtype and `p @ block[:, :out]`
accumulated in float32. No score leaves VMEM. Only a slot's last block is
masked by `length` (its rows past the length are zeroed too, so what they
hold, NaN included, reaches nothing); the blocks before it are whole.

HBM traffic: the rows held, to the block; q and the result once. Bound at
DeepSeek-V2's sizes (128 heads, 512 + 64 stored 640 wide, bf16): 1,280 B and
295 kFLOP a row, 1.56 ns by bytes and 1.50 ns by FLOPs on a v5e: the ridge.

`supported()` says which inputs take it; everything else keeps the plain
`latent_attention.absorbed`, which is also the reference in the tests.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import _common
from ._common import I0 as _I0, NEG_INF

# Rows a copy and a pair of products take. On a v5e at the DeepSeek-V2 cell's
# sizes (tools/latent_decode_bench.py; PERF.md, PR 36) a layer takes 0.86 ms
# at 256, 0.66 at 512, 0.57 at 1,024 and 0.58 at 2,048: a larger block
# fetches more rows past a length, a smaller one pays a loop turn more often.
BLOCK_ROWS = 1024


def _target():
    """How a call here is lowered: "mosaic" on a TPU, None elsewhere (the
    plain form). A test sets "interpret", a tool that compiles for a
    described chip "mosaic" (tools/decode_hlo_probe.py)."""
    return "mosaic" if jax.default_backend() == "tpu" else None


def supported(q_shape, rows_shape, lengths) -> bool:
    """q [b, s, heads, width] against rows [b, rows, width]: one query a
    slot (s == 1) with one length a slot, rows a multiple of 128 wide and a
    multiple of the block in count, in a single-device program (jax refuses
    a Mosaic kernel in a multi-device program outside a shard_map) on a
    backend that compiles kernels."""
    b, s = q_shape[0], q_shape[1]
    return (_target() is not None and s == 1
            and lengths is not None and tuple(lengths.shape) == (b,)
            and rows_shape[-1] % 128 == 0 and rows_shape[1] % BLOCK_ROWS == 0
            and _common.single_device_program())


def _kernel(len_ref, q_ref, rows_ref, o_ref, buf, sem, phase, m_scr, l_scr,
            acc_scr, *, block, out_w, scale):
    b = pl.program_id(0)
    slots = pl.num_programs(0)
    length = len_ref[b]
    # lax.div / bitwise and, not `//` and `%`: jnp's forms of them do not
    # lower here under jax_enable_x64
    whole = jax.lax.div(length, jnp.int32(block))   # blocks with no mask
    tail = length - whole * block           # rows of the masked block, or 0
    blocks = whole + (tail > 0).astype(jnp.int32)

    def copy(slot, i, at):
        return pltpu.make_async_copy(
            rows_ref.at[slot, pl.ds(i * block, block)], buf.at[at],
            sem.at[at])

    @pl.when(b == 0)
    def _first():
        phase[0] = _I0
        copy(b, _I0, _I0).start()

    base = phase[0]
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    def step(i, masked):
        at = (base + i) & 1

        # the copy that follows this one: the slot's next block, or the
        # next slot's first
        @pl.when(i + 1 < blocks)
        def _next_block():
            copy(b, i + 1, 1 - at).start()

        @pl.when(jnp.logical_and(i + 1 == blocks, b + 1 < slots))
        def _next_slot():
            copy(b + 1, _I0, 1 - at).start()

        copy(b, i, at).wait()
        rows = buf[at]                                        # [block, w]
        if masked:
            live = jax.lax.broadcasted_iota(jnp.int32, (block, 1), 0) < tail
            rows = jnp.where(live, rows, jnp.zeros_like(rows))
        s = jax.lax.dot_general(q_ref[0], rows, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * scale                                         # [h, block]
        if masked:
            cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(cols < tail, s, jnp.float32(NEG_INF))
        m_prev = m_scr[...]                                   # [h, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = alpha * acc_scr[...] + jnp.dot(
            p.astype(rows.dtype), rows[:, :out_w],
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    def whole_block(i, carry):
        step(i, masked=False)
        return carry

    jax.lax.fori_loop(_I0, whole, whole_block, _I0)

    @pl.when(tail > 0)
    def _tail():
        step(whole, masked=True)

    phase[0] = (base + blocks) & 1
    o_ref[0] = (acc_scr[...] / l_scr[...]).astype(o_ref.dtype)


def latent_decode(q, rows, lengths, scale: float, out: int):
    """q [slots, heads, width] against rows [slots, rows, width] up to
    lengths [slots] -> [slots, heads, out]: softmax(q . row * scale) over a
    slot's first `length` rows (clipped to 1..rows, as `SlotLatent.update`
    clips its write), times their first `out` columns (a multiple of 128).
    The caller has asked `supported()`."""
    return _call(q, rows, lengths, scale=float(scale), out=int(out),
                 block=BLOCK_ROWS, interpret=_target() == "interpret")


# jitted, so that a program's layers trace and lower ONE kernel between them
# (five calls lower in 0.2 s where they took 1.2 s apart, PERF.md PR 36)
@functools.partial(jax.jit, static_argnames=("scale", "out", "block",
                                             "interpret"))
def _call(q, rows, lengths, *, scale, out, block, interpret):
    slots, heads, width = q.shape
    lengths = jnp.clip(lengths.astype(jnp.int32), 1, rows.shape[1])
    kernel = functools.partial(_kernel, block=block, out_w=out, scale=scale)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((slots, heads, out), rows.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(slots,),
            in_specs=[
                pl.BlockSpec((1, heads, width), lambda b, n: (b, _I0, _I0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, heads, out),
                                   lambda b, n: (b, _I0, _I0)),
            scratch_shapes=[
                pltpu.VMEM((2, block, width), rows.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((heads, 1), jnp.float32),
                pltpu.VMEM((heads, 1), jnp.float32),
                pltpu.VMEM((heads, out), jnp.float32),
            ]),
        # the buffers' phase and the copy in flight go from a slot to the
        # next: the grid runs in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret, name="latent_decode")(lengths, q, rows)
