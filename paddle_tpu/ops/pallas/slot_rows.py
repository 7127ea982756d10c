"""The skeleton of the decode kernels (slot_decode.py, block_decode.py,
latent_decode.py): the slot is the grid axis, `lengths` is scalar-prefetched,
and a slot's rows come from HBM in blocks of `block` positions through two
VMEM buffers an array (`make_async_copy`), only up to `ceil(length /
block)`: a loop of dynamic trip count over the whole blocks, so a block past
a slot's length is never fetched, then the block the length cuts, masked.
The last block of a slot starts the copies of the NEXT slot's first block,
so the pipeline stays full across the grid (the buffers' phase is carried
in SMEM, and the grid runs in order).

A kernel keeps its own parts (scratch and initial state, the body of one
block, the epilogue, `supported()`, the `pallas_call` specs) and hands the
body in at trace time. The softmax's state lives where `init()` puts it: in
VMEM scratch the body updates in place (`init` returns None; the cut block
runs under `pl.when`), or in values carried through the loop (the body
returns the next; the cut block is a `lax.cond` over them).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import I0 as _I0


def stream_rows(len_ref, srcs, bufs, sem, phase, *, block, rows, init,
                body):
    """Run `body(at, tail, masked, state)` over the blocks slot
    `pl.program_id(0)` holds, up to its length `len_ref[slot]` in positions:
    `at` names the buffers the block is in; `masked` marks the cut block,
    whose first `tail` positions are held. `srcs` are the arrays in HBM,
    [slots, positions x rows a position, width] each; `bufs` their VMEM
    buffers [2, rows, width], `rows` a block's; `sem` the DMA semaphores, [2]
    for one array or [arrays, 2]; `phase` an SMEM int32 [1]. -> the state
    the last block left (None in the scratch form)."""
    b = pl.program_id(0)
    slots = pl.num_programs(0)
    length = len_ref[b]
    # lax.div / bitwise and, not `//` and `%`: jnp's forms of them do not
    # lower here under jax_enable_x64
    whole = jax.lax.div(length, jnp.int32(block))   # blocks with no mask
    tail = length - whole * block           # positions of the masked block
    blocks = whole + (tail > 0).astype(jnp.int32)

    def copies(slot, i, at):
        at_rows = pl.ds(i * rows, rows)
        return [pltpu.make_async_copy(
            src.at[slot, at_rows], buf.at[at],
            sem.at[at] if len(srcs) == 1 else sem.at[np.int32(j), at])
            for j, (src, buf) in enumerate(zip(srcs, bufs))]

    def start(slot, i, at):
        for c in copies(slot, i, at):
            c.start()

    @pl.when(b == 0)
    def _first():
        phase[0] = _I0
        start(b, _I0, _I0)

    base = phase[0]
    state = init()

    def step(i, state, masked):
        at = (base + i) & 1

        # the copies that follow these: the slot's next block, or the next
        # slot's first
        @pl.when(i + 1 < blocks)
        def _next_block():
            start(b, i + 1, 1 - at)

        @pl.when(jnp.logical_and(i + 1 == blocks, b + 1 < slots))
        def _next_slot():
            start(b + 1, _I0, 1 - at)

        for c in copies(b, i, at):
            c.wait()
        return body(at, tail, masked, state)

    if state is None:
        def whole_block(i, carry):
            step(i, None, masked=False)
            return carry

        jax.lax.fori_loop(_I0, whole, whole_block, _I0)

        @pl.when(tail > 0)
        def _tail():
            step(whole, None, masked=True)
    else:
        state = jax.lax.fori_loop(
            _I0, whole, lambda i, st: step(i, st, masked=False), state)
        state = jax.lax.cond(tail > 0,
                             lambda st: step(whole, st, masked=True),
                             lambda st: st, state)
    phase[0] = (base + blocks) & 1
    return state
