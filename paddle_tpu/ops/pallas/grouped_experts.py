"""Pallas kernel for the routed experts' SwiGLU over rows sorted by expert
(nn/layers/routed_experts.py): the three grouped matmuls and the `silu(gate)
* up` between them in one call, each expert's three matrices streamed from
HBM once for all of its rows.

    xs       [rows, hidden]            rows sorted by expert; stays in HBM
    w_gate   [count, hidden, width]    `_ExpertWeights` as stored: stay in
    w_up     [count, hidden, width]    HBM
    w_down   [count, width, hidden]
    sizes    [count] int32             rows of each expert held, in order;
                                       rows past their sum (experts held
                                       elsewhere) are not an expert's
    ->       [rows, hidden]            `(silu(x Wg_e) * (x Wu_e)) Wd_e` a
                                       row; past the last group unwritten

The grid runs over the experts held. `sizes` comes in as its cumulative sum
(`offsets`, scalar-prefetched) beside `nexts`, the next expert that has rows.
An expert with no row costs a grid step and fetches nothing. For the others,
a weight STEP is the matching `[hidden, tn]` tiles of `w_gate` and `w_up` and
`[tn, hidden]` of `w_down` (the whole expert where it fits `STEP_BYTES`),
copied (`make_async_copy`) into one of two VMEM buffers; every step starts
the copies of the next, across experts, so the copies never stop (the
buffers' phase is carried in SMEM). An expert's rows come in `ROW_TILE` rows
a copy, aligned to the tile, into a chunk of `CHUNK_ROWS` rows: a loop of
dynamic trip count (`ceil` of the expert's tiles), started while the
expert before it computes. A chunk is multiplied whole, the tiles past the
expert's rows included: at 16 rows an expert the MXU's time is the
weights' passage through it, whatever the row count up to 128.

Numerics as the plain path's: products in float32; `gate` and `up` stay
float32 through the SiLU (the plain path rounds them to the rows' dtype
first), `a` is rounded to the weights' dtype before the down product, `y`
accumulates in float32 over the width's tiles and is written in the rows'
dtype.

Dropless: an expert whose rows do not fit a chunk takes more passes, one a
chunk (with the whole expert in VMEM the weights stay; tiled, they are
fetched again a pass). Output goes out in tiles of `ROW_TILE` rows, in row
order, each written once: a tile that the expert ends inside is kept in VMEM
(`carry`) and completed by the next expert's first rows. The rows past the
last group (those of experts held elsewhere) are neither read nor written,
as XLA's `ragged_dot` on the chip leaves them (the caller masks them: the
combine of nn/layers/routed_experts.py); only the tile the last group ends
inside is read and written whole, its rows past the group as zeros.

HBM traffic: each expert that has rows, its three matrices once (once a pass
where they are tiled); its rows' tiles in and out once.

`takes_kernel()` says which calls take it; everything else keeps the three
`ragged_dot` calls, which are also the reference in the tests.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import _common
from ._common import I0 as _I0

ROW_TILE = 16        # rows a copy in and out: a bf16 tile's sublanes
# Rows a product takes. 64 and 128 read alike while an expert's rows fit 64
# (SDAR at 16 rows an expert: 1.631 and 1.637 ms a layer); past that a chunk
# of 64 takes a second pass, which a tiled expert pays with a second fetch
# of its weights (DeepSeek-V2 at 64 rows an expert: 5.04 ms at 64, 3.24 at
# 128; tools/grouped_experts_bench.py, PERF.md, PR 40).
CHUNK_ROWS = 128
STEP_BYTES = 16 * 2 ** 20   # the three matrices' tiles a step, at most

# The band of mean rows an expert (rows x top_k over the PUBLISHED experts)
# in which the kernel is taken. ms a layer on a v5e, `ragged_dot` / kernel
# (tools/grouped_experts_bench.py; PERF.md §5, PR 40):
#
#   rows an expert        1    2.4      8     16     32     64    128
#   SDAR      ragged_dot  1.73   2.25   4.40   4.44   4.60   4.90   5.77
#   2048x768  kernel      1.06   1.47   1.62   1.63   1.64   1.66   1.88
#   Trinity   ragged_dot  1.75   2.26   5.11   5.16   5.33   5.68   6.67
#   2048x1024 kernel      1.41   1.95   2.16   2.16   2.18   2.20   2.42
#   DeepSeek  ragged_dot  2.07   2.62   3.79   6.11   6.43   7.27   9.02
#   5120x1536 kernel      1.90   2.15   2.47   2.55   2.70   3.24   5.74
#   (40 of 160 held)
#
# From 8 rows an expert the kernel takes a third to two fifths of the time
# at every width (86 to 91% of the HBM peak up to 32). Below 8 it wins too
# (the decode steps of Trinity and DeepSeek-V2, at 1 and 2.4), where
# `ragged_dot` reads 55 to 83% of the peak: the band leaves them to a PR of
# their own (ROADMAP.md S21). Above 128 the passes' products outgrow the
# copies (DeepSeek-V2 at 224 with chunks of 64: 12.7 ms for 11.1).
ROWS_AN_EXPERT = (8.0, 128.0)


def _target():
    """How a call here is lowered: "mosaic" on a TPU, None elsewhere (the
    plain form). A test sets "interpret", a tool that compiles for a
    described chip "mosaic" (tools/decode_hlo_probe.py)."""
    return "mosaic" if jax.default_backend() == "tpu" else None


def takes_kernel(rows, num_experts, hidden, width, dtype, w_dtype) -> bool:
    """`rows` sorted rows (tokens x top_k) on the experts of a layer that
    routes over `num_experts`: the kernel where the mean rows an expert lies
    in `ROWS_AN_EXPERT`, the widths are whole lanes, the rows whole tiles
    and of the weights' dtype, in a single-device program (jax refuses a
    Mosaic kernel in a multi-device program outside a shard_map) on a
    backend that compiles kernels."""
    lo, hi = ROWS_AN_EXPERT
    return (_target() is not None and lo <= rows / num_experts <= hi
            and hidden % 128 == 0 and width % 128 == 0
            and rows % ROW_TILE == 0 and dtype == w_dtype
            and _common.single_device_program())


def tile_width(hidden, width, itemsize) -> int:
    """The widest tile of `width` (a multiple of 128 that divides it) whose
    three matrices' tiles fit `STEP_BYTES`."""
    fits = [tn for tn in range(128, width + 1, 128)
            if width % tn == 0 and 3 * hidden * tn * itemsize <= STEP_BYTES]
    return max(fits, default=128)


def _kernel(off_ref, nxt_ref, x_hbm, wg_hbm, wu_hbm, wd_hbm, y_hbm,
            xbuf, wgbuf, wubuf, wdbuf, acc, obuf, carry, wsem, xsem, osem,
            state, *, tile, chunk, tn, steps):
    e = pl.program_id(0)
    count = pl.num_programs(0)
    per_chunk = chunk // tile
    following = nxt_ref[e + 1]          # the next expert that has rows

    def div(a, b):
        # lax.div, not `//`: jnp's form does not lower here under
        # jax_enable_x64
        return jax.lax.div(a, jnp.int32(b))

    def span(ex):
        """(first row, end row, first tile, end tile) of expert ex."""
        s, end = off_ref[ex], off_ref[ex + 1]
        return s, end, div(s, tile), div(end + (tile - 1), tile)

    def tiles(ex, p):
        """(first tile, tiles) of expert ex's pass p."""
        _, _, t0, t1 = span(ex)
        c0 = t0 + p * per_chunk
        return c0, jnp.minimum(t1 - c0, per_chunk)

    # ---- copies: a pass's rows, a weight step, a tile out
    def x_copy(t, i, at):
        return pltpu.make_async_copy(x_hbm.at[pl.ds(t * tile, tile)],
                                     xbuf.at[at, pl.ds(i * tile, tile)],
                                     xsem.at[at])

    def x_loop(ex, p, at, wait):
        c0, n = tiles(ex, p)

        def body(i, c):
            if wait:
                x_copy(c0, i, at).wait()
            else:
                x_copy(c0 + i, i, at).start()
            return c

        jax.lax.fori_loop(_I0, n, body, _I0)

    def w_copies(ex, j, at):
        if steps == 1:
            src = (wg_hbm.at[ex], wu_hbm.at[ex], wd_hbm.at[ex])
        else:
            cols = pl.ds(j * tn, tn)
            src = (wg_hbm.at[ex, :, cols], wu_hbm.at[ex, :, cols],
                   wd_hbm.at[ex, cols, :])
        return [pltpu.make_async_copy(a, b.at[at], wsem.at[at, np.int32(k)])
                for k, (a, b) in enumerate(zip(src, (wgbuf, wubuf, wdbuf)))]

    def w_start(ex, j, at):
        for c in w_copies(ex, j, at):
            c.start()

    def o_copy(src, t):
        return pltpu.make_async_copy(src, y_hbm.at[pl.ds(t * tile, tile)],
                                     osem.at[_I0])

    def o_wait():
        """Every tile still on its way out of `obuf` or `carry`."""
        def body(i, c):
            o_copy(carry, _I0).wait()
            return c

        jax.lax.fori_loop(_I0, state[2], body, _I0)
        state[2] = _I0

    @pl.when(e == 0)
    def _first():
        state[0] = _I0
        state[1] = _I0
        state[2] = _I0
        first = nxt_ref[0]

        @pl.when(first < count)
        def _():
            x_loop(first, _I0, _I0, wait=False)
            w_start(first, _I0, _I0)

    def product(x, at):
        g = jnp.dot(x, wgbuf[at], preferred_element_type=jnp.float32)
        u = jnp.dot(x, wubuf[at], preferred_element_type=jnp.float32)
        a = (g * jax.nn.sigmoid(g) * u).astype(wdbuf.dtype)
        return jnp.dot(a, wdbuf[at], preferred_element_type=jnp.float32)

    def store(y, p):
        """This pass's rows of `y` [chunk, hidden] float32 out, in whole
        tiles; the tile the pass ends inside waits in `carry`."""
        s, end, t0, _ = span(e)
        c0 = t0 + p * per_chunk
        lo = jnp.maximum(s, c0 * tile)
        hi = jnp.minimum(end, (c0 + per_chunk) * tile)
        o_wait()
        obuf[...] = y.astype(obuf.dtype)
        rows = c0 * tile + jax.lax.broadcasted_iota(jnp.int32, (tile, 1), 0)
        # a select: the rows before `lo` are the carry's, and the carry's
        # rows past it, never written yet, may hold anything
        obuf[pl.ds(0, tile)] = jnp.where(rows < lo, carry[...],
                                         obuf[pl.ds(0, tile)])
        whole = div(hi, tile) - c0

        def out(i, c):
            o_copy(obuf.at[pl.ds(i * tile, tile)], c0 + i).start()
            return c

        jax.lax.fori_loop(_I0, whole, out, _I0)
        state[2] = whole

        @pl.when(hi > (c0 + whole) * tile)
        def _keep():
            carry[...] = obuf[pl.ds(pl.multiple_of(whole * tile, tile),
                                    tile)]

    @pl.when(off_ref[e + 1] > off_ref[e])
    def _expert():
        _, _, t0, t1 = span(e)
        n_pass = div(t1 - t0 + (per_chunk - 1), per_chunk)

        def one_pass(p, c):
            xa = state[1]

            # the rows that follow: this expert's next pass, or the next
            # expert's first
            @pl.when(p + 1 < n_pass)
            def _next_pass():
                x_loop(e, p + 1, 1 - xa, wait=False)

            @pl.when(jnp.logical_and(p + 1 == n_pass, following < count))
            def _next_expert():
                x_loop(following, _I0, 1 - xa, wait=False)

            if steps == 1:
                # the whole expert in one step, kept for every pass
                @pl.when(p == 0)
                def _weights():
                    wa = state[0]

                    @pl.when(following < count)
                    def _():
                        w_start(following, _I0, 1 - wa)

                    for cp in w_copies(e, _I0, wa):
                        cp.wait()

            x_loop(e, p, xa, wait=True)
            x = xbuf[xa]
            if steps == 1:
                y = product(x, state[0])
            else:
                acc[...] = jnp.zeros_like(acc)
                for j in range(steps):
                    wa = state[0]
                    if j + 1 < steps:
                        w_start(e, np.int32(j + 1), 1 - wa)
                    else:
                        @pl.when(p + 1 < n_pass)
                        def _again():
                            w_start(e, _I0, 1 - wa)

                        @pl.when(jnp.logical_and(p + 1 == n_pass,
                                                 following < count))
                        def _next():
                            w_start(following, _I0, 1 - wa)

                    for cp in w_copies(e, np.int32(j), wa):
                        cp.wait()
                    acc[...] += product(x, wa)
                    state[0] = 1 - wa
                y = acc[...]
            store(y, p)
            state[1] = 1 - xa
            return c

        jax.lax.fori_loop(_I0, n_pass, one_pass, _I0)
        if steps == 1:
            state[0] = 1 - state[0]

    @pl.when(e == count - 1)
    def _last():
        # the tile the last group ends inside, its rows past the group
        # zeroed; the tiles past it are not an expert's and stay unwritten
        o_wait()
        total = off_ref[count]
        t = div(total, tile)

        @pl.when(total > t * tile)
        def _part():
            rows = t * tile + jax.lax.broadcasted_iota(jnp.int32, (tile, 1),
                                                       0)
            carry[...] = jnp.where(rows < total, carry[...],
                                   jnp.zeros_like(carry))
            o_copy(carry, t).start()
            o_copy(carry, t).wait()


@jax.custom_vjp
def swiglu(xs, w_gate, w_up, w_down, sizes):
    """xs [rows, hidden] sorted by expert, `sizes` [count] rows an expert
    -> [rows, hidden]: `(silu(x Wg_e) * (x Wu_e)) Wd_e` for the rows of
    expert e; the rows past the last group are not written (the partial
    tile's are zeros). The caller has asked `takes_kernel()` and masks the
    rows past. Differentiable: the backward is the plain path's."""
    hidden, width = w_gate.shape[1:]
    return _call(xs, w_gate, w_up, w_down, sizes, tile=ROW_TILE,
                 chunk=CHUNK_ROWS,
                 tn=tile_width(hidden, width, w_gate.dtype.itemsize),
                 interpret=_target() == "interpret")


def plain(xs, w_gate, w_up, w_down, sizes):
    """The three `ragged_dot` calls the kernel replaces."""
    a = jax.nn.silu(jax.lax.ragged_dot(xs, w_gate, sizes)) \
        * jax.lax.ragged_dot(xs, w_up, sizes)
    return jax.lax.ragged_dot(a.astype(xs.dtype), w_down, sizes)


def _fwd(*args):
    return swiglu(*args), args


def _bwd(args, ct):
    *operands, sizes = args
    _, vjp = jax.vjp(lambda *a: plain(*a, sizes), *operands)
    return (*vjp(ct), None)


swiglu.defvjp(_fwd, _bwd)


# jitted, so that a program's layers trace and lower ONE kernel between them
@functools.partial(jax.jit, static_argnames=("tile", "chunk", "tn",
                                             "interpret"))
def _call(xs, w_gate, w_up, w_down, sizes, *, tile, chunk, tn, interpret):
    rows, hidden = xs.shape
    count, _, width = w_gate.shape
    sizes = sizes.astype(jnp.int32)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                               jnp.cumsum(sizes, dtype=jnp.int32)])
    # nexts[e]: the first expert from e on that has rows, `count` if none
    held = jnp.where(sizes > 0, jnp.arange(count, dtype=jnp.int32),
                     jnp.int32(count))
    nexts = jnp.concatenate([jax.lax.cummin(held, reverse=True),
                             jnp.full((1,), count, jnp.int32)])
    steps = width // tn
    kernel = functools.partial(_kernel, tile=tile, chunk=chunk, tn=tn,
                               steps=steps)
    item = xs.dtype.itemsize
    vmem = (2 * 3 * hidden * tn * w_gate.dtype.itemsize   # weight buffers
            + 3 * chunk * hidden * item                   # rows, out
            + 2 * chunk * hidden * 4                      # acc, y
            + 3 * chunk * tn * 4)                         # g, u, a
    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((rows, hidden), xs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(count,),
            in_specs=[anywhere] * 4, out_specs=anywhere,
            scratch_shapes=[
                pltpu.VMEM((2, chunk, hidden), xs.dtype),
                pltpu.VMEM((2, hidden, tn), w_gate.dtype),
                pltpu.VMEM((2, hidden, tn), w_up.dtype),
                pltpu.VMEM((2, tn, hidden), w_down.dtype),
                pltpu.VMEM((chunk, hidden) if steps > 1 else (8, 128),
                           jnp.float32),
                pltpu.VMEM((chunk, hidden), xs.dtype),
                pltpu.VMEM((tile, hidden), xs.dtype),
                pltpu.SemaphoreType.DMA((2, 3)),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((1,)),
                pltpu.SMEM((3,), jnp.int32),
            ]),
        # the buffers' phase, the copies in flight and the carried tile go
        # from an expert to the next: the grid runs in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=min(vmem + 16 * 2 ** 20, 100 * 2 ** 20)),
        interpret=interpret, name="grouped_experts")(
            offsets, nexts, xs, w_gate, w_up, w_down)
