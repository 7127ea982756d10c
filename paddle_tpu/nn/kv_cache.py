"""What a layer keeps of a sequence between two calls, how it is written and
read back: the one module that knows. Four kinds: two of rows of keys and
values, where position p lives in a row, one of latent rows that all heads
share, and one of state with no position.

A model declares what each layer keeps and never looks inside a cache:

    model.kv_cache_spec(max_seq_len) -> [KVLayerSpec(kind, rows, kv_heads,
                                                     head_dim)
                                         or LatentLayerSpec("latent", ...)
                                         or StateLayerSpec("state", ...), ...]

- `full`: every position is kept, row p holds position p.
- `window`: the last `rows` positions are kept as a ring, position p in row
  `p % rows`, however long the context.
- `latent`: every position is kept, row p holds position p, and a row is
  ONE vector for all heads: the layer's normalised latent (`latent_dim`
  values, from which keys and values a head are projected) and the one
  rotated key all heads share (`rope_dim`) behind it. There is no `v` beside
  it: the values are a projection of the row's first `latent_dim`.
- `state`: a recurrent layer's matrix a head and the last inputs of its
  causal convolution, the same size whatever the context, rewritten whole at
  every position; nothing in it can be addressed, cut or rewound by position.

An attention layer is handed one handle and calls it once:

    positions = cache.positions(s)            # [b or 1, s]: the chunk's own
    keys, values, held, cache = cache.update(k_new, v_new)

`update` writes the chunk's rows and returns what the queries may read:
`keys` / `values` [b, t, kv_heads, head_dim] and `held` (broadcasts against
[b, s, t]), the absolute position row t holds for each query. A row that holds
nothing yet reports the first position that will land on it, which is after
every query of the chunk, so ONE causal test `held <= position` hides unwritten
rows, stale rows past a rewound offset and later rows alike; a model with a
window adds `held > position - window`. `cache.fresh` (static) says that
nothing was held before this chunk, so the chunk's own keys are all there is
to see.

A model that generates by diffusion over blocks of `block` positions (every
query of a block sees the whole block, in both directions, and every block
before it) asks `block_causal_mask(held, positions, block)` for the test
instead: `held < (position // block + 1) * block`. The same rule about rows
holds, stated once here: a block's `block` queries are written together at
rows `[off, off + block)` (`SlotKV.update` at `s = block`), so those rows are
REWRITTEN by every forward over the block and only count as held once the
offset has passed them ("store the keys and values" is "advance the
offset"); rows at or past `off + block` hold positions after every query of
the block, unwritten or stale alike, and stay hidden. With `block` 1 it is
the causal test.

The handles are pytrees (`lax.scan` carries them, `tree_map` reorders beams):
`ChunkKV` here for a whole batch at one offset (generate(), beam search, a
request's prefill alone), `SlotKV` and `RingKV` for the serving engine's slot
cache at per-row offsets (serving/kv_state.py builds them), and the paged
pool's handle with the same two operations in serving/kv_pages.py.

A `state` layer is handed a `SlotState` and calls it twice:

    state, tail, valid = cache.read()
    cache = cache.replace(new_state, new_tail)

`state` [b, heads, key_dim, value_dim] float32 and `tail` [b, tail_rows,
channels] are what each row of the batch held before the chunk; `valid`
[b, s] says which positions of the chunk are real (a prefill's right-pad and
a decode step's idle slots are not). The layer must leave the state and the
tail of a row as they were over the positions that are not real, because the
cache keeps what the layer returns: `conv_tail` below does it for the tail.

A `latent` layer is handed a `ChunkLatent` (a request alone) or a
`SlotLatent` (the slot cache, a slot at its own offset) and calls it once:

    positions = cache.positions(s)
    rows, held, cache = cache.update(new)     # new [b, s, latent + rope]

`rows` [b, t, width] are the rows as stored, `width >= latent_dim + rope_dim`
with zeros behind what was written, so a query padded with zeros to `width`
scores against a whole row with no slice of the cache in between; `held` is
as above and the same causal test hides unwritten rows.

Where a row lives ON THE DEVICE is decided here too. The slot cache's arrays
(`SlotKV`, `RingKV`; serving/kv_state.py allocates them) are STORED as
[slots, rows, heads, head size] with `stored_dims`' pad: the head size to a
multiple of 128, the heads to a multiple of 8. For such a shape the chip's
default layout is the descending one, heads x head size tiled (8, 128), which
is what the decode loop's fusions keep the array in whatever its shape; for
the unpadded [.., 20, 64] or [.., 30, 128] the default is another (rows on the
lanes, rows under the heads), and every dispatch converted the whole cache
on its way into the loop and out of it. A `latent` layer's rows are stored as
ONE [slots, rows, 640] array (`latent_width`: 512 + 64 = 576 is 4.5 vectors
of 128 lanes, padded to 5): the compiler, asked for a described v5e
(`tools/decode_hlo_probe.py --serving deepseek-v2`, PERF.md PR 35), gives
that shape the descending layout with (8, 128) tiles at the program's entry
and keeps it through the loop, with no cache-sized copy outside or inside
it; 512 beside 64 padded to 128 is the same 640 a position in two arrays and
a concatenation at every read, so it was not taken. Nobody pins a layout:
the stored shape makes the default the right one (a pinned layout does not survive this jax's
persistent compile cache, PERF.md PR 34). `update` writes each new row whole,
zeros in its pad (`padded_rows`), and returns the [kv_heads, head_dim] window
of the stored rows, so the pad is never read and a model sees [b, t,
kv_heads, head_dim] as ever; `logical_rows` is that window for the host's
readers. `ChunkKV` (a request alone) stores exactly what it is given.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.tree_util import register_pytree_node_class

KINDS = ("full", "window", "state", "latent")


class KVLayerSpec(NamedTuple):
    kind: str
    rows: int
    kv_heads: int
    head_dim: int


class StateLayerSpec(NamedTuple):
    """A `state` layer: a float32 matrix [key_dim, value_dim] a head, and the
    last `tail_rows` inputs of a causal convolution over `channels`."""
    kind: str
    heads: int
    key_dim: int
    value_dim: int
    tail_rows: int
    channels: int


class LatentLayerSpec(NamedTuple):
    """A `latent` layer: `rows` positions of `latent_dim + rope_dim` values,
    shared by all heads."""
    kind: str
    rows: int
    latent_dim: int
    rope_dim: int


SPECS = {"state": StateLayerSpec, "latent": LatentLayerSpec}


class BlockDiffusion(NamedTuple):
    """How a model generates, where it is not one token after another: by
    diffusion over blocks of `block_length` positions (a model's
    `generation`). A block starts as `mask_token_id` at every position that
    is not given; a forward over it under `block_causal_mask` proposes a
    token at every position and the most confident are kept, until a forward
    over a block with no mask left commits it. The rest are a request's
    defaults: the forwards a block's positions are spread over, how the
    positions to keep are chosen (serving/diffusion.py `REMASKING`), and the
    confidence above which `low_confidence_dynamic` keeps a position at
    once."""
    block_length: int
    mask_token_id: int
    denoising_steps: int = 4
    remasking: str = "low_confidence_static"
    confidence_threshold: float = 0.9


def latent_width(layer: LatentLayerSpec) -> int:
    """The width a slot cache STORES a latent row at: a multiple of the
    chip's 128 lanes (576 -> 640)."""
    return -(-(layer.latent_dim + layer.rope_dim) // 128) * 128


def widened_rows(new, stored):
    """`new` [b, s, latent + rope] in `stored`'s dtype and as wide as
    `stored` [slots, rows, width]: zeros in the pad, so that a latent row is
    written whole."""
    new = new.astype(stored.dtype)
    pad = stored.shape[-1] - new.shape[-1]
    return jnp.pad(new, [(0, 0), (0, 0), (0, pad)]) if pad else new


def stored_dims(kv_heads: int, head_dim: int):
    """(heads, head size) of the array a slot cache STORES for rows of
    [kv_heads, head_dim]: the head size a multiple of the chip's 128 lanes
    and the heads a multiple of its 8 sublanes (2 and 4 have tiles of their
    own; a single head is left alone, its rows tile densely as they are),
    which is what the tiles of the decode loop pad them to in any case. For
    such a shape the chip's default layout is the descending one the loop
    keeps the array in, so the cache crosses every program's boundary as it
    is; for [.., 20, 64] or [.., 30, 128] the default moves the rows under
    the heads or onto the lanes and each dispatch converts the whole cache
    on the way in and out (PERF.md, PR 34)."""
    heads = kv_heads if kv_heads in (1, 2, 4) else -(-kv_heads // 8) * 8
    return heads, -(-head_dim // 128) * 128


def logical_rows(stored, kv_heads: int, head_dim: int):
    """[.., kv_heads, head_dim] of a stored [.., heads, head size] array:
    the array itself where nothing was padded."""
    if stored.shape[-2:] == (kv_heads, head_dim):
        return stored
    return stored[..., :kv_heads, :head_dim]


def padded_rows(new, stored):
    """`new` [.., kv_heads, head_dim] in `stored`'s dtype and as wide as
    `stored` [.., heads, head size]: zeros in the pad, so that a row is
    written whole (a write of part of a tile costs a pass of its own)."""
    new = new.astype(stored.dtype)
    heads = stored.shape[-2] - new.shape[-2]
    size = stored.shape[-1] - new.shape[-1]
    if heads or size:
        new = jnp.pad(new, [(0, 0)] * (new.ndim - 2) + [(0, heads), (0, size)])
    return new


def _write_rows(stored, slots, rows, new):
    """`stored` with `new` [b, s, kv_heads, head_dim] at rows `rows` [b, s]
    of slots `slots` [b, 1], and what a query may read of it: -> (stored,
    [b, rows, kv_heads, head_dim])."""
    stored = stored.at[slots, rows].set(padded_rows(new, stored))
    return stored, logical_rows(stored, *new.shape[2:])


def block_causal_mask(held, positions, block: int):
    """[b, s, t] bool: the query at `positions` [b or 1, s] sees the row that
    holds position `held` (broadcasts against [b, s, t]) iff the row's block
    is the query's or an earlier one, `held // block <= position // block`,
    written as one comparison against the end of the query's block."""
    ends = (positions // block + 1) * block
    return held < ends[:, :, None]


def ring_row(pos, rows: int):
    """The row of a ring of `rows` rows that holds position `pos`."""
    return pos % rows


def ring_held(tip, rows: int):
    """[..., rows]: the position each row of a ring holds once position `tip`
    is written, the last p <= tip with `ring_row(p) == r`; a row no position
    has reached reports r itself, the first that will land there."""
    r = jnp.arange(rows, dtype=jnp.int32)
    tip = tip[..., None]
    return jnp.maximum(tip - (tip - r) % rows, r)


class _KV:
    """k, v [b, rows, kv_heads, head_dim] (a slot layer's: as stored, with
    `stored_dims`' pad) and `offset`, the count of positions already held
    (int32: a scalar for the batch or one a row)."""

    fresh = False

    def __init__(self, k, v, offset):
        self.k, self.v, self.offset = k, v, offset

    def tree_flatten(self):
        return (self.k, self.v, self.offset), None

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves)

    def _advanced(self, k, v, s: int):
        return type(self)(k, v, self.offset + jnp.int32(s))


@register_pytree_node_class
class ChunkKV(_KV):
    """Every sequence of the batch at one scalar offset; row p holds
    position p. The new chunk lands at [offset, offset + s)."""

    def __init__(self, k, v, offset, fresh=False):
        super().__init__(k, v, offset)
        self.fresh = fresh

    @classmethod
    def zeros(cls, batch: int, rows: int, kv_heads: int, head_dim: int, dtype):
        def make():
            return jnp.zeros((batch, rows, kv_heads, head_dim), dtype)

        return cls(make(), make(), jnp.int32(0), fresh=True)

    def tree_flatten(self):
        return (self.k, self.v, self.offset), self.fresh

    @classmethod
    def tree_unflatten(cls, fresh, leaves):
        return cls(*leaves, fresh=fresh)

    def _advanced(self, k, v, s):
        return ChunkKV(k, v, self.offset + jnp.int32(s))

    def rewound(self, offset):
        """The same rows with `offset` positions counted as held (a bucketed
        prompt resumes at its true length: the pad's rows are overwritten
        before any query sees them)."""
        return ChunkKV(self.k, self.v, offset)

    def positions(self, s: int):
        return self.offset + jnp.arange(s, dtype=jnp.int32)[None, :]

    def update(self, k_new, v_new):
        zero = jnp.int32(0)
        at = (zero, self.offset, zero, zero)
        k = jax.lax.dynamic_update_slice(self.k, k_new.astype(self.k.dtype), at)
        v = jax.lax.dynamic_update_slice(self.v, v_new.astype(self.v.dtype), at)
        held = jnp.arange(k.shape[1], dtype=jnp.int32)[None, None, :]
        return k, v, held, self._advanced(k, v, k_new.shape[1])


@register_pytree_node_class
class SlotKV(_KV):
    """A `full` layer of the slot cache: each row of the batch is a slot at
    its own offset, row p of a slot holds position p. Rows past a slot's
    offset are never seen (the causal test), so retired and short slots stay
    inert and one batched step serves slots at any depth. Positions are in
    jnp's default integer width, the form GPT-2's decode programs have been
    compiled and measured in."""

    def positions(self, s: int):
        return self.offset[:, None] + jnp.arange(s)[None, :]

    def update(self, k_new, v_new):
        b, s = k_new.shape[0], k_new.shape[1]
        total = self.k.shape[1]
        slots = jnp.arange(b)[:, None]
        pos = jnp.clip(self.offset[:, None] + jnp.arange(s)[None, :], 0,
                       total - 1)
        k, keys = _write_rows(self.k, slots, pos, k_new)
        v, values = _write_rows(self.v, slots, pos, v_new)
        held = jnp.arange(total)[None, None, :]
        return keys, values, held, self._advanced(k, v, s)


@register_pytree_node_class
class RingKV(_KV):
    """A `window` layer of the slot cache: each slot keeps its last `rows`
    positions, position p in row `ring_row(p)`."""

    def positions(self, s: int):
        return self.offset[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]

    def update(self, k_new, v_new):
        b, s = k_new.shape[0], k_new.shape[1]
        rows = self.k.shape[1]
        pos = self.positions(s)
        slots = jnp.arange(b)[:, None]
        at = ring_row(pos, rows)
        k, keys = _write_rows(self.k, slots, at, k_new)
        v, values = _write_rows(self.v, slots, at, v_new)
        return keys, values, ring_held(pos, rows), self._advanced(k, v, s)


class _Latent:
    """rows [b, rows, width] and `offset`, the count of positions already
    held (int32: a scalar for the batch or one a row)."""

    fresh = False

    def __init__(self, rows, offset):
        self.rows, self.offset = rows, offset

    def tree_flatten(self):
        return (self.rows, self.offset), None

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves)


@register_pytree_node_class
class ChunkLatent(_Latent):
    """Every sequence of the batch at one scalar offset, row p holds
    position p, the rows as wide as they are given."""

    def __init__(self, rows, offset, fresh=False):
        super().__init__(rows, offset)
        self.fresh = fresh

    @classmethod
    def zeros(cls, batch: int, layer: LatentLayerSpec, dtype, rows=None):
        return cls(jnp.zeros((batch, layer.rows if rows is None else rows,
                              layer.latent_dim + layer.rope_dim), dtype),
                   jnp.int32(0), fresh=True)

    def tree_flatten(self):
        return (self.rows, self.offset), self.fresh

    @classmethod
    def tree_unflatten(cls, fresh, leaves):
        return cls(*leaves, fresh=fresh)

    def positions(self, s: int):
        return self.offset + jnp.arange(s, dtype=jnp.int32)[None, :]

    def update(self, new):
        zero = jnp.int32(0)
        rows = jax.lax.dynamic_update_slice(
            self.rows, new.astype(self.rows.dtype), (zero, self.offset, zero))
        held = jnp.arange(rows.shape[1], dtype=jnp.int32)[None, None, :]
        return rows, held, ChunkLatent(rows,
                                       self.offset + jnp.int32(new.shape[1]))


@register_pytree_node_class
class SlotLatent(_Latent):
    """A `latent` layer of the slot cache: each row of the batch is a slot
    at its own offset, row p of a slot holds position p, stored
    `latent_width` wide with zeros in the pad. Rows past a slot's offset are
    never seen (the causal test)."""

    def positions(self, s: int):
        return self.offset[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]

    def update(self, new):
        b, s = new.shape[0], new.shape[1]
        total = self.rows.shape[1]
        pos = jnp.clip(self.positions(s), 0, total - 1)
        rows = self.rows.at[jnp.arange(b)[:, None], pos].set(
            widened_rows(new, self.rows))
        held = jnp.arange(total, dtype=jnp.int32)[None, None, :]
        return rows, held, SlotLatent(rows, self.offset + jnp.int32(s))


@register_pytree_node_class
class SlotState:
    """A `state` layer's handle: what each row of the batch held before the
    chunk, and which of the chunk's positions are real."""

    fresh = False

    def __init__(self, state, tail, valid):
        self.state, self.tail, self.valid = state, tail, valid

    def tree_flatten(self):
        return (self.state, self.tail, self.valid), None

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves)

    @classmethod
    def zeros(cls, batch: int, layer: StateLayerSpec, dtype, valid):
        return cls(jnp.zeros((batch, layer.heads, layer.key_dim,
                              layer.value_dim), jnp.float32),
                   jnp.zeros((batch, layer.tail_rows, layer.channels), dtype),
                   valid)

    def read(self):
        return self.state, self.tail, self.valid

    def replace(self, state, tail):
        return SlotState(state.astype(self.state.dtype),
                         tail.astype(self.tail.dtype), self.valid)


def conv_tail(tail, z, valid):
    """The last `tail_rows` real inputs of a causal convolution after a
    chunk: `tail` [b, rows, channels] held before it, `z` [b, s, channels]
    the chunk's inputs, of which the first `valid.sum(1)` a row are real."""
    rows = tail.shape[1]
    seen = jnp.concatenate([tail.astype(z.dtype), z], axis=1)
    at = valid.sum(1, dtype=jnp.int32)[:, None] + jnp.arange(rows)[None, :]
    return jnp.take_along_axis(seen, at[:, :, None], axis=1)
