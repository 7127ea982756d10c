"""The slot cache the serving engine holds: what a served model keeps per
layer, laid out for `slots` concurrent requests.

A model declares its state (`kv_cache_spec`, nn/kv_cache.py); the engine
builds ONE object from the declaration and its `kv_layout`, passes it whole
and never looks at the model's config or at where a row lives. `SlotCache`
here is the contiguous layout; `PagedSlotCache` (kv_pages.py) has the same
interface over a page pool. The draft model's cache is a second `SlotCache`.

Between dispatches (host):
    args()                 the cache's device state, a program's leading
                           cache arguments, every one donated (`n_args`)
    take(results, stepped) the first `n_args` results of that program, and
                           the rows a decode or verify ran as active (None
                           after a prefill, which steps no seated row)
    cover(...) / release(slot) / truncate(slot, keep) / gauges()
                           page accounting; nothing to do for fixed rows
    nbytes()

Inside a traced program, over the traced `args`:
    views(args, offsets, write_mask) -> one handle a layer for the model
    absorb(args, handles, active)    -> the new args after the model ran
    tip(offsets)                     -> the offsets a decode step writes at
    prefill_views(args, bucket, length, *at) / commit_prefill(args, handles,
        length, *at) / first_position(length, *at): a request's prefill;
        `at` says where it goes and `prefill_at` names its parts. Here the
        request runs alone over a fresh cache of its bucket's rows, and
        `commit_prefill` writes that into the slot's rows: all of it for a
        `full` layer, the last `rows` positions of the prompt for a `window`
        layer.

What the arrays are on the device: `k_stored` / `v_stored`, one [slots, rows,
heads, head size] a layer with `stored_dims`' pad (nn/kv_cache.py: the shape
whose default device layout is the one the decode loop keeps, so no program
converts the cache at its boundary and nothing is pinned), are what `args()`
hands every program and `take()` receives. `k` / `v` are the same rows as
[slots, rows, kv_heads, head_dim], a view computed at each read: what the
benchmark's checks, the rehearsals and the tests index on the host
(`.shape[1]`, `[slot]`, `[:held]`), never a program's argument.

A `state` layer (nn/kv_cache.py: a matrix a head and a convolution's tail, no
position) has its arrays in `state` / `tail` beside `k` / `v`, and the cache
then has four arguments, not two. Its handle tells the layer which positions
are real (`valid`: below `length` in a prefill, the active rows in a decode
step) and the layer leaves the rest alone, so an idle slot's state stays as
it was; a prefill starts from zeros and `commit_prefill` copies its last
state over the slot's, so nothing of the slot's last request is left.
"""
from __future__ import annotations

from collections.abc import Sequence
from typing import List

from ..nn.kv_cache import (KINDS, ChunkKV, KVLayerSpec, RingKV, SlotKV,
                           SlotState, StateLayerSpec, logical_rows,
                           padded_rows, ring_held, stored_dims)


def spec_of(model, max_seq_len: int) -> list:
    spec = []
    for s in model.kv_cache_spec(int(max_seq_len)):
        if s[0] not in KINDS:
            raise ValueError(f"unknown cache kind {s[0]!r} "
                             f"(expected one of {KINDS})")
        spec.append((StateLayerSpec if s[0] == "state" else KVLayerSpec)(*s))
    return spec


def window_layers(spec: Sequence) -> List[int]:
    return [i for i, s in enumerate(spec) if s.kind == "window"]


def state_layers(spec: Sequence) -> List[int]:
    return [i for i, s in enumerate(spec) if s.kind == "state"]


def refuse_state_layers(spec: Sequence, who: str, why: str) -> None:
    """Raise for a model whose layers `who` cannot hold, by name."""
    layers = state_layers(spec)
    if layers:
        raise ValueError(
            f"{who} cannot hold this model: layers {layers} keep a "
            f"recurrent state (a matrix a head and a convolution's tail, "
            f"rewritten whole at every position), and {why}")


def scatter_prefill(layer: KVLayerSpec, big, local, slot, plen):
    """Write a request's prefilled rows `local` [1, bucket, kv_heads,
    head_dim] into row `slot` of the slot cache `big` [slots, rows, ...] as
    stored (whole rows, zeros in the pad). `slot` and `plen` (the true
    prompt length) are traced."""
    import jax
    import jax.numpy as jnp

    bucket = local.shape[1]
    if layer.kind == "window" and bucket > layer.rows:
        # each row of the ring takes the position it holds after the prompt;
        # a row no position has reached takes a pad row, which the position
        # it reports hides
        held = ring_held(plen - 1, layer.rows)
        local = jnp.take(local, jnp.clip(held, 0, bucket - 1), axis=1)
    return jax.lax.dynamic_update_slice(
        big, padded_rows(local, big),
        (slot, jnp.int32(0), jnp.int32(0), jnp.int32(0)))


class _Rows(Sequence):
    """Stored arrays read as one [slots, rows, kv_heads, head_dim] array a
    layer; a layer's is cut out of its stored array when it is indexed (on
    the device: a copy of that layer for as long as the reader holds it)."""

    def __init__(self, spec, stored):
        self._dims = [(s.kv_heads, s.head_dim) for s in spec
                      if s.kind != "state"]
        self._stored = stored

    def __len__(self):
        return len(self._stored)

    def __getitem__(self, layer):
        return logical_rows(self._stored[layer], *self._dims[layer])


class SlotCache:
    """One [slots, rows, kv_heads, head_dim] pair of arrays (`k`, `v`; stored
    padded as `k_stored`, `v_stored`, this module's header) a
    `full` or `window` layer: `rows` is `max_seq_len` for a `full` layer, the
    window for a `window` layer. One [slots, heads, key_dim, value_dim]
    float32 matrix (`state`) and one [slots, tail_rows, channels] array
    (`tail`) a `state` layer. Each list holds its own layers in the order of
    the spec; a spec with no `state` layer has the two arguments `k`, `v`
    and nothing else."""

    masks_writes = False
    prefill_at = ("slot",)

    def __init__(self, spec: Sequence, slots: int, max_seq_len: int, dtype):
        import jax.numpy as jnp

        self.spec = list(spec)
        self.max_seq_len = int(max_seq_len)
        self.dtype = dtype
        rows = [s for s in self.spec if s.kind != "state"]
        held = [s for s in self.spec if s.kind == "state"]

        def make():
            return [jnp.zeros((slots, s.rows)
                              + stored_dims(s.kv_heads, s.head_dim), dtype)
                    for s in rows]

        self.k_stored, self.v_stored = make(), make()
        self.state = [jnp.zeros((slots, s.heads, s.key_dim, s.value_dim),
                                jnp.float32) for s in held]
        self.tail = [jnp.zeros((slots, s.tail_rows, s.channels), dtype)
                     for s in held]
        self.n_args = 4 if held else 2

    @property
    def k(self):
        """One [slots, rows, kv_heads, head_dim] array a `full` or `window`
        layer: what the slots hold, for checks, rehearsals and tests to
        read."""
        return _Rows(self.spec, self.k_stored)

    @property
    def v(self):
        return _Rows(self.spec, self.v_stored)

    # ---- between dispatches -------------------------------------------
    def args(self):
        return (self.k_stored, self.v_stored, self.state,
                self.tail)[:self.n_args]

    def take(self, results, stepped=None) -> None:
        self.k_stored, self.v_stored, *held = results
        if held:
            self.state, self.tail = held

    def state_bytes(self) -> int:
        return sum(int(a.size) * a.dtype.itemsize
                   for a in (*self.state, *self.tail))

    def nbytes(self) -> int:
        return self.state_bytes() + sum(
            int(a.size) * a.dtype.itemsize
            for a in (*self.k_stored, *self.v_stored))

    def cover(self, active, offsets, last) -> None:
        pass

    def release(self, slot: int) -> None:
        pass

    def truncate(self, slot: int, keep: int) -> None:
        pass

    def gauges(self) -> dict:
        return {"state_bytes": self.state_bytes()} if self.state else {}

    # ---- inside a traced program --------------------------------------
    def _by_layer(self, args):
        """The traced `args` as one tuple a layer of the spec: (k, v) or
        (state, tail)."""
        rows = zip(args[0], args[1])
        held = zip(*args[2:]) if self.n_args == 4 else iter(())
        return [next(held if s.kind == "state" else rows) for s in self.spec]

    def _as_args(self, pairs):
        """One pair of arrays a layer, (k, v) or (state, tail) -> the
        arguments, as `args()` orders them."""
        rows = [p for s, p in zip(self.spec, pairs) if s.kind != "state"]
        held = [p for s, p in zip(self.spec, pairs) if s.kind == "state"]
        out = ([a for a, _ in rows], [b for _, b in rows])
        if self.n_args == 4:
            out += ([a for a, _ in held], [b for _, b in held])
        return out

    def tip(self, offsets):
        """Idle slots keep writing their (unread) tip row; a full slot must
        not index past the cache."""
        import jax.numpy as jnp

        return jnp.minimum(offsets, jnp.int32(self.max_seq_len - 1))

    def views(self, args, offsets, write_mask):
        """`write_mask` is the pool's to read. Here a row that must not count
        writes at its own offset, where nothing reads before it is written
        again."""
        import jax.numpy as jnp

        offsets = offsets.astype(jnp.int32)
        return [SlotState(a, b, write_mask[:, None]) if s.kind == "state"
                else (RingKV if s.kind == "window" else SlotKV)(a, b, offsets)
                for s, (a, b) in zip(self.spec, self._by_layer(args))]

    def absorb(self, args, handles, active):
        return self._as_args([(h.state, h.tail) if s.kind == "state"
                              else (h.k, h.v)
                              for s, h in zip(self.spec, handles)])

    def prefill_views(self, args, bucket: int, length, slot):
        """Fresh caches for one request alone, `bucket` rows each; causal
        masking makes the right-pad inert for rows, and a `state` layer is
        told that the positions from `length` on are not real. Where the
        rows go (`slot`) is `commit_prefill`'s to read."""
        import jax.numpy as jnp

        def held(s):
            valid = jnp.arange(bucket, dtype=jnp.int32)[None, :] < length
            return SlotState.zeros(1, s, self.dtype, valid)

        return [held(s) if s.kind == "state"
                else ChunkKV.zeros(1, bucket, s.kv_heads, s.head_dim,
                                   self.dtype)
                for s in self.spec]

    def commit_prefill(self, args, handles, length, slot):
        import jax
        import jax.numpy as jnp

        def put(big, local):
            at = (slot,) + (jnp.int32(0),) * (big.ndim - 1)
            return jax.lax.dynamic_update_slice(
                big, local.astype(big.dtype), at)

        out = []
        for s, (a, b), local in zip(self.spec, self._by_layer(args), handles):
            if s.kind == "state":
                with jax.named_scope("state_write"):
                    out.append((put(a, local.state), put(b, local.tail)))
            else:
                out.append((scatter_prefill(s, a, local.k, slot, length),
                            scatter_prefill(s, b, local.v, slot, length)))
        return self._as_args(out)

    @staticmethod
    def first_position(length, slot):
        """The position of the request's first generated token."""
        return length
