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
"""
from __future__ import annotations

from typing import List, Sequence

from ..nn.kv_cache import (KINDS, ChunkKV, KVLayerSpec, RingKV, SlotKV,
                           ring_held)


def spec_of(model, max_seq_len: int) -> List[KVLayerSpec]:
    spec = [KVLayerSpec(*s) for s in model.kv_cache_spec(int(max_seq_len))]
    for s in spec:
        if s.kind not in KINDS:
            raise ValueError(f"unknown cache kind {s.kind!r} "
                             f"(expected one of {KINDS})")
    return spec


def window_layers(spec: Sequence[KVLayerSpec]) -> List[int]:
    return [i for i, s in enumerate(spec) if s.kind == "window"]


def scatter_prefill(layer: KVLayerSpec, big, local, slot, plen):
    """Write a request's prefilled rows `local` [1, bucket, ...] into row
    `slot` of the slot cache `big` [slots, rows, ...]. `slot` and `plen` (the
    true prompt length) are traced."""
    import jax
    import jax.numpy as jnp

    local = local.astype(big.dtype)
    bucket = local.shape[1]
    if layer.kind == "window" and bucket > layer.rows:
        # each row of the ring takes the position it holds after the prompt;
        # a row no position has reached takes a pad row, which the position
        # it reports hides
        held = ring_held(plen - 1, layer.rows)
        local = jnp.take(local, jnp.clip(held, 0, bucket - 1), axis=1)
    return jax.lax.dynamic_update_slice(
        big, local, (slot, jnp.int32(0), jnp.int32(0), jnp.int32(0)))


class SlotCache:
    """One [slots, rows, kv_heads, head_dim] pair of arrays a layer (`k`,
    `v`): `rows` is `max_seq_len` for a `full` layer, the window for a
    `window` layer."""

    n_args = 2
    masks_writes = False
    prefill_at = ("slot",)

    def __init__(self, spec: Sequence[KVLayerSpec], slots: int,
                 max_seq_len: int, dtype):
        import jax.numpy as jnp

        self.spec = list(spec)
        self.max_seq_len = int(max_seq_len)
        self.dtype = dtype

        def make():
            return [jnp.zeros((slots, s.rows, s.kv_heads, s.head_dim), dtype)
                    for s in self.spec]

        self.k, self.v = make(), make()

    # ---- between dispatches -------------------------------------------
    def args(self):
        return self.k, self.v

    def take(self, results, stepped=None) -> None:
        self.k, self.v = results

    def nbytes(self) -> int:
        return sum(int(a.size) * a.dtype.itemsize for a in (*self.k, *self.v))

    def cover(self, active, offsets, last) -> None:
        pass

    def release(self, slot: int) -> None:
        pass

    def truncate(self, slot: int, keep: int) -> None:
        pass

    def gauges(self) -> dict:
        return {}

    # ---- inside a traced program --------------------------------------
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
        return [(RingKV if s.kind == "window" else SlotKV)(k, v, offsets)
                for s, k, v in zip(self.spec, *args)]

    def absorb(self, args, handles, active):
        return [h.k for h in handles], [h.v for h in handles]

    def prefill_views(self, args, bucket: int, length, slot):
        """Fresh caches for one request alone, `bucket` rows each; causal
        masking makes the right-pad inert. Where the rows go (`length`,
        `slot`) is `commit_prefill`'s to read."""
        return [ChunkKV.zeros(1, bucket, s.kv_heads, s.head_dim, self.dtype)
                for s in self.spec]

    def commit_prefill(self, args, handles, length, slot):
        ks, vs = [], []
        for s, big_k, big_v, local in zip(self.spec, *args, handles):
            ks.append(scatter_prefill(s, big_k, local.k, slot, length))
            vs.append(scatter_prefill(s, big_v, local.v, slot, length))
        return ks, vs

    @staticmethod
    def first_position(length, slot):
        """The position of the request's first generated token."""
        return length
