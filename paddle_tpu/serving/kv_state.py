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

A `latent` layer (nn/kv_cache.py: one row a position for all heads) has ONE
array in `latent_stored`, [slots, rows, `latent_width`], after the others in
`args()`; `latent` is the same rows as [slots, rows, latent_dim + rope_dim]
for the host's readers. A prefill runs over a `ChunkLatent` of its bucket's
rows and `commit_prefill` writes them, padded, at the slot's row 0.
"""
from __future__ import annotations

from collections.abc import Sequence
from typing import List

from ..nn.kv_cache import (KINDS, SPECS, ChunkKV, ChunkLatent, KVLayerSpec,
                           RingKV, SlotKV, SlotLatent, SlotState,
                           latent_width, logical_rows, padded_rows, ring_held,
                           stored_dims, widened_rows)


def spec_of(model, max_seq_len: int) -> list:
    spec = []
    for s in model.kv_cache_spec(int(max_seq_len)):
        if s[0] not in KINDS:
            raise ValueError(f"unknown cache kind {s[0]!r} "
                             f"(expected one of {KINDS})")
        spec.append(SPECS.get(s[0], KVLayerSpec)(*s))
    return spec


def window_layers(spec: Sequence) -> List[int]:
    return [i for i, s in enumerate(spec) if s.kind == "window"]


def state_layers(spec: Sequence) -> List[int]:
    return [i for i, s in enumerate(spec) if s.kind == "state"]


def latent_layers(spec: Sequence) -> List[int]:
    return [i for i, s in enumerate(spec) if s.kind == "latent"]


def _refuse(layers: List[int], keep: str, who: str, why: str) -> None:
    if layers:
        raise ValueError(f"{who} cannot hold this model: layers {layers} "
                         f"keep {keep}, and {why}")


def refuse_latent_layers(spec: Sequence, who: str, why: str) -> None:
    """Raise for a model whose layers `who` cannot hold, by name."""
    _refuse(latent_layers(spec),
            "latent rows (one row a position shared by all heads, with no "
            "values beside it)", who, why)


def refuse_state_layers(spec: Sequence, who: str, why: str) -> None:
    """Raise for a model whose layers `who` cannot hold, by name."""
    _refuse(state_layers(spec),
            "a recurrent state (a matrix a head and a convolution's tail, "
            "rewritten whole at every position)", who, why)


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


# a `SlotCache`'s groups of arrays, in the order of its arguments: rows of
# keys and values (always there), a state and its tail, latent rows
_GROUPS = (("k_stored", "v_stored"), ("state", "tail"), ("latent_stored",))


def _group(layer) -> int:
    """Which of `_GROUPS` holds a layer's arrays."""
    return {"state": 1, "latent": 2}.get(layer.kind, 0)


class _Rows(Sequence):
    """Stored arrays read as one [slots, rows, kv_heads, head_dim] array a
    layer; a layer's is cut out of its stored array when it is indexed (on
    the device: a copy of that layer for as long as the reader holds it)."""

    def __init__(self, spec, stored):
        self._dims = [(s.kv_heads, s.head_dim) for s in spec
                      if _group(s) == 0]
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
    (`tail`) a `state` layer. One [slots, rows, latent_width] array
    (`latent_stored`) a `latent` layer. Each list holds its own layers in
    the order of the spec; a spec with no `state` and no `latent` layer has
    the two arguments `k`, `v` and nothing else."""

    masks_writes = False
    prefill_at = ("slot",)

    def __init__(self, spec: Sequence, slots: int, max_seq_len: int, dtype):
        import jax.numpy as jnp

        self.spec = list(spec)
        self.max_seq_len = int(max_seq_len)
        self.dtype = dtype
        rows, held, latent = ([s for s in self.spec if _group(s) == g]
                              for g in range(3))

        def make():
            return [jnp.zeros((slots, s.rows)
                              + stored_dims(s.kv_heads, s.head_dim), dtype)
                    for s in rows]

        self.k_stored, self.v_stored = make(), make()
        self.state = [jnp.zeros((slots, s.heads, s.key_dim, s.value_dim),
                                jnp.float32) for s in held]
        self.tail = [jnp.zeros((slots, s.tail_rows, s.channels), dtype)
                     for s in held]
        self.latent_stored = [jnp.zeros((slots, s.rows, latent_width(s)),
                                        dtype) for s in latent]
        # the arguments: `k`, `v`, then the groups this spec has, in order
        self._names = _GROUPS[0] + (_GROUPS[1] if held else ()) \
            + (_GROUPS[2] if latent else ())
        self.n_args = len(self._names)

    @property
    def k(self):
        """One [slots, rows, kv_heads, head_dim] array a `full` or `window`
        layer: what the slots hold, for checks, rehearsals and tests to
        read."""
        return _Rows(self.spec, self.k_stored)

    @property
    def v(self):
        return _Rows(self.spec, self.v_stored)

    @property
    def latent(self):
        """One [slots, rows, latent_dim + rope_dim] array a `latent` layer,
        the stored rows without their pad."""
        widths = [s.latent_dim + s.rope_dim for s in self.spec
                  if _group(s) == 2]
        return [a[..., :w] for a, w in zip(self.latent_stored, widths)]

    # ---- between dispatches -------------------------------------------
    def args(self):
        return tuple(getattr(self, name) for name in self._names)

    def take(self, results, stepped=None) -> None:
        for name, arrays in zip(self._names, results):
            setattr(self, name, arrays)

    def state_bytes(self) -> int:
        return sum(int(a.size) * a.dtype.itemsize
                   for a in (*self.state, *self.tail))

    def latent_bytes(self) -> int:
        return sum(int(a.size) * a.dtype.itemsize for a in self.latent_stored)

    def nbytes(self) -> int:
        return self.state_bytes() + self.latent_bytes() + sum(
            int(a.size) * a.dtype.itemsize
            for a in (*self.k_stored, *self.v_stored))

    def cover(self, active, offsets, last) -> None:
        pass

    def release(self, slot: int) -> None:
        pass

    def truncate(self, slot: int, keep: int) -> None:
        pass

    def gauges(self) -> dict:
        out = {"state_bytes": self.state_bytes()} if self.state else {}
        if self.latent_stored:
            out["latent_bytes"] = self.latent_bytes()
        return out

    # ---- inside a traced program --------------------------------------
    def _by_layer(self, args):
        """The traced `args` as one tuple a layer of the spec: (k, v),
        (state, tail) or (latent rows,)."""
        given = dict(zip(self._names, args))
        groups = [zip(*(given[n] for n in names)) if names[0] in given
                  else iter(()) for names in _GROUPS]
        return [next(groups[_group(s)]) for s in self.spec]

    def _as_args(self, parts):
        """One tuple of arrays a layer, as `_by_layer` gives them -> the
        arguments, as `args()` orders them."""
        out = {}
        for g, names in enumerate(_GROUPS):
            mine = [p for s, p in zip(self.spec, parts) if _group(s) == g]
            for i, name in enumerate(names):
                out[name] = [p[i] for p in mine]
        return tuple(out[name] for name in self._names)

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

        def view(s, held):
            if s.kind == "state":
                return SlotState(*held, write_mask[:, None])
            if s.kind == "latent":
                return SlotLatent(*held, offsets)
            return (RingKV if s.kind == "window" else SlotKV)(*held, offsets)

        return [view(s, held)
                for s, held in zip(self.spec, self._by_layer(args))]

    def absorb(self, args, handles, active):
        return self._as_args([(h.state, h.tail) if s.kind == "state"
                              else (h.rows,) if s.kind == "latent"
                              else (h.k, h.v)
                              for s, h in zip(self.spec, handles)])

    def prefill_views(self, args, bucket: int, length, slot):
        """Fresh caches for one request alone, `bucket` rows each; causal
        masking makes the right-pad inert for rows, and a `state` layer is
        told that the positions from `length` on are not real. Where the
        rows go (`slot`) is `commit_prefill`'s to read."""
        import jax.numpy as jnp

        def fresh(s):
            if s.kind == "state":
                valid = jnp.arange(bucket, dtype=jnp.int32)[None, :] < length
                return SlotState.zeros(1, s, self.dtype, valid)
            if s.kind == "latent":
                return ChunkLatent.zeros(1, s, self.dtype, rows=bucket)
            return ChunkKV.zeros(1, bucket, s.kv_heads, s.head_dim,
                                 self.dtype)

        return [fresh(s) for s in self.spec]

    def commit_prefill(self, args, handles, length, slot):
        import jax
        import jax.numpy as jnp

        def put(big, local):
            at = (slot,) + (jnp.int32(0),) * (big.ndim - 1)
            return jax.lax.dynamic_update_slice(
                big, local.astype(big.dtype), at)

        out = []
        for s, held, local in zip(self.spec, self._by_layer(args), handles):
            if s.kind == "state":
                with jax.named_scope("state_write"):
                    out.append((put(held[0], local.state),
                                put(held[1], local.tail)))
            elif s.kind == "latent":
                # whole rows: zeros in the pad, as a decode step writes them
                big, = held
                out.append((put(big, widened_rows(local.rows, big)),))
            else:
                out.append((scatter_prefill(s, held[0], local.k, slot,
                                            length),
                            scatter_prefill(s, held[1], local.v, slot,
                                            length)))
        return self._as_args(out)

    @staticmethod
    def first_position(length, slot):
        """The position of the request's first generated token."""
        return length
