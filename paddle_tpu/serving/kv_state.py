"""What a served model keeps per layer, and the slot cache built from it.

A model declares its state, the engine allocates, donates and scatters by
the declaration and never looks at the model's config:

    model.kv_cache_spec(max_seq_len) -> [KVLayerSpec(kind, rows, kv_heads,
                                                     head_dim), ...]

- `full`: a slot keeps every position, row p holds position p; `rows` is the
  engine's `max_seq_len`.
- `window`: a slot keeps its last `rows` positions as a ring, position p in
  row `p % rows`, however long the slot's context. The model's attention
  writes there and masks each row by the true position it holds.

Prefill runs a request alone over a fresh cache of its bucket's rows; the
engine then writes that into the slot's rows (`scatter_prefill`): all of it
for a `full` layer, the last `rows` positions of the prompt for a `window`
layer. Latent rows and recurrent state would be further kinds (ROADMAP.md).
"""
from __future__ import annotations

from typing import List, NamedTuple, Sequence

KINDS = ("full", "window")


class KVLayerSpec(NamedTuple):
    kind: str
    rows: int
    kv_heads: int
    head_dim: int


def spec_of(model, max_seq_len: int) -> List[KVLayerSpec]:
    spec = [KVLayerSpec(*s) for s in model.kv_cache_spec(int(max_seq_len))]
    for s in spec:
        if s.kind not in KINDS:
            raise ValueError(f"unknown cache kind {s.kind!r} "
                             f"(expected one of {KINDS})")
    return spec


def window_layers(spec: Sequence[KVLayerSpec]) -> List[int]:
    return [i for i, s in enumerate(spec) if s.kind == "window"]


def allocate(spec: Sequence[KVLayerSpec], slots: int, dtype):
    """The slot cache: (k arrays, v arrays), one [slots, rows, kv_heads,
    head_dim] pair a layer."""
    import jax.numpy as jnp

    def make():
        return [jnp.zeros((slots, s.rows, s.kv_heads, s.head_dim), dtype)
                for s in spec]

    return make(), make()


def request_local(spec: Sequence[KVLayerSpec], bucket: int, dtype):
    """Fresh caches for one request's prefill, `bucket` rows each, in the
    `(k, v, offset)` form the models take; causal masking makes the
    right-pad inert."""
    import jax.numpy as jnp

    from ..core.tensor import Tensor

    return [(Tensor(jnp.zeros((1, bucket, s.kv_heads, s.head_dim), dtype)),
             Tensor(jnp.zeros((1, bucket, s.kv_heads, s.head_dim), dtype)),
             Tensor(jnp.int32(0))) for s in spec]


def scatter_prefill(layer: KVLayerSpec, big, local, slot, plen):
    """Write a request's prefilled rows `local` [1, bucket, ...] into row
    `slot` of the slot cache `big` [slots, rows, ...]. `slot` and `plen` (the
    true prompt length) are traced."""
    import jax
    import jax.numpy as jnp

    local = local.astype(big.dtype)
    bucket = local.shape[1]
    if layer.kind == "window" and bucket > layer.rows:
        # row r of the ring holds the last position p < plen with
        # p % rows == r; rows no position has reached yet hold whatever the
        # clip fetches and are masked by the position they would hold
        r = jnp.arange(layer.rows, dtype=jnp.int32)
        pos = r + layer.rows * ((plen - 1 - r) // layer.rows)
        local = jnp.take(local, jnp.clip(pos, 0, bucket - 1), axis=1)
    return jax.lax.dynamic_update_slice(
        big, local, (slot, jnp.int32(0), jnp.int32(0), jnp.int32(0)))


def cache_bytes(arrays) -> int:
    return sum(int(a.size) * a.dtype.itemsize for a in arrays)
