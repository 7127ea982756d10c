"""The attention core of a decode step over a `full` layer of the slot
cache, where the rows can bound the read: the one door from the models'
decode branches to the kernel (ops/pallas/slot_decode.py).

`SlotKV.update` (nn/kv_cache.py) hands a model ALL `rows` of every slot and
the model masks: a plain core reads the whole cache however little of it is
held. A decode step knows better: slot b holds `offset + 1` positions, the
row it has just written included. `decode_core` gives that bound to the
kernel, which fetches a slot's rows to it and no further, from the arrays as
they are stored.

    o = slot_attention.decode_core(q, cache)    # cache: what `update` returned
    if o is None:
        ...the model's plain core, as before

What takes the kernel is decided by what the call can see
(`slot_decode.supported`): a `SlotKV`, one query a slot, one query head a
key head, whole tiles, a single-device program on a TPU. A ring (`RingKV`:
the wrap is a second bound), grouped queries, a chunk of s > 1 (verify,
prefill), `ChunkKV` (generate()), the paged handle, a mesh and the CPU get
None and keep the model's own plain core, which is the kernel's reference in
the tests. `attn.calls.slot` counts the decode steps over a `SlotKV` traced
here, `attn.calls.slot_kernel` those that took the kernel.
"""
from __future__ import annotations

import math

import jax.numpy as jnp

from ..nn.kv_cache import SlotKV
from ..observability import metrics


def _count(form: str) -> None:
    metrics.default_registry().counter(
        "attn.calls." + form,
        "decode steps over a full layer of the slot cache traced, and those "
        "that took the kernel").inc()


def decode_core(q, cache):
    """q [b, s, kv_heads, groups, d] against the rows of `cache`, the
    handle `update` returned (its offset counts the chunk's own rows) ->
    [b, s, kv_heads, groups, d] in the rows' dtype, every held position
    attended at 1 / sqrt(d), or None where the caller's plain core is to
    run."""
    if type(cache) is not SlotKV or q.shape[1] != 1:
        return None
    _count("slot")
    # imported where a caller may take the kernel: the kernels' toolkit
    # takes over a second to import
    from .pallas import slot_decode
    if not slot_decode.supported(q.shape, cache):
        return None
    _count("slot_kernel")
    b, _, kv_heads, _, d = q.shape
    heads, width = cache.k.shape[2:]
    q = jnp.pad(q.reshape(b, kv_heads, d).astype(cache.k.dtype),
                [(0, 0), (0, heads - kv_heads), (0, width - d)])
    o = slot_decode.slot_decode(q, cache.k, cache.v, cache.offset,
                                1.0 / math.sqrt(d))
    return o[:, None, :kv_heads, None, :d]
