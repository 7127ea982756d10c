"""The attention core of a decode step or a block step over a `full` layer
of the slot cache, where the rows can bound the read: the one door from the
models' cached branches to the kernels (ops/pallas/slot_decode.py,
ops/pallas/block_decode.py).

`SlotKV.update` (nn/kv_cache.py) hands a model ALL `rows` of every slot and
the model masks: a plain core reads the whole cache however little of it is
held. A decode step knows better: slot b holds `offset + 1` positions, the
row it has just written included. So does a block step of block diffusion,
whose caller states it with `block`: every query of a slot's block sees the
rows before `offset`, the block's own included. `decode_core` gives that
bound to a kernel, which fetches a slot's rows to it and no further, from
the arrays as they are stored.

    o = slot_attention.decode_core(q, cache)    # cache: what `update` returned
    o = slot_attention.decode_core(q, cache, block=4)   # a block step
    if o is None:
        ...the model's plain core, as before

What takes a kernel is decided by what the call can see. Without `block`
(`slot_decode.supported`): a `SlotKV`, one query a slot, one query head a
key head, whole tiles, a single-device program on a TPU; a ring (`RingKV`:
the wrap is a second bound), grouped queries and a chunk of s > 1 (verify,
prefill: causal, not block-causal) get None. With `block`
(`block_decode.supported`): a `SlotKV`, a chunk of exactly `block` queries,
any number of query heads a key head, stored heads that merge with the rows
at no cost. `ChunkKV` (generate()), the paged handle, a mesh and the CPU get
None either way and keep the model's own plain core, which is the kernels'
reference in the tests. `attn.calls.slot` / `attn.calls.block` count the
decode and block steps over a `SlotKV` traced here, `attn.calls.slot_kernel`
/ `attn.calls.block_kernel` those that took a kernel.
"""
from __future__ import annotations

import math

import jax.numpy as jnp

from ..nn.kv_cache import SlotKV
from ..observability import metrics


def _count(form: str) -> None:
    metrics.default_registry().counter(
        "attn.calls." + form,
        "decode and block steps over a full layer of the slot cache traced, "
        "and those that took a kernel").inc()


def decode_core(q, cache, block=None):
    """q [b, s, kv_heads, groups, d] against the rows of `cache`, the
    handle `update` returned (its offset counts the chunk's own rows) ->
    [b, s, kv_heads, groups, d] in the rows' dtype, every held position
    attended at 1 / sqrt(d), or None where the caller's plain core is to
    run. With `block` the chunk is a block step's: s == block queries a
    slot, each of which sees the rows before the slot's offset."""
    if type(cache) is not SlotKV or q.shape[1] != (block or 1):
        return None
    if block is not None:
        return _block_core(q, cache, block)
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


def _block_core(q, cache, block):
    """A block step's core through ops/pallas/block_decode.py: a key head's
    `block x groups` queries as the rows of one matrix."""
    _count("block")
    from .pallas import block_decode
    if not block_decode.supported(q.shape, cache, block):
        return None
    _count("block_kernel")
    b, s, kv_heads, groups, d = q.shape
    width = cache.k.shape[3]
    q = jnp.pad(jnp.swapaxes(q, 1, 2).reshape(b, kv_heads, s * groups, d)
                .astype(cache.k.dtype), [(0, 0), (0, 0), (0, 0),
                                         (0, width - d)])
    o = block_decode.block_decode(q, cache.k, cache.v, cache.offset,
                                  1.0 / math.sqrt(d))
    return jnp.swapaxes(o[..., :d].reshape(b, kv_heads, s, groups, d), 1, 2)
