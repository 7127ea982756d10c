"""Attention over the rows a layer may read, written once: the plain core of
the zoo's decoders (models/afmoe.py, olmo_hybrid.py, sdar.py) under the one
rule of which rows a query sees (nn/kv_cache.py `visible`), and the one door
from it to the kernels (ops/pallas/slot_decode.py, block_decode.py).

A layer states its rule as data, `window` (a sliding layer) or `block`
(diffusion over blocks), and makes one call in its `core` scope:

    o = slot_attention.core(q, k, v, positions, seen, cache, window=w)

`seen` None is a chunk alone (no cache, or a fresh one): `chunk_core`
attends its own keys, `QUERY_BLOCK` queries at a time against the keys the
rule lets them see, so that no [s, s] score matrix of all heads is alive.
Otherwise `seen` is the keys, values and held positions `update` returned
beside `cache`, the handle: the door first, then `attend` under the mask.

The door, `decode_core`: `SlotKV.update` hands a model ALL `rows` of every
slot, and the plain core reads them all however few are held. A decode step
knows better: slot b holds `offset + 1` positions, the row it has just
written included. So does a block step of block diffusion, whose caller
states `block`: every query of a slot's block sees the rows before
`offset`, the block's own included. `decode_core` gives that bound to a
kernel, which fetches a slot's rows to it and no further, from the arrays
as they are stored. A window is a second bound no kernel takes, so a
sliding layer never asks.

What takes a kernel is decided by what the call can see. Without `block`
(`slot_decode.supported`): a `SlotKV`, one query a slot, one query head a
key head, whole tiles, a single-device program on a TPU; a ring (`RingKV`:
the wrap is a second bound), grouped queries and a chunk of s > 1 (verify,
prefill: causal, not block-causal) get None. With `block`
(`block_decode.supported`): a `SlotKV`, a chunk of exactly `block` queries,
any number of query heads a key head, stored heads that merge with the rows
at no cost. `ChunkKV` (generate()), the paged handle, a mesh and the CPU get
None either way and keep the plain core, which is the kernels' reference in
the tests. `attn.calls.slot` / `attn.calls.block` count the decode and block
steps over a `SlotKV` traced here, `attn.calls.slot_kernel` /
`attn.calls.block_kernel` those that took a kernel.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..nn.kv_cache import SlotKV, visible
from ..observability import metrics

QUERY_BLOCK = 512     # a chunk alone attends this many queries at a time


def attend(q, k, v, mask):
    """q [b, s, kvh, g, d] against k, v [b, t, kvh, d] under mask
    [b or 1, s, t]: query head (j, i) reads key head j. Scores and softmax
    in float32."""
    scores = jnp.einsum("bskgd,btkd->bkgst", q, k,
                        preferred_element_type=jnp.float32)
    scores = scores / math.sqrt(q.shape[-1])
    scores = jnp.where(mask[:, None, None], scores, -jnp.inf)
    att = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bkgst,btkd->bskgd", att, v)


def chunk_core(q, k, v, window=None, block=None):
    """A whole chunk whose first token is position 0 against its own k, v
    [b, s, kv_heads, d] under the rule (`visible`), a block of queries at a
    time against the keys that block can see."""
    s = q.shape[1]
    outs = []
    for q0 in range(0, s, QUERY_BLOCK):
        q1 = min(s, q0 + QUERY_BLOCK)
        k0 = 0 if window is None else max(0, q0 + 1 - window)
        k1 = q1 if block is None else min(s, -(-q1 // block) * block)
        mask = visible(jnp.arange(k0, k1)[None, :], jnp.arange(q0, q1),
                       window, block)
        outs.append(attend(q[:, q0:q1], k[:, k0:k1], v[:, k0:k1],
                           mask[None]))
    return jnp.concatenate(outs, axis=1) if len(outs) > 1 else outs[0]


def core(q, k, v, positions, seen, cache, *, window=None, block=None):
    """q [b, s, kv_heads, groups, d] -> [b, s, kv_heads, groups, d]: a
    layer's attention core under its rule. `seen` None: a chunk alone
    against its own k, v (`chunk_core`). Otherwise the keys, values and held
    positions `update` returned beside `cache`, the handle, for the queries
    at `positions` [b or 1, s]: the kernel door, then the plain core."""
    if seen is None:
        return chunk_core(q, k, v, window, block)
    o = None if window is not None else decode_core(q, cache, block)
    if o is None:
        keys, values, held = seen
        o = attend(q, keys, values, visible(held, positions, window, block))
    return o


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
