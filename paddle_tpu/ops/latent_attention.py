"""The cores of multi-head latent attention (DeepSeek-V2's MLA): the same
mathematics in two plain forms, so that a kernel can replace one function,
and the kernel that replaces the one a decode step takes.

A position keeps ONE row for all heads: its normalised latent `n` [r] (keys
`k_n = n W_uk` and values `v = n W_uv` a head are projections of it) and one
rotated key `k_r` [d_r] that every head shares. The score of a head's query
(`q_n` [d_n], `q_r` [d_r]) against position t is

    (q_n . k_n,t + q_r . k_r,t) * scale

`expanded`: keys and values a head are given (the caller has multiplied the
chunk's latents by `W_kvb`), the chunk attends itself causally from position
0. It runs a block of queries at a time against the keys the block can see,
so that no [heads, s, s] score tensor is alive. This is a prefill's form:
s^2 scores against keys of 192 and values of 128 a head, computed once.

`absorbed`: the queries are carried into the latent space (`q_l = q_n
W_uk^T` [r], the caller's product), the scores and the weighted sum are taken
against the rows themselves, `[n_t | k_r,t]`, and the caller carries the
result `o_l` [r] back (`o = o_l W_uv`). Since q_n . (n W_uk) = (q_n W_uk^T) .
n, the numbers are the expanded form's. This is a decode step's form: each
row is read once for all heads, 2 x (r + d_r) bytes a position and not
2 x heads x (d_n + d_r + d_v). The rows may be stored wider than r + d_r with
zeros behind (nn/kv_cache.py): the query is padded with zeros to the row's
width, so no slice of the cache stands between it and the two products.

The absorbed form has two bodies that share no logic, chosen by what the
call can see in its inputs (`pallas/latent_decode.supported`):

- the kernel (`pallas/latent_decode.py`): ONE query a slot (s == 1) whose
  caller gives one length a slot (a decode step over `SlotLatent` rows), rows
  a multiple of 128 wide and of the kernel's block in count, a single-device
  program on a TPU. A slot's rows are fetched to its length only, each once
  for both products, and no score leaves VMEM;
- the plain einsums, for everything else: a chunk of s > 1 behind held rows,
  a scalar offset (`generate()`'s `ChunkLatent`), a mesh, the CPU. They read
  every row twice and keep the [b, h, s, t] float32 scores in HBM between
  the products, and are the kernel's reference in the tests.

Scores and softmax are float32; the products take the inputs' dtype with
float32 accumulation. `mla.calls.<form>` counts the calls traced: a call
that takes the kernel counts under `absorbed` and under `absorbed_kernel`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..observability import metrics

QUERY_BLOCK = 256   # [128 heads, 256, 3584] float32 scores are 0.47 GB


def _count(form: str) -> None:
    metrics.default_registry().counter(
        "mla.calls." + form,
        "latent-attention cores traced, by the form they took").inc()


def _softmax(scores, mask, dtype):
    scores = jnp.where(mask, scores, -jnp.inf)
    return jax.nn.softmax(scores, axis=-1).astype(dtype)


def expanded(q_n, q_r, k_n, k_r, v, scale: float, block: int = None):
    """q_n [b, s, h, d_n], q_r [b, s, h, d_r], k_n [b, s, h, d_n], k_r
    [b, s, d_r] (one for all heads), v [b, s, h, d_v] -> [b, s, h, d_v]:
    causal attention of a chunk whose first token is position 0."""
    _count("expanded")
    s = q_n.shape[1]
    block = QUERY_BLOCK if block is None else block
    outs = []
    for q0 in range(0, s, block):
        q1 = min(s, q0 + block)
        scores = jnp.einsum("bshd,bthd->bhst", q_n[:, q0:q1], k_n[:, :q1],
                            preferred_element_type=jnp.float32)
        scores = scores + jnp.einsum("bshd,btd->bhst", q_r[:, q0:q1],
                                     k_r[:, :q1],
                                     preferred_element_type=jnp.float32)
        mask = jnp.arange(q1)[None, :] <= jnp.arange(q0, q1)[:, None]
        att = _softmax(scores * scale, mask[None, None], v.dtype)
        outs.append(jnp.einsum("bhst,bthd->bshd", att, v[:, :q1]))
    return jnp.concatenate(outs, axis=1) if len(outs) > 1 else outs[0]


def absorbed(q_l, q_r, rows, mask, scale: float, lengths=None):
    """q_l [b, s, h, r], q_r [b, s, h, d_r] against rows [b, t, width >= r +
    d_r] (`[n_t | k_r,t | zeros]`) under mask [b or 1, s, t] -> o_l
    [b, s, h, r], the weighted sum of the rows' latents. `lengths` [b]
    int32, where the caller has one a row of the batch, says the mask again
    for s == 1 (row t is seen where t < length): with it a decode step
    takes the kernel where `latent_decode.supported()` allows."""
    _count("absorbed")
    r = q_l.shape[-1]
    q = jnp.concatenate([q_l, q_r.astype(q_l.dtype)], axis=-1)
    pad = rows.shape[-1] - q.shape[-1]
    if pad:
        q = jnp.pad(q, [(0, 0)] * 3 + [(0, pad)])
    q = q.astype(rows.dtype)
    if lengths is not None:
        # imported where a caller may take the kernel: the kernels' toolkit
        # takes over a second to import, and the other families never call
        from .pallas import latent_decode
        if latent_decode.supported(q.shape, rows.shape, lengths):
            _count("absorbed_kernel")
            # whole lanes of the rows' latent part; the width is one too
            o = latent_decode.latent_decode(q[:, 0], rows, lengths, scale,
                                            out=-(-r // 128) * 128)
            return o[:, None, :, :r]
    scores = jnp.einsum("bshw,btw->bhst", q, rows,
                        preferred_element_type=jnp.float32)
    att = _softmax(scores * scale, mask[:, None], rows.dtype)
    return jnp.einsum("bhst,btw->bshw", att, rows)[..., :r]
