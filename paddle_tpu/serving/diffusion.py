"""Generation by diffusion over blocks, the part of a decode step that is
not the model: which positions of a block a forward keeps.

A slot's block is `x [B]` token ids at positions `[off, off + B)`, some of
them the mask token. One forward gives logits `[B, V]` (the logit at position
i predicts the token AT position i). If no position holds the mask, the
forward's keys and values are the block's and the block is COMMITTED: the
offset moves by B, the tokens are emitted, the next block starts all mask.
Otherwise a token `x0_i` is drawn at every position with its probability
`c_i` under the distribution it was drawn from (`sampling.
sample_tokens_with_prob`), and `unmask` says which masked positions take
their draw, by the request's `remasking`:

- `low_confidence_static`: the `n` masked positions of largest `c_i` (ties:
  the leftmost), `n` this forward's share of the block's B positions spread
  evenly over the request's `denoising_steps` (`share`);
- `low_confidence_dynamic`: every masked position with `c_i` above the
  request's threshold if those number at least `n`, else as static;
- `sequential`: the leftmost `n` masked positions.

After SDAR's published `generate.py` (`block_diffusion_generate`). The engine
(`ServingEngine._build_block_decode`) runs this a slot, slots in any phase of
their blocks in one forward.
"""
from __future__ import annotations

import jax.numpy as jnp

from ..nn.kv_cache import BlockDiffusion  # noqa: F401

REMASKING = ("low_confidence_static", "low_confidence_dynamic", "sequential")


def remasking_id(name: str) -> int:
    if name not in REMASKING:
        raise ValueError(f"remasking {name!r}: expected one of {REMASKING}")
    return REMASKING.index(name)


def share(block: int, steps, k):
    """How many positions forward `k` (0-based, int32 [n]) of a block
    unmasks when `block` positions are spread evenly over `steps` [n]
    forwards, the remainder on the first ones; at least one once the
    schedule has run out (a block with masks left must still move)."""
    steps = jnp.maximum(steps, 1)
    n = block // steps + (k < block % steps).astype(jnp.int32)
    return jnp.where(k >= steps, jnp.maximum(n, 1), n)


def unmask(masked, conf, n, remasking, threshold):
    """masked [n, B] bool, conf [n, B] float32, n [n] int32, remasking [n]
    int32 (index into `REMASKING`), threshold [n] float32 -> [n, B] bool:
    the masked positions that take their draw in this forward."""
    block = masked.shape[1]
    conf = jnp.where(masked, conf, -jnp.inf)
    at = jnp.arange(block)
    # rank 0 is the most confident; an equal confidence further left is ahead
    ahead = (conf[:, None, :] > conf[:, :, None]) | (
        (conf[:, None, :] == conf[:, :, None])
        & (at[None, None, :] < at[None, :, None]))
    static = masked & (ahead.sum(-1) < n[:, None])
    high = masked & (conf > threshold[:, None])
    dynamic = jnp.where((high.sum(-1) >= n)[:, None], high, static)
    leftmost = masked & (jnp.cumsum(masked, axis=-1) - 1 < n[:, None])
    return jnp.where((remasking == 1)[:, None], dynamic,
                     jnp.where((remasking == 2)[:, None], leftmost, static))
