"""Per-slot token sampling with TRACED parameters.

Legacy generate() bakes (temperature, top_k, top_p) into the decode
executable as compile-time constants — one compiled program per sampling
config. The serving decode step instead carries them as per-slot traced
vectors, so ONE executable serves any mix of greedy / top-k / top-p
requests concurrently. Both the bucketed-prefill and the decode-step
programs sample through sample_tokens, so first-token and subsequent-token
sampling cannot drift (pinned by tests/test_serving_engine.py).

Semantics mirror gpt.generate()'s sample(): greedy when temperature == 0;
otherwise scale by temperature, top-k filter (clamped to vocab, <= 0
disables), then top-p nucleus filter over the top-k-filtered distribution
(>= 1 disables), then categorical.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def filter_topk_topp(logits, top_k, top_p):
    """Mask [n, V] logits to the per-row top-k / nucleus top-p support.

    top_k int32 [n] (<= 0 disables; clamped to vocab) and top_p f32 [n]
    (>= 1 disables) are traced, so mixed configs share one executable.
    Returns logits with excluded entries at -inf. Top-p operates on the
    top-k-filtered distribution, matching legacy sample() order.
    """
    vocab = logits.shape[-1]
    top_k = jnp.asarray(top_k, jnp.int32)
    top_p = jnp.asarray(top_p, jnp.float32)
    sorted_desc = jnp.sort(logits, axis=-1)[:, ::-1]
    k_eff = jnp.clip(top_k, 1, vocab)
    kth = jnp.take_along_axis(sorted_desc, (k_eff - 1)[:, None], axis=-1)
    logits = jnp.where((top_k[:, None] > 0) & (logits < kth),
                       -jnp.inf, logits)
    # nucleus cutoff over the (possibly) top-k-filtered logits
    sorted_f = jnp.sort(logits, axis=-1)[:, ::-1]
    probs = jax.nn.softmax(sorted_f, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    cutoff_idx = jnp.sum(cum < top_p[:, None], axis=-1)
    cutoff = jnp.take_along_axis(
        sorted_f, jnp.clip(cutoff_idx, 0, vocab - 1)[:, None], axis=-1)
    return jnp.where((top_p[:, None] < 1.0) & (logits < cutoff),
                     -jnp.inf, logits)


def sample_tokens(logits, keys, temperature, top_k, top_p):
    """Sample one token per row: [n, V] logits, [n] PRNG keys, per-row
    traced temperature/top_k/top_p. Returns int32 [n]. temperature == 0
    selects greedy argmax for that row (the sampling branch still traces,
    its result is discarded by the select)."""
    with jax.named_scope("sample"):
        logits = jnp.asarray(logits, jnp.float32)
        temperature = jnp.asarray(temperature, jnp.float32)
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        scaled = logits / jnp.maximum(temperature, 1e-6)[:, None]
        filtered = filter_topk_topp(scaled, top_k, top_p)
        sampled = jax.vmap(jax.random.categorical)(keys, filtered)
        return jnp.where(temperature == 0.0, greedy,
                         sampled.astype(jnp.int32))


def request_key(seed, position, base=None):
    """Deterministic per-(request, position) PRNG key: the token emitted at
    sequence position p for a request with seed s is sampled with
    fold_in(fold_in(base, s), p) — identical whether it comes from the
    prefill program (first token) or the decode step (every later token),
    and independent of which slot the request landed in or what its
    neighbors did. Traceable (seed/position may be tracers)."""
    if base is None:
        base = jax.random.key(0)
    return jax.random.fold_in(jax.random.fold_in(base, seed), position)


# Speculative-decode stream salts: the draft proposal and the acceptance
# uniform for position p must each draw from streams DISJOINT from the
# request_key(seed, p) stream — the residual/bonus sample at p reuses the
# plain stream so a fully-accepted window emits the exact token sequential
# decode would have sampled there.
DRAFT_SALT = 0x5BEC
ACCEPT_SALT = 0xACCE


def spec_key(seed, position, salt):
    """request_key folded one level deeper — the draft-proposal and
    acceptance-uniform streams of speculative decoding."""
    return jax.random.fold_in(request_key(seed, position), salt)


def filtered_probs(logits, temperature, top_k, top_p):
    """Per-row post-filter sampling distribution [n, V] — softmax over the
    temperature-scaled, top-k/top-p-masked logits. This is the p(token)
    both sides of the speculative acceptance test u < p_t(d)/p_d(d) must
    agree on (filtering applied to target and draft identically, or the
    leftover-distribution correction loses its exactness)."""
    logits = jnp.asarray(logits, jnp.float32)
    temperature = jnp.asarray(temperature, jnp.float32)
    scaled = logits / jnp.maximum(temperature, 1e-6)[:, None]
    return jax.nn.softmax(filter_topk_topp(scaled, top_k, top_p), axis=-1)


def residual_sample(keys, p_target, p_draft):
    """Leftover-distribution sample after a rejected draft token: one draw
    per row from normalize(max(p_t - p_d, 0)) (Leviathan et al. speculative
    sampling). Rows where the residual has zero mass (p_t == p_d exactly —
    unreachable in exact arithmetic because the acceptance ratio is then 1)
    fall back to p_t. Returns int32 [n]."""
    res = jnp.maximum(p_target - p_draft, 0.0)
    mass = jnp.sum(res, axis=-1, keepdims=True)
    res = jnp.where(mass > 0.0, res, p_target)
    logp = jnp.log(jnp.maximum(res, 1e-38))
    return jax.vmap(jax.random.categorical)(keys, logp).astype(jnp.int32)
