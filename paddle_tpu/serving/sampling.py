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


# Thresholds tried in one pass over the row: a search over the 2**32 float32
# patterns narrows 4-fold a pass, so it ends within 16 passes. On the chip 3
# beat 1, 7 and 15 at [16, 200192] and [16, 50304] (PERF.md, PR 29).
_WAYS = 3


_as = jax.lax.bitcast_convert_type


def _float_key(x):
    """float32 -> int32, order preserving: a < b iff key(a) < key(b) (the
    two zeros one apart). Its own inverse on the bit patterns."""
    bits = _as(x, jnp.int32)
    return bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))


def _key_float(key):
    return _as(key ^ ((key >> 31) & jnp.int32(0x7FFFFFFF)), jnp.float32)


def _search_keys(holds, lo, hi):
    """Per row, the largest key t in [lo, hi] at which ``holds`` is true.

    ``holds(tf)`` takes one float32 threshold a row, [n, 1], and returns
    bool [n]: a predicate that is true up to some key and false above it,
    and is taken as true at ``lo`` without being asked. A selection over
    the value range: each pass tries _WAYS keys spread over what is left of
    a row's range, one fused compare-and-reduce over the row for each, so
    no row is ever sorted; rows with lo == hi cost no pass, and no row at
    all means no pass."""
    ways = jnp.arange(1, _WAYS + 1, dtype=jnp.uint32)

    def cond(c):
        lo, hi = c
        return jnp.any(hi > lo)

    def body(c):
        lo, hi = c
        span = _as(hi - lo, jnp.uint32)              # wraps right: hi >= lo
        step = span // jnp.uint32(_WAYS + 1) + jnp.uint32(1)
        offs = jnp.minimum(ways[None, :] * step[:, None], span[:, None])
        tf = _key_float(lo[:, None] + _as(offs, jnp.int32))
        # true on a prefix of the ways: the last true one is the new floor,
        # the first false one less 1 the new ceiling
        n_ok = sum(holds(tf[:, j:j + 1]).astype(jnp.uint32)
                   for j in range(_WAYS))
        below = jnp.minimum(n_ok * step, span)
        above = jnp.minimum((n_ok + jnp.uint32(1)) * step - jnp.uint32(1),
                            span)
        return (lo + _as(below, jnp.int32), lo + _as(above, jnp.int32))

    return jax.lax.while_loop(cond, body, (lo, hi))[0]


# The narrowed form (`filter_topk_topp`): the CAP chunks of LANES columns
# with the largest maxima hold every candidate. It is taken where a row is
# whole chunks, CAP of them or more: 8,192 columns up, a multiple of 128.
# ms a call of `sample_tokens_with_prob` on a v5e at temperature 0.8,
# top_k 50, top_p 0.9, a quarter of the rows greedy (tools/sampler_bench.py):
#
#   rows x vocabulary          full   narrowed   one row top_p alone
#   256 x 151,936 (SDAR)      11.16     3.51     12.82 / 12.82
#    16 x 200,192 (Trinity)    0.381    0.279     0.420 / 0.424
#    16 x 100,352 (Olmo)       0.248    0.208     0.272 / 0.273
#    16 x  50,304 (GPT-2)      0.187    0.167     0.205 / 0.205
#    64 x  25,600 (DeepSeek)   0.247    0.231     0.271 / 0.269
#
# The narrowed form wins at every width measured. Where a row sends the
# `lax.cond` down the full search (last column: full form / narrowed form's
# fallback) the cond costs nothing measurable.
LANES = 128
CAP = 64


def _count(form: str) -> None:
    from ..observability import metrics

    metrics.default_registry().counter(
        "sample.calls." + form,
        "top-k / top-p filters traced, by form").inc()


def takes_narrowed(vocab: int) -> bool:
    """Whether a filter over rows of `vocab` columns holds the narrowed
    form (and picks it at run time by the rows' parameters)."""
    return vocab % LANES == 0 and vocab // LANES >= CAP


def narrowed_exact(top_k, top_p):
    """Per row, whether the narrowed search is exact for its parameters:
    it asks for 1 to CAP values, or filters nothing. numpy or jax arrays."""
    return (((top_k > 0) & (top_k <= CAP))
            | ((top_k <= 0) & (top_p >= 1.0)))


def _candidates(x):
    """[n, V] -> (row maxima [n], [n, CAP x LANES] candidates): the CAP
    chunks of LANES columns with the largest maxima (the row is whole
    chunks: `takes_narrowed`). Rows are taken 8 at a time where they can be:
    [n / 8, 8, chunks, LANES] is the chip's tiled layout of [n, V] as it
    lies, so neither the maxima nor the gather copies the row."""
    n, vocab = x.shape
    chunks = vocab // LANES
    tile = 8 if n % 8 == 0 else 1
    x = x.reshape(n // tile, tile, chunks, LANES)
    chunk_max = jnp.max(x, axis=-1).reshape(n, chunks)
    _, at = jax.lax.top_k(chunk_max, CAP)
    cand = x[jnp.arange(n // tile)[:, None, None],
             jnp.arange(tile)[None, :, None], at.reshape(n // tile, tile, CAP)]
    return jnp.max(chunk_max, axis=-1), cand.reshape(n, CAP * LANES)


def _cutoff(x, top_k, top_p, narrowed=False):
    """Per row, the smallest value the filter keeps: the k-th largest, or
    above it the nucleus's last, or -inf where nothing is filtered.

    Both thresholds are found by selection over the unsorted row
    (_search_keys): the k-th largest value is the largest t that at least
    k values reach, exactly, so ties at the k-th value stay; a value x
    stays in the nucleus iff the mass of the values strictly above x is
    under top_p (top_p <= 0 keeps the maximum and its ties). The cost
    follows the width searched, not k or p, and a search no row asks for
    runs no pass.

    `narrowed`: both searches run over the candidates (`_candidates`),
    exact for rows that ask for 0 < top_k <= CAP. The values above the
    k-th lie in fewer than k chunks, each with a maximum above it, so all
    of them are candidates; every candidate chunk holds a value at or
    above it, so the candidates hold k such values. Each threshold the
    nucleus tries lies at or above the k-th, so the mass above it is the
    candidates'. The mass of the top k, ties at the k-th included, is
    summed over the whole row: a tie may lie outside the candidates."""
    vocab = x.shape[-1]
    use_k = top_k > 0
    use_p = top_p < 1.0
    if narrowed:
        row_max, among = _candidates(x)
    else:
        row_max, among = jnp.max(x, axis=-1), x
    top = _float_key(row_max)
    floor = jnp.full_like(top, _float_key(jnp.float32(-jnp.inf)))

    k_eff = jnp.clip(top_k, 1, vocab)

    def reached_by_k(tf):                      # count(x >= t) >= k
        return jnp.sum(among >= tf, axis=-1, dtype=jnp.int32) >= k_eff

    kth_key = _search_keys(reached_by_k, jnp.where(use_k, floor, top), top)
    kth_key = jnp.where(use_k, kth_key, floor)
    kth = _key_float(kth_key)

    # nucleus over the values at or above kth: every threshold tried lies at
    # or above kth, so the mass above it needs no second mask
    shifted = x - row_max[:, None]
    total = jnp.sum(jnp.where(x >= kth[:, None], jnp.exp(shifted), 0.0),
                    axis=-1)
    want = top_p * total
    if narrowed:
        shifted = among - row_max[:, None]

    def mass_above_reaches_p(tf):              # sum(e[x > t]) >= p * Z
        return jnp.sum(jnp.where(among > tf, jnp.exp(shifted), 0.0),
                       axis=-1) >= want

    # the last key whose mass above still reaches p, then one up: the first
    # key that stays. Never past the maximum, which always stays.
    lo = kth_key - 1
    hi = jnp.maximum(top - 1, lo)
    cut_key = _search_keys(mass_above_reaches_p,
                           jnp.where(use_p, lo, hi), hi) + 1
    return jnp.where(use_p, _key_float(cut_key), kth)


def filter_topk_topp(logits, top_k, top_p):
    """Mask [n, V] logits to the per-row top-k / nucleus top-p support.

    top_k int32 [n] (<= 0 disables; clamped to vocab) and top_p f32 [n]
    (>= 1 disables) are traced, so mixed configs share one executable.
    Returns logits with excluded entries at -inf. Top-p operates on the
    top-k-filtered distribution, matching legacy sample() order.

    Where the row is wide (`takes_narrowed`), a `lax.cond` searches the
    candidates when every row either asks for 0 < top_k <= CAP or filters
    nothing (`narrowed_exact`), and the whole row otherwise; the cut is
    applied to the whole row either way. `sample.calls.narrowed` /
    `.full` count the forms traced.
    """
    x = jnp.asarray(logits, jnp.float32)
    top_k = jnp.asarray(top_k, jnp.int32)
    top_p = jnp.asarray(top_p, jnp.float32)
    if takes_narrowed(x.shape[-1]):
        _count("narrowed")
        cutoff = jax.lax.cond(
            jnp.all(narrowed_exact(top_k, top_p)),
            lambda *a: _cutoff(*a, narrowed=True), _cutoff, x, top_k, top_p)
    else:
        _count("full")
        cutoff = _cutoff(x, top_k, top_p)
    return jnp.where(x < cutoff[:, None], -jnp.inf, logits)


def sample_tokens(logits, keys, temperature, top_k, top_p):
    """Sample one token per row: [n, V] logits, [n] PRNG keys, per-row
    traced temperature/top_k/top_p. Returns int32 [n]. temperature == 0
    selects greedy argmax for that row (the sampling branch still traces,
    its result is discarded by the select, so such a row asks the filter
    for nothing)."""
    with jax.named_scope("sample"):
        logits = jnp.asarray(logits, jnp.float32)
        temperature = jnp.asarray(temperature, jnp.float32)
        is_greedy = temperature == 0.0
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        scaled = logits / jnp.maximum(temperature, 1e-6)[:, None]
        filtered = filter_topk_topp(scaled, jnp.where(is_greedy, 0, top_k),
                                    jnp.where(is_greedy, 1.0, top_p))
        sampled = jax.vmap(jax.random.categorical)(keys, filtered)
        return jnp.where(is_greedy, greedy, sampled.astype(jnp.int32))


def sample_tokens_with_prob(logits, keys, temperature, top_k, top_p):
    """`sample_tokens`, and beside each token its probability under the
    distribution it was drawn from: [n, V] logits -> (int32 [n], float32
    [n]). A sampled row's distribution is the softmax of its tempered,
    filtered logits; a greedy row (temperature 0) draws the argmax, and its
    probability is that of the plain softmax of its logits (a delta would
    say 1 of every row). What generation by diffusion ranks positions by."""
    with jax.named_scope("sample"):
        logits = jnp.asarray(logits, jnp.float32)
        temperature = jnp.asarray(temperature, jnp.float32)
        is_greedy = temperature == 0.0
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        # a greedy row at temperature 1 and no filter: its own logits
        scaled = logits / jnp.where(is_greedy, 1.0,
                                    jnp.maximum(temperature, 1e-6))[:, None]
        filtered = filter_topk_topp(scaled, jnp.where(is_greedy, 0, top_k),
                                    jnp.where(is_greedy, 1.0, top_p))
        sampled = jax.vmap(jax.random.categorical)(keys, filtered)
        tok = jnp.where(is_greedy, greedy, sampled.astype(jnp.int32))
        drawn = jnp.take_along_axis(filtered, tok[:, None], axis=-1)[:, 0]
        prob = jnp.exp(drawn - jax.nn.logsumexp(filtered, axis=-1))
        return tok, prob


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
