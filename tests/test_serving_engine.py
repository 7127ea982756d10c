"""Serving engine (ISSUE 4 tentpole): bucketed prefill + slot KV cache +
continuous-batching decode.

The two contracts that must never drift:
- numerics: engine greedy output is token-identical to legacy generate()
  at matching shapes, and per-slot EOS retirement never alters surviving
  slots' tokens;
- shape stability: total prefill/decode compiles for a mixed-length
  workload are bounded by the bucket ladder, never by the number of
  distinct prompt shapes (the regression alarm for accidental re-keying).
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core import monitor
from paddle_tpu.models import GPTForPretraining, gpt_tiny
from paddle_tpu.observability import InMemorySink
from paddle_tpu.serving import (
    ServingEngine, bucket_for, clip_ladder, filter_topk_topp, sample_tokens,
)


@pytest.fixture(scope="module")
def model():
    from paddle_tpu.distributed.mesh import set_hybrid_communicate_group

    set_hybrid_communicate_group(None)
    paddle.seed(0)
    m = GPTForPretraining(gpt_tiny())
    m.eval()
    return m


def _counter(name):
    return monitor.registry().report().get(name, {}).get("value", 0)


def _legacy_greedy(model, prompt, n_new, eos=None):
    out = model.generate(paddle.to_tensor(prompt[None]),
                         max_new_tokens=n_new, temperature=0,
                         eos_token_id=eos).numpy()[0]
    return out


# ---------------------------------------------------------------- numerics
def test_engine_greedy_matches_legacy_generate(model):
    """Acceptance: token-identical greedy output at matching shapes, across
    mixed prompt lengths and slot placements."""
    rng = np.random.RandomState(0)
    eng = ServingEngine(model, slot_count=3, ladder=(8, 16, 32),
                        max_new_cap=16, steps_per_dispatch=4)
    prompts = [rng.randint(0, 1024, (n,)).astype(np.int64)
               for n in (5, 7, 9, 12, 3, 17)]
    reqs = [eng.submit(p, max_new_tokens=6, temperature=0.0)
            for p in prompts]
    eng.run()
    for p, r in zip(prompts, reqs):
        assert r.done and r.finish_reason == "length"
        ref = _legacy_greedy(model, p, 6)
        np.testing.assert_array_equal(r.output_ids(), ref)


def test_eos_retirement_never_alters_survivors(model):
    """Acceptance: a slot retiring mid-flight (early EOS) must not change
    any other slot's tokens — each request's stream depends only on its own
    (prompt, seed), pinned against a solo run AND legacy generate()."""
    rng = np.random.RandomState(1)
    pA = rng.randint(0, 1024, (6,)).astype(np.int64)
    pB = rng.randint(0, 1024, (9,)).astype(np.int64)
    # an eos greedy decoding of A actually emits early
    eosA = int(_legacy_greedy(model, pA, 2)[-1])

    eng1 = ServingEngine(model, slot_count=2, ladder=(8, 16),
                         max_new_cap=16, steps_per_dispatch=4)
    rB_alone = eng1.submit(pB, max_new_tokens=10, temperature=0.0)
    eng1.run()

    eng2 = ServingEngine(model, slot_count=2, ladder=(8, 16),
                         max_new_cap=16, steps_per_dispatch=4)
    rA = eng2.submit(pA, max_new_tokens=10, temperature=0.0,
                     eos_token_id=eosA)
    rB = eng2.submit(pB, max_new_tokens=10, temperature=0.0)
    eng2.run()
    assert rA.finish_reason == "eos" and len(rA.tokens) < 10
    assert rA.tokens[-1] == eosA
    assert rB.tokens == rB_alone.tokens
    np.testing.assert_array_equal(rB.output_ids(),
                                  _legacy_greedy(model, pB, 10))


def test_sampling_deterministic_and_slot_independent(model):
    """Same (prompt, seed) -> same tokens regardless of neighbors or slot;
    different seed diverges. Prefill (first token) and decode step (rest)
    share one RNG/sampling convention, so the stream cannot depend on
    which program emitted the token."""
    rng = np.random.RandomState(2)
    p = rng.randint(0, 1024, (6,)).astype(np.int64)
    other = rng.randint(0, 1024, (11,)).astype(np.int64)

    eng1 = ServingEngine(model, slot_count=2, ladder=(8, 16),
                         max_new_cap=16, steps_per_dispatch=4)
    solo = eng1.submit(p, max_new_tokens=8, temperature=0.8, top_k=50,
                       top_p=0.9, seed=7)
    eng1.run()

    eng2 = ServingEngine(model, slot_count=3, ladder=(8, 16),
                         max_new_cap=16, steps_per_dispatch=4)
    # neighbors with different sampling configs, seated first (different slot)
    n1 = eng2.submit(other, max_new_tokens=8, temperature=0.0)
    n2 = eng2.submit(other, max_new_tokens=8, temperature=1.2, top_k=5,
                     seed=3)
    crowded = eng2.submit(p, max_new_tokens=8, temperature=0.8, top_k=50,
                          top_p=0.9, seed=7)
    reseeded = eng2.submit(p, max_new_tokens=8, temperature=0.8, top_k=50,
                           top_p=0.9, seed=8)
    eng2.run()
    assert crowded.tokens == solo.tokens
    assert reseeded.tokens != solo.tokens
    assert n1.done and n2.done
    v = model.config.vocab_size
    for r in (solo, crowded, reseeded, n2):
        assert all(0 <= t < v for t in r.tokens)


# ------------------------------------------------------- shape stability
def test_compile_count_bounded_by_ladder(model):
    """Regression alarm: >= 8 distinct prompt lengths through the engine
    must cost at most |ladder| prefill executables + 1 decode executable
    (<= ladder size total here) — if this grows, something re-keyed on
    prompt length or max_new_tokens."""
    rng = np.random.RandomState(3)
    ladder = (8, 16, 32, 48)
    p0, d0 = _counter("serving.prefill_compiles"), \
        _counter("serving.decode_compiles")
    eng = ServingEngine(model, slot_count=4, ladder=ladder, max_seq_len=64,
                        max_new_cap=16, steps_per_dispatch=4)
    lengths = [3, 5, 7, 9, 11, 14, 18, 25, 28, 30]   # 10 distinct, 3 rungs
    assert len(set(bucket_for(n, ladder) for n in lengths)) == 3
    reqs = [eng.submit(rng.randint(0, 1024, (n,)).astype(np.int64),
                       max_new_tokens=5 + (i % 4), temperature=0.0)
            for i, n in enumerate(lengths)]
    eng.run()
    assert all(r.done for r in reqs)
    prefills = _counter("serving.prefill_compiles") - p0
    decodes = _counter("serving.decode_compiles") - d0
    assert prefills == 3          # one per rung actually used
    assert decodes == 1           # one executable, all max_new/slots/steps
    assert prefills + decodes <= len(ladder)
    # second mixed wave: everything stays warm, ZERO new compiles
    reqs2 = [eng.submit(rng.randint(0, 1024, (n,)).astype(np.int64),
                        max_new_tokens=7, temperature=0.0)
             for n in (4, 6, 13, 26)]
    eng.run()
    assert all(r.done for r in reqs2)
    assert _counter("serving.prefill_compiles") - p0 == prefills
    assert _counter("serving.decode_compiles") - d0 == decodes


def test_decode_families_bounded(model):
    """Mixed greedy + sampling traffic compiles at most TWO decode
    executables (the sampling-family split), with per-slot sampling params
    traced — not one program per config."""
    rng = np.random.RandomState(4)
    eng = ServingEngine(model, slot_count=3, ladder=(8, 16), max_new_cap=8,
                        steps_per_dispatch=2)
    d0 = _counter("serving.decode_compiles")
    configs = [dict(temperature=0.0),
               dict(temperature=0.7, top_k=20),
               dict(temperature=1.3, top_p=0.8, seed=5),
               dict(temperature=0.5, top_k=7, top_p=0.95, seed=9),
               dict(temperature=0.0)]
    for i, kw in enumerate(configs):
        eng.submit(rng.randint(0, 1024, (5 + i,)).astype(np.int64),
                   max_new_tokens=6, **kw)
    eng.run()
    assert eng.stats()["decode_executables"] <= 2
    assert _counter("serving.decode_compiles") - d0 <= 2


# ----------------------------------------------- sampling shared semantics
def sorted_filter_topk_topp(logits, top_k, top_p):
    """The filter as it was up to PR 28: two sorts of the whole row. The
    oracle of the selection that took its place (float32, in jax, so that
    it can stand in for the program's own filter inside an engine)."""
    import jax
    import jax.numpy as jnp

    vocab = logits.shape[-1]
    top_k = jnp.asarray(top_k, jnp.int32)
    top_p = jnp.asarray(top_p, jnp.float32)
    sorted_desc = jnp.sort(logits, axis=-1)[:, ::-1]
    k_eff = jnp.clip(top_k, 1, vocab)
    kth = jnp.take_along_axis(sorted_desc, (k_eff - 1)[:, None], axis=-1)
    logits = jnp.where((top_k[:, None] > 0) & (logits < kth),
                       -jnp.inf, logits)
    sorted_f = jnp.sort(logits, axis=-1)[:, ::-1]
    probs = jax.nn.softmax(sorted_f, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    cutoff_idx = jnp.sum(cum < top_p[:, None], axis=-1)
    cutoff = jnp.take_along_axis(
        sorted_f, jnp.clip(cutoff_idx, 0, vocab - 1)[:, None], axis=-1)
    return jnp.where((top_p[:, None] < 1.0) & (logits < cutoff),
                     -jnp.inf, logits)


def legacy_mask(row, top_k, top_p):
    """Legacy sample()'s static filtering of one row, in float64 ->
    (masked, near): what is excluded, and where the mass above a value
    lies within 1e-5 of top_p, so that a float32 sum may fall either way."""
    row = row.astype(np.float64)
    near = np.zeros(row.shape, bool)
    if top_k and top_k > 0:
        k_eff = min(int(top_k), row.shape[-1])
        kth = np.sort(row)[-k_eff]
        row = np.where(row < kth, -np.inf, row)
    if top_p < 1.0:
        srt = np.sort(row)[::-1]
        e = np.exp(srt - srt[0])
        probs = e / e.sum()
        cum = np.cumsum(probs)
        cutoff_idx = int((cum < top_p).sum())
        cutoff = srt[min(cutoff_idx, row.shape[-1] - 1)]
        # the mass strictly above each value: cum just before its first tie
        first = np.searchsorted(-srt, -row, side="left")
        above = np.where(first > 0, cum[np.maximum(first, 1) - 1], 0.0)
        near = (np.abs(above - top_p) <= 1e-5) & (row < srt[0])
        row = np.where(row < cutoff, -np.inf, row)
    return np.isinf(row), near


_WIDTHS = (50, 50_304, 200_192)


def _filter_rows():
    """(id, vocab, kind, top_k, top_p): the four cases this test always
    had, the grid of ISSUE 29 at three widths, and the rows a selection
    could get wrong where a sort cannot."""
    rows = [(f"v50-randn-k{k}-p{p}", 50, "randn", k, p)
            for k, p in ((0, 1.0), (10, 1.0), (0, 0.7), (10, 0.7))]
    for v in _WIDTHS:
        for k in (0, 1, 7, 50, 1000, v + 5):
            for p in (0.0, 0.5, 0.9, 1.0):
                rows.append((f"v{v}-randn-k{k}-p{p}", v, "randn", k, p))
        rows += [
            (f"v{v}-ties_at_kth", v, "ties_at_kth", 7, 1.0),
            (f"v{v}-ties_at_kth-p0.9", v, "ties_at_kth", 7, 0.9),
            (f"v{v}-ties_at_nucleus", v, "ties_at_nucleus", 0, 0.55),
            (f"v{v}-ties_at_nucleus-k50", v, "ties_at_nucleus", 50, 0.55),
            (f"v{v}-padded-k50-p0.9", v, "padded", 50, 0.9),
            (f"v{v}-padded-kall-p0.5", v, "padded", v + 5, 0.5),
            (f"v{v}-one_hot", v, "one_hot", 50, 0.9),
            (f"v{v}-constant", v, "constant", 7, 0.5),
        ]
    return rows


def _filter_row(vocab, kind):
    rng = np.random.RandomState(5 + vocab % 97)
    row = (rng.randn(vocab) * 3).astype(np.float32)
    if kind == "ties_at_kth":       # five more columns at the 7th value
        row[rng.choice(vocab, 5, replace=False)] = np.sort(row)[-7]
    elif kind == "ties_at_nucleus":
        # 0.4, then four columns of 0.1 each, the rest sharing 0.2: at
        # top_p 0.55 the cut falls among the four, and all four stay
        probs = np.full(vocab, 0.2 / (vocab - 5))
        probs[:5] = (0.4, 0.1, 0.1, 0.1, 0.1)
        row = np.log(probs).astype(np.float32)
        row[1:5] = row[1]
        row = row[rng.permutation(vocab)]
    elif kind == "padded":          # a padded vocabulary's columns
        row[-47 if vocab > 100 else -7:] = -np.inf
    elif kind == "one_hot":
        row[:] = -np.inf
        row[vocab // 3] = 0.0
    elif kind == "constant":
        row[:] = 1.25
    return row


@pytest.mark.parametrize("vocab,kind,top_k,top_p",
                         [pytest.param(*r[1:], id=r[0])
                          for r in _filter_rows()])
def test_filter_topk_topp_matches_legacy_reference(vocab, kind, top_k,
                                                   top_p):
    """Combined top-k+top-p support equivalence between the traced per-slot
    filter (shared by prefill and decode-step programs) and legacy
    sample()'s static filtering, and the sort-based filter it replaced:
    the same support, but where the float64 mass at the boundary lies
    within 1e-5 of top_p."""
    import jax.numpy as jnp

    row = _filter_row(vocab, kind)
    k = jnp.asarray([top_k], jnp.int32)
    p = jnp.asarray([top_p], jnp.float32)
    got = np.asarray(filter_topk_topp(jnp.asarray(row[None]), k, p))[0]
    masked, near = legacy_mask(row, top_k, top_p)
    kept = ~np.isinf(got)
    np.testing.assert_array_equal(got[kept], row[kept])
    np.testing.assert_array_equal(np.isinf(got)[~near], masked[~near])
    assert near.sum() <= 4            # the exemption is a few columns
    assert kept.any()
    was = np.asarray(sorted_filter_topk_topp(jnp.asarray(row[None]), k, p))
    np.testing.assert_array_equal(np.isinf(got)[~near],
                                  np.isinf(was[0])[~near])
    if kind == "ties_at_kth" and top_p >= 1.0:
        assert kept.sum() == 12         # 7 and the five ties: all stay
    if kind == "ties_at_nucleus":
        assert kept.sum() == 5
    if kind in ("one_hot", "constant"):
        assert kept.sum() == (1 if kind == "one_hot" else vocab)


@pytest.mark.parametrize("vocab", _WIDTHS)
def test_sample_tokens_draws_what_the_sorted_filter_drew(vocab):
    """16 rows of mixed greedy / top-k / top-p / plain parameters with
    fixed seeds: the token drawn through the selection is the token
    `categorical` draws over the sort-filtered row with the same key."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.serving import request_key

    rng = np.random.RandomState(11)
    logits = jnp.asarray((rng.randn(16, vocab) * 3).astype(np.float32))
    temps = jnp.asarray([0.0, 0.8, 1.0, 1.3] * 4, jnp.float32)
    top_k = jnp.asarray([50, 50, 0, 0, 7, 1, 1000, vocab + 5] * 2, jnp.int32)
    top_p = jnp.asarray([0.9] * 4 + [1.0] * 4 + [0.5] * 4 + [0.0, 0.3, 0.9,
                                                             1.0],
                        jnp.float32)
    keys = jax.vmap(request_key)(jnp.arange(16, dtype=jnp.int32) * 7 + 1,
                                 jnp.arange(16, dtype=jnp.int32) + 3)
    got = np.asarray(sample_tokens(logits, keys, temps, top_k, top_p))
    scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
    want = np.asarray(jax.vmap(jax.random.categorical)(
        keys, sorted_filter_topk_topp(scaled, top_k, top_p)))
    greedy = np.asarray(jnp.argmax(logits, axis=-1))
    want = np.where(np.asarray(temps) == 0.0, greedy, want)
    np.testing.assert_array_equal(got, want)
    # the draws are not all the argmax: the test would see a wrong support
    assert (got != greedy).sum() >= 4


def test_engine_tokens_equal_the_sorted_filters(model, monkeypatch):
    """An engine run of mixed requests emits the tokens of the same run
    with the sort-based oracle patched in for `filter_topk_topp`, from the
    prefill program (first token) and the decode program (the rest)."""
    from paddle_tpu.serving import sampling

    rng = np.random.RandomState(12)
    prompts = [rng.randint(0, 1024, (n,)).astype(np.int64)
               for n in (5, 9, 12, 7, 14, 6)]
    configs = [dict(temperature=0.8, top_k=50, top_p=0.9, seed=1),
               dict(temperature=0.0),
               dict(temperature=1.2, top_k=5, seed=2),
               dict(temperature=1.0, top_p=0.5, seed=3),
               dict(temperature=0.7, seed=4),
               dict(temperature=0.9, top_k=2000, top_p=0.0, seed=5)]

    def run():
        eng = ServingEngine(model, slot_count=3, ladder=(8, 16),
                            max_new_cap=16, steps_per_dispatch=4)
        reqs = [eng.submit(p, max_new_tokens=10, **kw)
                for p, kw in zip(prompts, configs)]
        eng.run()
        assert all(r.done for r in reqs)
        return [r.tokens for r in reqs]

    got = run()
    with monkeypatch.context() as m:
        m.setattr(sampling, "filter_topk_topp", sorted_filter_topk_topp)
        want = run()
    assert got == want
    assert len({tuple(t) for t in got}) == len(got)


def test_sample_tokens_traced_params():
    """Greedy rows argmax; top_k clamps past vocab; full-support sampling
    stays in range; rows are independent."""
    import jax
    import jax.numpy as jnp

    rng = np.random.RandomState(6)
    logits = jnp.asarray(rng.randn(3, 17).astype(np.float32))
    keys = jax.random.split(jax.random.key(0), 3)
    toks = np.asarray(sample_tokens(
        logits, keys,
        jnp.asarray([0.0, 1.0, 0.9], jnp.float32),
        jnp.asarray([0, 10_000, 3], jnp.int32),      # 10k >> vocab: clamped
        jnp.asarray([1.0, 1.0, 0.5], jnp.float32)))
    assert toks[0] == int(np.argmax(np.asarray(logits)[0]))
    assert all(0 <= t < 17 for t in toks)
    # row 2 must come from its own top-3 support
    top3 = set(np.argsort(np.asarray(logits)[2])[-3:])
    assert toks[2] in top3


# --------------------------------------------------------- engine plumbing
def test_continuous_batching_queue_and_telemetry(model):
    """More requests than slots: all complete, telemetry carries TTFT /
    tokens-per-sec / occupancy / queue depth, and slots are reused."""
    rng = np.random.RandomState(7)
    sink = InMemorySink()
    eng = ServingEngine(model, slot_count=2, ladder=(8, 16), max_new_cap=8,
                        steps_per_dispatch=2, sink=sink)
    reqs = [eng.submit(rng.randint(0, 1024, (4 + i,)).astype(np.int64),
                       max_new_tokens=4, temperature=0.0) for i in range(5)]
    eng.run()
    assert all(r.done for r in reqs)
    req_recs = [r for r in sink.records if r["event"] == "serve_request"]
    step_recs = [r for r in sink.records if r["event"] == "serve_step"]
    assert len(req_recs) == 5 and step_recs
    for rec in req_recs:
        assert rec["ttft_s"] > 0 and rec["tokens_per_sec"] > 0
        assert rec["bucket"] in (8, 16)
        assert 0 <= rec["slot"] < 2
    assert any(rec["queue_depth_at_submit"] > 0 for rec in req_recs)
    for rec in step_recs:
        assert 0 < rec["occupancy"] <= 1.0
        assert rec["steps_per_dispatch"] == 2
    # 5 requests over 2 slots: some slot served >= 3 requests
    slots_used = [rec["slot"] for rec in req_recs]
    assert max(slots_used.count(s) for s in set(slots_used)) >= 3


def test_engine_validation_and_bucketing(model):
    eng = ServingEngine(model, slot_count=2, ladder=(8, 16), max_new_cap=8)
    with pytest.raises(ValueError, match="ladder"):
        eng.submit(np.zeros(100, np.int64))        # prompt exceeds rungs
    assert bucket_for(5, (8, 16)) == 8
    assert bucket_for(9, (8, 16)) == 16
    with pytest.raises(ValueError):
        bucket_for(17, (8, 16))
    assert clip_ladder((8, 16, 64, 128), 64, reserve=16) == (8, 16)
    assert clip_ladder((64, 128), 32) == (32,)     # largest feasible length
    with pytest.raises(ValueError, match="slot_count"):
        ServingEngine(model, slot_count=0)
    # max_new clamped to cache room: bucket 16 in max_seq_len 24 leaves 8
    eng2 = ServingEngine(model, slot_count=1, ladder=(8, 16),
                         max_seq_len=24, max_new_cap=8)
    r = eng2.submit(np.zeros(10, np.int64), max_new_tokens=100)
    assert r.max_new_tokens == 8
    eng2.run()
    assert r.done and len(r.tokens) == 8


# ------------------------------------------------- observability (ISSUE 7)
def test_serve_span_lifecycle_ordering(model):
    """Every request's span lifecycle lands in causal order: enqueue ->
    queue_wait -> prefill -> decode -> request envelope -> retire, all
    tagged with the request id."""
    from paddle_tpu.observability import get_tracer

    rng = np.random.RandomState(11)
    tr = get_tracer()
    tr.enable()
    tr.clear()
    try:
        eng = ServingEngine(model, slot_count=2, ladder=(8, 16),
                            max_new_cap=8, steps_per_dispatch=2)
        reqs = [eng.submit(rng.randint(0, 1024, (4 + i,)).astype(np.int64),
                           max_new_tokens=4, temperature=0.0)
                for i in range(4)]  # 4 requests / 2 slots -> real queueing
        eng.run()
        events = tr.events()
    finally:
        tr.disable()
        tr.clear()
        tr.clear_stats()

    assert {e["name"] for e in events} >= {
        "serve.enqueue", "serve.queue_wait", "serve.prefill", "serve.decode",
        "serve.request", "serve.retire", "serve.step", "serve.admit",
        "serve.decode.dispatch", "serve.decode.fetch", "serve.emit"}
    for req in reqs:
        evs = {e["name"]: e for e in events
               if (e.get("args") or {}).get("request") == req.id}
        assert set(evs) == {"serve.enqueue", "serve.queue_wait",
                            "serve.prefill", "serve.decode", "serve.request",
                            "serve.retire", "serve.prefill.dispatch",
                            "serve.prefill.sync"}

        def end(e):
            return e["ts"] + e["dur"]

        qw, pf, dec, env = (evs["serve.queue_wait"], evs["serve.prefill"],
                            evs["serve.decode"], evs["serve.request"])
        # queue_wait starts at submit; the enqueue instant fires just after
        assert qw["ts"] <= evs["serve.enqueue"]["ts"]
        assert end(qw) == pytest.approx(pf["ts"])       # admit boundary
        assert end(pf) == pytest.approx(dec["ts"])      # first-token boundary
        # envelope spans submit -> done and contains every phase
        assert env["ts"] == pytest.approx(qw["ts"])
        assert end(dec) == pytest.approx(end(env))
        assert evs["serve.retire"]["ts"] >= end(dec) - 1e-6
        assert env["args"]["finish"] == req.finish_reason
        assert evs["serve.decode"]["args"]["tokens"] == len(req.tokens)
    # later-submitted requests genuinely waited for a slot
    waits = [e["dur"] for e in events if e["name"] == "serve.queue_wait"]
    assert len(waits) == 4 and max(waits) > min(waits)


def test_serve_metrics_scrape_acceptance(model, monkeypatch):
    """ISSUE 7 acceptance: a ServingEngine run with PADDLE_TPU_METRICS_PORT
    set serves a scrape where the TTFT/TPOT/queue-wait histogram counts
    equal the number of completed requests."""
    import urllib.request

    from paddle_tpu.observability import exporter, metrics

    exporter.stop_exporter()
    metrics.reset()
    monkeypatch.setenv("PADDLE_TPU_METRICS_PORT", "0")  # ephemeral bind
    try:
        rng = np.random.RandomState(13)
        eng = ServingEngine(model, slot_count=2, ladder=(8, 16),
                            max_new_cap=8, steps_per_dispatch=2)
        ex = exporter.get_exporter()
        assert ex is not None and ex.running  # engine autostarted it
        reqs = [eng.submit(rng.randint(0, 1024, (5 + i,)).astype(np.int64),
                           max_new_tokens=4, temperature=0.0)
                for i in range(4)]
        eng.run()
        assert all(r.done for r in reqs)
        with urllib.request.urlopen(ex.url + "/metrics", timeout=10) as resp:
            body = resp.read().decode("utf-8")
        n = len(reqs)
        assert f"paddle_tpu_serve_ttft_ms_count {n}" in body
        assert f"paddle_tpu_serve_tpot_ms_count {n}" in body
        assert f"paddle_tpu_serve_queue_wait_ms_count {n}" in body
        assert f"paddle_tpu_serve_prefill_ms_count {n}" in body
        assert "paddle_tpu_serve_decode_step_ms_bucket" in body
        assert "paddle_tpu_serve_occupancy_count" in body
        # JSON twin agrees with the text exposition
        with urllib.request.urlopen(ex.url + "/metrics.json",
                                    timeout=10) as resp:
            import json as _json
            doc = _json.loads(resp.read().decode("utf-8"))
        assert doc["histograms"]["serve.ttft_ms"]["count"] == n
        assert doc["histograms"]["serve.ttft_ms"]["min"] > 0
    finally:
        exporter.stop_exporter()
        metrics.reset()
