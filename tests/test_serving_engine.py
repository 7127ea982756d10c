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
def test_filter_topk_topp_matches_legacy_reference():
    """Combined top-k+top-p support equivalence between the traced per-slot
    filter (shared by prefill and decode-step programs) and legacy
    sample()'s static filtering."""
    import jax.numpy as jnp

    rng = np.random.RandomState(5)
    logits = rng.randn(4, 50).astype(np.float32) * 3

    def legacy_mask(row, top_k, top_p):
        row = row.copy()
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
            row = np.where(row < cutoff, -np.inf, row)
        return np.isinf(row)

    cases = [(0, 1.0), (10, 1.0), (0, 0.7), (10, 0.7)]
    top_k = jnp.asarray([c[0] for c in cases], jnp.int32)
    top_p = jnp.asarray([c[1] for c in cases], jnp.float32)
    got = np.asarray(filter_topk_topp(jnp.asarray(logits), top_k, top_p))
    for i, (k, p) in enumerate(cases):
        np.testing.assert_array_equal(
            np.isinf(got[i]), legacy_mask(logits[i], k, p),
            err_msg=f"case top_k={k} top_p={p}")


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
