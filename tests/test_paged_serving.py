"""Paged KV cache + radix prefix reuse + replica router (ISSUE 13).

The contracts that must never drift:
- numerics: the paged layout is token-identical to the contiguous engine
  (greedy AND sampled — sampling keys on (seed, position), not layout),
  under prefix hits, pool-pressure eviction, and int8 page quantization;
- reuse: a cached full prefix skips prefill entirely (replay seat), a
  partial hit prefills only the unshared tail at its small rung, and
  eviction can only take refcount-zero pages — never a live slot's;
- fleet: the router stops admitting to a draining replica immediately
  while its active slots finish, and no request is lost.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core import monitor
from paddle_tpu.models import GPTForPretraining, gpt_tiny
from paddle_tpu.serving import (
    PagePool, PoolExhausted, RadixPrefixCache, ReplicaRouter, ServingEngine,
)
from paddle_tpu.serving.kv_pages import (
    RESERVED_PAGES, quantize_kv_int8, resolve_store_dtype,
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


def _paged(model, pool_pages=None, dtype=None, **kw):
    kw.setdefault("slot_count", 3)
    kw.setdefault("ladder", (8, 16, 32))
    kw.setdefault("max_new_cap", 8)
    kw.setdefault("max_seq_len", 48)
    kw.setdefault("steps_per_dispatch", 4)
    return ServingEngine(model, kv_layout="paged", kv_page_tokens=8,
                         kv_num_pages=pool_pages, kv_cache_dtype=dtype, **kw)


def _dense(model, **kw):
    kw.setdefault("slot_count", 3)
    kw.setdefault("ladder", (8, 16, 32))
    kw.setdefault("max_new_cap", 8)
    kw.setdefault("max_seq_len", 48)
    kw.setdefault("steps_per_dispatch", 4)
    return ServingEngine(model, **kw)


def _mixed_work(rng, n=6):
    """Half greedy, half sampled — sampled must also be layout-invariant."""
    work = []
    for i in range(n):
        plen = int(rng.choice([5, 8, 11, 14, 17, 23]))
        work.append({
            "prompt": rng.randint(0, 1024, (plen,)).astype(np.int64),
            "temperature": 0.0 if i % 2 == 0 else 0.8,
            "top_k": 0 if i % 2 == 0 else 50,
            "seed": 1000 + i,
        })
    return work


def _run(eng, work, max_new=5):
    reqs = [eng.submit(w["prompt"], max_new_tokens=max_new,
                       temperature=w["temperature"], top_k=w["top_k"],
                       seed=w["seed"]) for w in work]
    eng.run()
    return [list(r.output_ids()) for r in reqs]


# ------------------------------------------------------------ allocator
def test_page_pool_refcount_lifecycle():
    pool = PagePool(8)
    assert pool.free_count == 8 - RESERVED_PAGES
    a = pool.alloc()
    b = pool.alloc()
    assert a >= RESERVED_PAGES and b != a
    pool.incref(a)
    pool.decref(a)
    pool.decref(a)
    pool.release(a)          # refcount hit 0 -> releasable
    assert pool.free_count == 8 - RESERVED_PAGES - 1
    with pytest.raises(RuntimeError):
        pool.release(b)      # still referenced: not releasable
    while pool.free_count:
        pool.alloc()
    with pytest.raises(PoolExhausted):
        pool.alloc()


def test_pool_exhaustion_is_loud(model):
    """An engine whose pool can never fit one request must raise, not hang."""
    eng = _paged(model, pool_pages=RESERVED_PAGES + 1)
    eng.submit(np.arange(16, dtype=np.int64), max_new_tokens=4,
               temperature=0.0)
    with pytest.raises(PoolExhausted):
        eng.run()


# ----------------------------------------------------------- radix trie
def test_radix_trie_match_insert_evict():
    pool = PagePool(16)
    trie = RadixPrefixCache(pool, page_tokens=4)
    toks = list(range(12))
    pages = [pool.alloc() for _ in range(3)]
    trie.insert(toks, pages)
    for p in pages:          # trie holds weakly: caller's ref is dropped
        trie.release(p)
    assert pool.cached == 3 and pool.in_use == 0
    # peek has no side effects; match increfs the whole path
    assert trie.peek(toks) == 12
    assert pool.in_use == 0
    got = trie.match(toks[:8] + [99, 98])
    assert got == pages[:2]
    assert pool.in_use == 2 and pool.cached == 1
    # only the refcount-zero leaf is evictable; the live path never is
    assert trie.evict(3) == 1
    assert trie.peek(toks) == 8
    for p in pages[:2]:
        trie.release(p)
    assert trie.evict(4) == 2 and pool.cached == 0
    assert trie.peek(toks) == 0


def test_quantize_kv_int8_roundtrip_bound():
    rng = np.random.RandomState(0)
    x = rng.randn(6, 8, 4, 16).astype(np.float32) * 3.0
    q, scale = quantize_kv_int8(x)
    assert q.dtype == np.int8 and scale.shape == x.shape[:-1]
    err = np.abs(np.asarray(q, np.float32) * np.asarray(scale)[..., None] - x)
    # absmax/127 per (…, head) group: half a quantization step + rounding
    bound = np.abs(x).max(-1) / 127 * 0.5 + 1e-6
    assert (err <= bound[..., None] + 1e-6).all()
    assert resolve_store_dtype("auto", np.float32)[1] is False
    assert resolve_store_dtype("int8", np.float32)[1] is True


# ------------------------------------------------------------- numerics
def test_paged_matches_contiguous_greedy_and_sampled(model):
    """Acceptance: token-identical output across layouts on a mixed
    greedy+sampled workload."""
    work = _mixed_work(np.random.RandomState(2))
    ref = _run(_dense(model), work)
    got = _run(_paged(model), work)
    assert got == ref


def test_prefix_full_hit_skips_prefill(model):
    """A page-aligned repeat prompt replays from cached pages: zero prefill
    dispatches, one prefill skip, tokens identical to the dense engine."""
    rng = np.random.RandomState(3)
    prompt = rng.randint(0, 1024, (16,)).astype(np.int64)  # 2 full pages
    eng = _paged(model)
    dense = _dense(model)

    def once(e, seed):
        r = e.submit(prompt, max_new_tokens=5, temperature=0.0, seed=seed)
        e.run()
        return list(r.output_ids())

    first = once(eng, 7)
    d0, s0 = _counter("serving.prefill_dispatches"), \
        _counter("serving.prefill_skips")
    second = once(eng, 7)
    assert _counter("serving.prefill_dispatches") == d0, \
        "full prefix hit still dispatched a prefill"
    assert _counter("serving.prefill_skips") == s0 + 1
    assert first == second == once(dense, 7)
    assert eng.stats()["prefix"]["full_hits"] >= 1


def _trie_pages(eng, prompt):
    """The pages the trie holds for `prompt`'s full chunks, without the
    refcounts and statistics a `match` would move."""
    trie = eng._kv.prefix
    pages, children = [], trie._root
    for chunk in trie._chunks(prompt):
        node = children.get(chunk)
        if node is None:
            break
        pages.append(node.page)
        children = node.children
    return pages


@pytest.mark.parametrize("dtype", [None, "int8"])
def test_replay_seat_keeps_its_flag_across_a_prefill(model, dtype):
    """A full hit and a miss admitted in ONE round: the miss's prefill is
    dispatched between the replay seat and that slot's first step. The
    step's re-derived K/V of position plen-1 must still go to the scratch
    page: the published pages keep their bytes, and the tokens are the
    dense engine's (for int8 pages: those of the same requests served one
    at a time)."""
    rng = np.random.RandomState(11)
    repeat = rng.randint(0, 1024, (16,)).astype(np.int64)   # 2 full pages
    fresh = rng.randint(0, 1024, (11,)).astype(np.int64)

    def both(e, together):
        reqs = []
        for prompt, seed in ((repeat, 3), (fresh, 4)):
            reqs.append(e.submit(prompt, max_new_tokens=5, temperature=0.0,
                                 seed=seed))
            if not together:
                e.run()
        e.run()
        return [list(r.output_ids()) for r in reqs]

    eng = _paged(model, dtype=dtype)
    eng.submit(repeat, max_new_tokens=2, temperature=0.0)
    eng.run()                                  # publishes the two pages
    pages = np.asarray(_trie_pages(eng, repeat))
    assert len(pages) == 2

    def shared_bytes():
        return [np.asarray(a)[pages] for name in ("k", "v", "ks", "vs")
                for a in eng._kv.state[name]]

    before = shared_bytes()
    skips = _counter("serving.prefill_skips")
    got = both(eng, together=True)
    assert _counter("serving.prefill_skips") == skips + 1
    for was, now in zip(before, shared_bytes()):
        np.testing.assert_array_equal(was, now)
    if dtype is None:
        want = both(_dense(model), together=True)
    else:
        ref = _paged(model, dtype=dtype)
        ref.submit(repeat, max_new_tokens=2, temperature=0.0)
        ref.run()
        want = both(ref, together=False)
    assert got == want


def test_partial_hit_prefills_only_tail(model):
    """Shared prefix + fresh suffix: exactly one prefill dispatch (the
    unshared tail at its small rung), tokens still layout-identical."""
    rng = np.random.RandomState(4)
    prefix = rng.randint(0, 1024, (16,)).astype(np.int64)
    sfx_a = rng.randint(0, 1024, (4,)).astype(np.int64)
    sfx_b = rng.randint(0, 1024, (4,)).astype(np.int64)
    eng, dense = _paged(model), _dense(model)

    def once(e, sfx):
        r = e.submit(np.concatenate([prefix, sfx]), max_new_tokens=4,
                     temperature=0.0)
        e.run()
        return list(r.output_ids())

    once(eng, sfx_a)
    d0 = _counter("serving.prefill_dispatches")
    got = once(eng, sfx_b)
    assert _counter("serving.prefill_dispatches") == d0 + 1
    assert eng.stats()["prefix"]["partial_hits"] >= 1
    assert got == once(dense, sfx_b)


def test_eviction_never_corrupts_live_slots(model):
    """A pool sized to force LRU eviction of cached prefixes mid-workload
    must still produce exactly the unconstrained engine's tokens."""
    rng = np.random.RandomState(5)
    work = _mixed_work(rng, n=8)
    ref = _run(_paged(model), work)
    small = _paged(model, pool_pages=RESERVED_PAGES + 9)
    got = _run(small, work)
    assert got == ref
    assert small.stats()["prefix"]["evicted_pages"] > 0, (
        "pool was not small enough to exercise eviction")


def test_int8_pages_bounded_error_and_smaller_cache(model):
    """kv_cache_dtype=int8 quarters the pool bytes; per-page scales keep
    greedy decoding on the tiny model token-identical to f32 pages."""
    rng = np.random.RandomState(6)
    work = [{"prompt": rng.randint(0, 1024, (n,)).astype(np.int64),
             "temperature": 0.0, "top_k": 0, "seed": 0}
            for n in (5, 9, 14, 20)]
    f32 = _paged(model)
    q8 = _paged(model, dtype="int8")
    assert _run(q8, work) == _run(f32, work)
    assert q8.kv_cache_bytes() < f32.kv_cache_bytes() / 2
    bf16 = _paged(model, dtype="bf16")
    assert _run(bf16, work, max_new=3)  # completes; numerics are cast-level


# ---------------------------------------------------------------- fleet
def test_router_drains_replica_to_zero_admissions(model):
    rng = np.random.RandomState(8)
    prefix = rng.randint(0, 1024, (16,)).astype(np.int64)
    prompts = [np.concatenate(
        [prefix, rng.randint(0, 1024, (4,)).astype(np.int64)])
        for _ in range(8)]
    router = ReplicaRouter({"a": _paged(model, slot_count=2),
                            "b": _paged(model, slot_count=2)})
    reqs = [router.submit(p, max_new_tokens=4, temperature=0.0)
            for p in prompts[:4]]
    router.step()
    routed_a = router.routed["a"]
    replaced = router.begin_drain("a")
    more = [router.submit(p, max_new_tokens=4, temperature=0.0)
            for p in prompts[4:]]
    router.run()
    assert router.drained("a")
    # routed credit for never-admitted requests moves with the re-placement
    # (the capacity controller's counter audit, ISSUE 16); admissions after
    # the drain would make it larger, never smaller
    assert router.routed["a"] == routed_a - len(replaced), \
        "draining replica kept admitting"
    assert router.routed["b"] >= len(more)
    survivors = [r for r in reqs if r.done] + replaced + more
    assert {tuple(r.prompt_ids) for r in survivors} == \
        {tuple(p) for p in prompts}
    assert all(len(r.tokens) == 4 for r in survivors)
    with pytest.raises(RuntimeError):
        router.begin_drain("b") or router.submit(
            prompts[0], max_new_tokens=2)


# ----------------------------------------------- contracts + telemetry
def test_paged_contracts_donate_pool_and_analyze_clean(model):
    eng = _paged(model)
    _run(eng, _mixed_work(np.random.RandomState(9), n=3))
    contracts = {c.name: c for c in eng.default_contracts()}
    labels = [n for n in contracts if "cache-donation" in n]
    assert any("decode" in n for n in labels)
    assert any("prefill" in n for n in labels)
    pool_bytes = eng._kv.nbytes()
    for name in labels:
        if "decode" in name:
            # decode donates the whole pool state: pools + scales + tables
            assert contracts[name].donated_bytes >= pool_bytes
    rep = eng.analyze()
    assert rep.ok, [str(v) for v in rep.violations]


def test_paged_gauges_reach_registry_and_prometheus(model):
    from paddle_tpu.observability import metrics

    reg = metrics.enable()
    try:
        eng = _paged(model)
        rng = np.random.RandomState(10)
        prompt = rng.randint(0, 1024, (16,)).astype(np.int64)
        for _ in range(2):
            eng.submit(prompt, max_new_tokens=3, temperature=0.0)
            eng.run()
        snap = reg.snapshot()
        for g in ("serve.pages_in_use", "serve.pages_cached",
                  "serve.prefix_hit_rate"):
            assert g in snap["gauges"], sorted(snap["gauges"])
        assert snap["gauges"]["serve.prefix_hit_rate"] > 0
        text = reg.to_prometheus()
        assert "serve_pages_in_use" in text.replace(".", "_")
    finally:
        metrics.disable()
        metrics.reset()
