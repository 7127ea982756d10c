"""The decode loop one chunk ahead of its fetch (serving/engine.py,
`_decode_step` / `_may_run_ahead`): while every slot is live and none can end
inside the chunk in flight, the next chunk is enqueued on the device's own
carry before the host reads this one. What must hold, on the CPU at toy size:
the tokens are those of the loop that never runs ahead, the decode entry
keeps ONE executable a sampling family, a freed slot is refilled at the same
boundary, an EOS nobody foresaw costs one masked chunk and nothing else,
every way out with a chunk in flight delivers each token once, and each
`serve_step` record is its own dispatch's.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core import monitor
from paddle_tpu.models import (AfmoeForCausalLM, GPTForPretraining,
                               afmoe_tiny, gpt_tiny)
from paddle_tpu.observability import InMemorySink
from paddle_tpu.serving import ServingEngine

N = 4                                   # steps a dispatch, everywhere here


@pytest.fixture(scope="module")
def gpt():
    from paddle_tpu.distributed.mesh import set_hybrid_communicate_group

    set_hybrid_communicate_group(None)
    paddle.seed(0)
    m = GPTForPretraining(gpt_tiny())
    m.eval()
    return m


@pytest.fixture(scope="module")
def afmoe():
    paddle.seed(3)
    m = AfmoeForCausalLM(afmoe_tiny())
    m.eval()
    return m


@pytest.fixture(scope="module")
def draft():
    paddle.seed(1)
    d = GPTForPretraining(gpt_tiny())
    d.eval()
    return d


def _counter(name):
    return monitor.registry().report().get(name, {}).get("value", 0)


def _engine(model, slots=3, **kw):
    args = dict(slot_count=slots, ladder=(8, 16, 32), max_seq_len=96,
                max_new_cap=48, steps_per_dispatch=N)
    args.update(kw)
    return ServingEngine(model, **args)


def _prompts(n, vocab, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, vocab, (4 + (5 * i) % 11,)).astype(np.int64)
            for i in range(n)]


BUDGETS = (25, 18, 30, 9, 22, 14)       # tokens a request, first included


def _submit_all(eng, prompts, sampling, budgets=BUDGETS, first=0):
    """Request `first + i` has its own seed whichever engine serves it."""
    return [eng.submit(p, max_new_tokens=b, seed=11 + first + i, **sampling)
            for i, (p, b) in enumerate(zip(prompts, budgets))]


def _never_ahead(eng, monkeypatch):
    """Today's loop on the same engine: enqueue, fetch, deliver."""
    monkeypatch.setattr(eng, "_may_run_ahead", lambda: False)
    return eng


SAMPLING = {"greedy": dict(temperature=0.0),
            "sample": dict(temperature=0.8, top_k=20, top_p=0.9)}


# --------------------------------------------------------------- (a) tokens
@pytest.mark.parametrize("family", ["greedy", "sample"])
@pytest.mark.parametrize("which", ["gpt", "afmoe"])
def test_tokens_equal_the_loop_that_never_runs_ahead(which, family, request):
    """The same seeded requests through an engine that is never full (two
    requests at a time in three slots: today's loop) and through a full one
    that runs ahead."""
    model = request.getfixturevalue(which)
    prompts = _prompts(len(BUDGETS), model.config.vocab_size)
    sampling = SAMPLING[family]

    idle = _engine(model)
    want = []
    for k in range(0, len(prompts), 2):
        reqs = _submit_all(idle, prompts[k:k + 2], sampling,
                           BUDGETS[k:k + 2], first=k)
        idle.run()
        want += [r.tokens for r in reqs]
    assert idle.stats()["decode_ahead_share"] == 0.0

    a0 = _counter("serving.decode_ahead")
    full = _engine(model)
    reqs = _submit_all(full, prompts, sampling)
    full.run()
    assert _counter("serving.decode_ahead") - a0 > 0
    assert 0.0 < full.stats()["decode_ahead_share"] < 1.0
    assert full._inflight is None
    for r, tokens, b in zip(reqs, want, BUDGETS):
        assert r.done and r.finish_reason == "length" and len(r.tokens) == b
        assert r.tokens == tokens


# ------------------------------------------------------ (b) one executable
@pytest.mark.parametrize("family", ["greedy", "sample"])
def test_decode_entry_keeps_one_executable_a_family(gpt, family):
    """A second call form (another commitment, sharding or weak type of the
    carry) would grow the entry's jit cache: a second load of the decode
    program in every process. The first dispatch is made from the host's
    arrays, every later one from a chunk's outputs, ahead or not."""
    eng = _engine(gpt)
    c0 = _counter("serving.decode_compiles")
    warm = eng.submit(_prompts(1, 1024)[0], max_new_tokens=N + 2,
                      **SAMPLING[family])
    eng.run()                       # the family's program exists: one compile
    assert warm.done
    entry = eng.exec_registry().entry_for(("serve.decode", family))
    assert entry.cache_size() == 1
    assert _counter("serving.decode_compiles") - c0 == 1

    a0 = _counter("serving.decode_ahead")
    reqs = _submit_all(eng, _prompts(len(BUDGETS), 1024), SAMPLING[family])
    eng.run()
    assert all(r.done for r in reqs)
    assert _counter("serving.decode_ahead") - a0 > 0
    assert entry.cache_size() == 1
    assert _counter("serving.decode_compiles") - c0 == 1
    assert eng.stats()["decode_executables"] == 1


def test_program_fed_its_own_outputs_is_the_same_call(gpt):
    """ISSUE 32's hypothesis, read directly: the decode program called with
    host-made arrays and called with its own outputs is one jit cache entry
    (all of them uncommitted arrays on the default device, none weak)."""
    import jax.numpy as jnp

    from paddle_tpu.serving.engine import _split

    eng = _engine(gpt)
    eng.submit(_prompts(1, 1024)[0], max_new_tokens=N + 2, temperature=0.0)
    eng.step()
    entry = eng.exec_registry().entry_for(("serve.decode", "greedy"))
    kv = eng._kv
    host = [jnp.asarray(a) for a in (
        eng._offsets, eng._last_tok, eng._active, eng._temps, eng._topk,
        eng._topp, eng._eos, eng._remaining, eng._seeds)]
    out = entry(eng._params, *kv.args(), *host)
    cache, (off, tok, active, remaining, *_) = _split(out, kv.n_args)
    kv.take(cache, eng._active)
    for made, got in zip((host[0], host[1], host[2], host[7]),
                         (off, tok, active, remaining)):
        assert got.dtype == made.dtype and got.weak_type == made.weak_type
        assert got.sharding == made.sharding
        assert got.committed == made.committed
    out = entry(eng._params, *kv.args(), off, tok, active, *host[3:7],
                remaining, host[8])
    kv.take(_split(out, kv.n_args)[0], eng._active)
    assert entry.cache_size() == 1


# ---------------------------------------------- (c) the same boundaries
def _seats_by_step(eng, reqs):
    """Drive to the end; after each step(), which request sits in which
    slot, and how many tokens each request holds."""
    order = {r.id: i for i, r in enumerate(reqs)}
    seats = []
    while eng.queue_depth() or eng._active.any():
        eng.step()
        seats.append([None if r is None else order[r.id]
                      for r in eng._slot_req])
    return seats


def test_backlog_takes_the_dispatches_of_todays_loop(gpt, monkeypatch):
    """Known budgets, no EOS: a slot whose budget ends is refilled at that
    chunk's boundary, so the seats after every step, the number of steps and
    the number of dispatches are those of the loop that never runs ahead."""
    prompts = _prompts(len(BUDGETS), 1024)
    plain = _never_ahead(_engine(gpt), monkeypatch)
    want = _seats_by_step(plain, _submit_all(plain, prompts,
                                             SAMPLING["greedy"]))
    eng = _engine(gpt)
    got = _seats_by_step(eng, _submit_all(eng, prompts, SAMPLING["greedy"]))
    assert got == want
    assert (eng.stats()["decode_dispatches"]
            == plain.stats()["decode_dispatches"] == len(want))
    assert eng.stats()["steps"] == plain.stats()["steps"]
    assert eng.stats()["decode_ahead_share"] > 0.25


def test_no_chunk_is_enqueued_past_a_budgets_end(gpt):
    """A chunk goes ahead only if every slot outlasts the chunk in flight:
    with budgets that all end inside the second chunk, the second chunk
    itself goes ahead of the first, and nothing goes ahead of the second."""
    eng = _engine(gpt, slots=2)
    reqs = [eng.submit(p, max_new_tokens=1 + N + 2, temperature=0.0)
            for p in _prompts(2, 1024)]
    eng.step()
    assert eng._inflight is not None and eng._inflight["ahead"]
    assert [len(r.tokens) for r in reqs] == [1 + N, 1 + N]
    eng.step()
    assert eng._inflight is None and all(r.done for r in reqs)
    assert eng.stats()["decode_dispatches"] == 2


# ------------------------------------------------------ (d) unforeseen EOS
def test_unforeseen_eos_ends_the_request_once_at_its_eos(gpt, monkeypatch):
    """Slot 0 meets its EOS inside chunk 1 while chunk 2 is in flight: the
    request ends there with `eos`, chunk 2 holds the slot masked and emits
    nothing for it, the survivors' tokens are untouched, and the queued
    request takes the slot one chunk later than today's loop would seat it."""
    prompts = _prompts(4, 1024, seed=5)
    solo = _never_ahead(_engine(gpt), monkeypatch)
    draw = dict(SAMPLING["sample"], seed=3)
    free = [solo.submit(p, max_new_tokens=20, **draw) for p in prompts]
    solo.run()
    # a token slot 0 draws inside the first chunk and has not drawn before
    cut = next(j for j in range(2, 1 + N)
               if free[0].tokens[j] not in free[0].tokens[:j])
    eos = free[0].tokens[cut]

    eng = _engine(gpt)
    reqs = [eng.submit(p, max_new_tokens=20, **draw,
                       eos_token_id=(eos if i == 0 else None))
            for i, p in enumerate(prompts)]
    done0 = _counter("serving.requests")
    eng.step()                                  # chunk 1 read, chunk 2 ahead
    assert eng._inflight is not None
    assert reqs[0].done and reqs[0].finish_reason == "eos"
    assert reqs[0].tokens == free[0].tokens[:cut + 1]
    assert _counter("serving.requests") - done0 == 1
    assert reqs[3].admit_ts is None             # the slot waits for chunk 2
    eng.step()                                  # chunk 2: slot 0 masked
    assert reqs[0].tokens == free[0].tokens[:cut + 1]
    assert reqs[3].admit_ts is None and eng._inflight is None
    eng.run()
    assert reqs[3].slot == 0
    for r, f in zip(reqs[1:], free[1:]):
        assert r.done and r.tokens == f.tokens
    assert _counter("serving.requests") - done0 == 4


def test_every_slot_at_its_eos_leaves_nothing_in_flight(gpt, monkeypatch):
    """All slots end at an EOS inside a chunk with the next in flight: that
    chunk ran masked and is fetched in the same step, so an engine without a
    live slot never holds a chunk (run(), the router and drain() read
    `_active` for "busy")."""
    p = _prompts(1, 1024, seed=9)[0]
    solo = _never_ahead(_engine(gpt, slots=1), monkeypatch)
    draw = dict(SAMPLING["sample"], seed=3)
    free = solo.submit(p, max_new_tokens=20, **draw)
    solo.run()
    cut = next(j for j in range(2, 1 + N)
               if free.tokens[j] not in free.tokens[:j])

    sink = InMemorySink()
    eng = _engine(gpt, slots=1, sink=sink)
    r = eng.submit(p, max_new_tokens=20, **draw,
                   eos_token_id=free.tokens[cut])
    assert eng.step() == 0
    assert r.done and r.tokens == free.tokens[:cut + 1]
    assert eng._inflight is None and not eng._active.any()
    steps = [x for x in sink.records if x["event"] == "serve_step"]
    assert [(x["ahead"], x["tokens"]) for x in steps] == [(False, cut),
                                                          (True, 0)]


# ------------------------------------------- (e) every way out is whole
def _reference_tokens(model, prompts, monkeypatch, budgets=BUDGETS):
    plain = _never_ahead(_engine(model), monkeypatch)
    reqs = _submit_all(plain, prompts, SAMPLING["sample"], budgets)
    plain.run()
    return [r.tokens for r in reqs]


def test_run_in_pieces_then_drain_delivers_every_token_once(gpt,
                                                            monkeypatch):
    prompts = _prompts(len(BUDGETS), 1024)
    want = _reference_tokens(gpt, prompts, monkeypatch)
    eng = _engine(gpt)
    reqs = _submit_all(eng, prompts, SAMPLING["sample"])
    eng.run(max_steps=1)
    assert eng._inflight is not None            # left with a chunk in flight
    eng.refresh_params()                        # it keeps the arrays it has
    eng.run(max_steps=2)
    eng.run()
    assert eng._inflight is None
    assert [r.tokens for r in reqs] == want

    eng = _engine(gpt)
    reqs = _submit_all(eng, prompts[:3], SAMPLING["sample"])
    eng.run(max_steps=1)
    assert eng._inflight is not None
    a0 = _counter("serving.decode_ahead")
    done = eng.drain()
    assert _counter("serving.decode_ahead") == a0   # draining: today's loop
    assert eng._inflight is None and len(done) == 3
    assert [r.tokens for r in reqs] == want[:3]


def test_begin_drain_stops_running_ahead(gpt, monkeypatch):
    prompts = _prompts(len(BUDGETS), 1024)
    want = _reference_tokens(gpt, prompts, monkeypatch)
    eng = _engine(gpt)
    reqs = _submit_all(eng, prompts, SAMPLING["sample"])
    eng.step()
    assert eng._inflight is not None
    eng.begin_drain()
    a0 = _counter("serving.decode_ahead")
    eng.run()                   # the seated three finish; the queue stays
    assert _counter("serving.decode_ahead") == a0
    assert eng._inflight is None and eng.queue_depth() == 3
    assert [r.tokens for r in reqs[:3]] == want[:3]
    assert all(not r.tokens for r in reqs[3:])


def test_drain_timeout_drops_the_chunk_in_flight(gpt):
    eng = _engine(gpt)
    reqs = _submit_all(eng, _prompts(3, 1024), SAMPLING["greedy"])
    eng.step()
    assert eng._inflight is not None
    held = [len(r.tokens) for r in reqs]
    eng.drain(timeout_s=-1.0)
    assert eng._inflight is None and not eng._active.any()
    assert [r.outcome for r in reqs] == ["drained"] * 3
    assert [len(r.tokens) for r in reqs] == held


@pytest.mark.parametrize("where", ["ahead_dispatch", "fetch"])
def test_a_failure_with_a_chunk_in_flight_finishes_each_request_once(
        gpt, monkeypatch, where):
    eng = _engine(gpt)
    reqs = _submit_all(eng, _prompts(3, 1024), SAMPLING["greedy"])
    enqueue = eng._enqueue_decode

    class Unreadable:
        def __array__(self, *a, **kw):
            raise RuntimeError("fetch failed")

    def failing(ahead):
        if where == "ahead_dispatch" and ahead:
            raise RuntimeError("dispatch failed")
        chunk = enqueue(ahead)
        if where == "fetch" and not ahead:
            chunk["toks"] = Unreadable()
        return chunk

    monkeypatch.setattr(eng, "_enqueue_decode", failing)
    e0 = _counter("serving.outcome.error")
    with pytest.raises(RuntimeError, match=where.split("_")[-1] + " failed"):
        eng.step()
    assert _counter("serving.outcome.error") - e0 == 3
    assert [r.outcome for r in reqs] == ["error"] * 3
    assert all(r.done for r in reqs) and eng._inflight is None


# ----------------------------------------------------- (f) sink records
def _step_records(eng):
    return [r for r in eng.sink.records if r["event"] == "serve_step"]


def test_each_record_is_its_own_dispatchs(afmoe, monkeypatch):
    """One record a dispatch, in order; `tokens`, `occupancy`, `contexts`
    and the model's counters are the values today's loop writes for the same
    dispatch, although a record is written while the next chunk runs; an
    `ahead` dispatch had no gap in which nothing was enqueued."""
    prompts = _prompts(len(BUDGETS), afmoe.config.vocab_size)
    own = ("step", "steps_per_dispatch", "active_slots", "occupancy",
           "queue_depth", "tokens", "contexts", "moe_touched", "moe_max_load")

    plain = _never_ahead(_engine(afmoe, sink=InMemorySink(), max_seq_len=48,
                                 max_new_cap=32), monkeypatch)
    _submit_all(plain, prompts, SAMPLING["greedy"])
    plain.run()
    want = _step_records(plain)
    assert all(r["ahead"] is False for r in want)

    eng = _engine(afmoe, sink=InMemorySink(), max_seq_len=48, max_new_cap=32)
    _submit_all(eng, prompts, SAMPLING["greedy"])
    eng.run()
    got = _step_records(eng)
    assert len(got) == len(want) == eng.stats()["decode_dispatches"]
    for g, w in zip(got, want):
        assert {k: g[k] for k in own} == {k: w[k] for k in own}
    ahead = [r for r in got if r["ahead"]]
    assert ahead and len(ahead) == round(
        eng.stats()["decode_ahead_share"] * len(got))
    assert all(r["host_gap_ms"] == 0.0 for r in ahead)
    assert got[0]["host_gap_ms"] is None
    assert all(r["host_gap_ms"] > 0 for r in got[1:] if not r["ahead"])
    assert [r["ts"] for r in got] == sorted(r["ts"] for r in got)
    for r in got:
        assert r["spans_ms"]["decode_dispatch"] > 0
        assert r["spans_ms"]["decode_fetch"] >= 0
    # prefills are told with the dispatch they precede, never an `ahead` one
    assert all(r["spans_ms"]["prefill_sync"] == [] for r in ahead)


def test_span_tree_of_a_step_that_runs_ahead(gpt):
    """`serve.step` > `serve.admit`, then this chunk's
    `serve.decode.dispatch`, the next chunk's, `serve.decode.fetch`,
    `serve.emit`; the step after it enqueues nothing for itself."""
    from paddle_tpu.observability.tracer import get_tracer

    eng = _engine(gpt, slots=2)
    for p in _prompts(2, 1024):
        eng.submit(p, max_new_tokens=1 + 3 * N, temperature=0.0)
    tr = get_tracer()
    n0 = len(tr.events())
    eng.step()
    first = [e for e in tr.events()[n0:] if e["name"].startswith("serve.")]
    n1 = len(tr.events())
    eng.step()
    second = [e for e in tr.events()[n1:] if e["name"].startswith("serve.")]
    (step,) = [e for e in first if e["name"] == "serve.step"]
    order = sorted((e for e in first if e["parent"] == step["id"]),
                   key=lambda e: e["ts"])
    assert [e["name"] for e in order] == [
        "serve.admit", "serve.decode.dispatch", "serve.decode.dispatch",
        "serve.decode.fetch", "serve.emit"]
    assert [e["args"]["step"] for e in order[1:3]] == [0, N]
    assert [e["name"] for e in sorted(second, key=lambda e: e["ts"])
            if e["name"].startswith("serve.decode")] == [
        "serve.decode.dispatch", "serve.decode.fetch"]


# -------------------------------------- (g) paged layout, speculation
def test_paged_layout_runs_ahead_with_the_same_tokens(gpt, monkeypatch):
    """The page table of chunk n+1 is covered from the host's offsets plus a
    chunk, which are exact while every slot is live, so the paged layout
    takes the same loop."""
    prompts = _prompts(len(BUDGETS), 1024)
    want = _reference_tokens(gpt, prompts, monkeypatch)
    eng = _engine(gpt, kv_layout="paged", kv_page_tokens=8)
    reqs = _submit_all(eng, prompts, SAMPLING["sample"])
    eng.run()
    assert eng.stats()["decode_ahead_share"] > 0
    assert [r.tokens for r in reqs] == want
    assert eng.stats()["pages_in_use"] == 0

    again = _submit_all(eng, prompts, SAMPLING["sample"])   # prefix hits
    eng.run()
    assert [r.tokens for r in again] == want


def test_a_speculating_slot_keeps_todays_loop(gpt, draft):
    """A verify dispatch commits a number of tokens a slot that only its
    fetch tells, so while any live slot speculates nothing is enqueued ahead;
    once only plain slots are left, the loop runs ahead again."""
    eng = _engine(gpt, draft_model=draft, spec_ladder=(4,))
    prompts = _prompts(3, 1024)
    a0 = _counter("serving.decode_ahead")
    reqs = [eng.submit(p, max_new_tokens=30, temperature=0.0,
                       speculate_k=4 if i == 0 else 0)
            for i, p in enumerate(prompts)]
    while not reqs[0].done:
        eng.step()
        assert eng._inflight is None
    assert _counter("serving.decode_ahead") == a0
    assert eng.stats()["decode_dispatches"] == 0        # verify windows only

    plain = [eng.submit(p, max_new_tokens=30, temperature=0.0)
             for p in prompts]
    eng.run()
    assert all(r.done for r in reqs + plain)
    assert _counter("serving.decode_ahead") > a0
