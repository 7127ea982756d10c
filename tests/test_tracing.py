"""Tracing (ISSUE 26): names inside the compiled programs, spans inside the
two engines, and the program's reducer of a device trace.

- a scope is trace-time metadata: the StableHLO of the train step and of the
  decode program is the same text with `jax.named_scope` patched out;
- the engines' boundary spans are always recorded, carry ids, parents and
  request ids, nest inside their parent, and add no device sync;
- `observability/device_trace.py` reads a recorded TPU trace
  (`tests/data/scoped_probe.xplane.pb`, recorded on the chip by
  `tests/data/record_scoped_probe.py`, PR 26: three train steps of a
  two-block GPT and a short serving run) into seconds by scope, by kernel
  and by executable, and puts every idle gap down to a program span.
"""
import contextlib
import importlib.util
import json
import os
import re
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.observability import device_trace, get_tracer
from paddle_tpu.observability.tracer import Tracer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PROBE = os.path.join(REPO, "tests", "data", "scoped_probe.xplane.pb")
RUNNER_ANNOTATIONS = {"submit", "serve_step", "wait", "feed", "engine_step"}


# ------------------------------------------------------------ the tracer
def test_boundary_spans_record_without_enable_and_nest():
    tr = Tracer(capacity=8)
    assert not tr.enabled
    with tr.boundary("a.step") as a:
        with tr.boundary("a.child", request=7) as c:
            pass
        with tr.span("per_op"):                 # gated: not recorded
            pass
    with tr.boundary("a.step") as b:
        pass
    ev = {e["id"]: e for e in tr.events()}
    assert [e["name"] for e in tr.events()] == ["a.child", "a.step", "a.step"]
    assert ev[c.id]["parent"] == a.id and ev[a.id]["parent"] is None
    assert ev[b.id]["parent"] is None and len({a.id, b.id, c.id}) == 3
    assert ev[c.id]["args"] == {"request": 7}
    child, parent = ev[c.id], ev[a.id]
    assert parent["ts"] <= child["ts"]
    assert child["ts"] + child["dur"] <= parent["ts"] + parent["dur"]
    assert a.ms == pytest.approx(parent["dur"] * 1e3)


def test_boundary_ring_is_bounded_and_exports_the_parent():
    tr = Tracer(capacity=4)
    with tr.boundary("root") as root:
        for _ in range(6):
            with tr.boundary("late"):
                pass
    ev = tr.events()
    assert len(ev) == 4 and tr.dropped == 3
    assert {e["parent"] for e in ev} == {root.id, None}
    trace = tr.chrome_trace()["traceEvents"]
    assert all(e["args"]["parent_span"] == root.id
               for e in trace if e["name"] == "late")


def test_boundary_span_cost_is_microseconds():
    """The cost of one engine-boundary span, printed for PERF.md; no assert
    on time beyond an order of magnitude that would mean a sync or I/O."""
    import jax  # noqa: F401  (the span mirrors itself as a TraceAnnotation)

    tr = Tracer()
    n = 20000
    t0 = time.perf_counter()
    for _ in range(n):
        with tr.boundary("cost.probe", request=1):
            pass
    us = (time.perf_counter() - t0) / n * 1e6
    print(f"\nboundary span: {us:.2f} us each over {n}")
    assert us < 1000


# ----------------------------------------------------- names in programs
def _scope_literals():
    found = []
    for d, _, fs in os.walk(os.path.join(REPO, "paddle_tpu")):
        for f in fs:
            if f.endswith(".py"):
                text = open(os.path.join(d, f)).read()
                found += [(os.path.join(d, f), m) for m in re.findall(
                    r"named_scope\(\s*[\"']([^\"']+)[\"']", text)]
                if f == "flash_attention.py":   # a kernel's name is its scope
                    found += [(f, m) for m in re.findall(
                        r"\bname=\"(\w+)\"", text)]
    return found


def test_scope_vocabulary_is_fixed_and_unnumbered():
    found = _scope_literals()
    used = {name for _, name in found}
    assert used, "no jax.named_scope in paddle_tpu"
    assert used <= device_trace.SCOPES, used - device_trace.SCOPES
    assert not any(re.search(r"\d", name) for name in used)
    # every word of the vocabulary is in use, kernels included
    assert used == set(device_trace.SCOPES)


def test_no_span_is_named_as_a_runner_annotation():
    names = set()
    for d, _, fs in os.walk(os.path.join(REPO, "paddle_tpu")):
        for f in fs:
            if f.endswith(".py"):
                names |= set(re.findall(
                    r"\.boundary\(\s*[\"']([^\"']+)[\"']",
                    open(os.path.join(d, f)).read()))
    assert names >= {"serve.step", "serve.admit", "serve.prefill.dispatch",
                     "serve.prefill.sync", "serve.decode.dispatch",
                     "serve.decode.fetch", "serve.emit", "engine.step",
                     "engine.place_batch", "engine.dispatch"}
    assert not names & RUNNER_ANNOTATIONS
    assert all(n.startswith(device_trace.SPAN_PREFIXES) for n in names)


class _NullScope(contextlib.ContextDecorator):
    def __init__(self, *a, **k):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


@pytest.fixture
def tiny_gpt():        # a new one each: a train step donates the weights
    from paddle_tpu.distributed.mesh import set_hybrid_communicate_group
    from paddle_tpu.models import GPTForPretraining, gpt_tiny

    set_hybrid_communicate_group(None)
    paddle.seed(0)
    return GPTForPretraining(gpt_tiny())


def _train_engine(model):
    import jax

    from paddle_tpu.distributed.engine import TrainStepEngine
    from paddle_tpu.distributed.mesh import HybridCommunicateGroup

    hcg = HybridCommunicateGroup(dp_degree=1, devices=jax.devices()[:1])
    opt = paddle.optimizer.AdamW(
        learning_rate=1e-3, parameters=model.parameters(), weight_decay=0.01,
        grad_clip=paddle.nn.ClipGradByGlobalNorm(1.0))
    return TrainStepEngine(model, opt, hcg=hcg)


def _lower_train(eng):
    import jax
    import jax.numpy as jnp

    ids = jnp.zeros((2, 32), jnp.int64)
    return jax.jit(eng._raw_step()).lower(
        eng.params, eng.opt_state, jnp.float32(1e-3), jnp.int32(1),
        jax.random.key(0), ids, ids)


def _lower_decode(eng):
    import jax.numpy as jnp

    s = eng.slot_count

    def vec(dtype):
        return jnp.zeros((s,), dtype)

    return eng._build_decode("sample").lower(
        eng._params, *eng.slot_cache.args(), vec(jnp.int32), vec(jnp.int32),
        vec(jnp.bool_), vec(jnp.float32), vec(jnp.int32), vec(jnp.float32),
        vec(jnp.int32), vec(jnp.int32), vec(jnp.int32))


def _op_names(lowered):
    return set(re.findall(r'op_name="([^"]+)"', lowered.compile().as_text()))


def test_train_step_stablehlo_identical_without_scopes(tiny_gpt, monkeypatch):
    import jax

    tiny_gpt.train()
    eng = _train_engine(tiny_gpt)
    scoped = _lower_train(eng)
    with monkeypatch.context() as m:
        m.setattr(jax, "named_scope", _NullScope)
        bare = _lower_train(eng)
    assert scoped.as_text() == bare.as_text()
    # and the names are there to be read: every part of the step
    scopes = {device_trace.scope_of(n)[0] for n in _op_names(scoped)}
    assert scopes >= {"embed", "attn", "attn/qkv", "attn/core", "attn/out",
                      "mlp", "final_norm", "lm_head_loss", "grad_clip",
                      "optimizer"}
    assert any(device_trace.scope_of(n)[:2] == ("lm_head_loss", True)
               for n in _op_names(scoped))      # the custom backward too


def test_decode_program_stablehlo_identical_without_scopes(tiny_gpt,
                                                           monkeypatch):
    import jax

    from paddle_tpu.serving import ServingEngine

    tiny_gpt.eval()
    eng = ServingEngine(tiny_gpt, slot_count=2, ladder=(8, 16),
                        max_new_cap=8, steps_per_dispatch=2)
    scoped = _lower_decode(eng)
    with monkeypatch.context() as m:
        m.setattr(jax, "named_scope", _NullScope)
        bare = _lower_decode(eng)
    assert scoped.as_text() == bare.as_text()
    scopes = {device_trace.scope_of(n)[0] for n in _op_names(scoped)}
    assert scopes >= {"decode/embed", "decode/attn/qkv",
                      "decode/attn/cache_write", "decode/attn/core",
                      "decode/attn/out", "decode/mlp", "decode/final_norm",
                      "decode/lm_head", "decode/sample"}


def test_afmoe_decode_program_stablehlo_identical_without_scopes(
        monkeypatch):
    """The routed-expert decoder's scopes (attn > qk_norm, rope, gate; moe >
    router, dispatch, experts, shared, combine) are metadata too."""
    import jax

    from paddle_tpu.models import AfmoeForCausalLM, afmoe_tiny
    from paddle_tpu.serving import ServingEngine

    paddle.seed(0)
    model = AfmoeForCausalLM(afmoe_tiny())
    eng = ServingEngine(model, slot_count=2, ladder=(8, 16), max_seq_len=32,
                        max_new_cap=8, steps_per_dispatch=2)
    scoped = _lower_decode(eng)
    with monkeypatch.context() as m:
        m.setattr(jax, "named_scope", _NullScope)
        bare = _lower_decode(eng)
    assert scoped.as_text() == bare.as_text()
    scopes = {device_trace.scope_of(n)[0] for n in _op_names(scoped)}
    assert scopes >= {"decode/embed", "decode/attn/qkv",
                      "decode/attn/qk_norm", "decode/attn/rope",
                      "decode/attn/cache_write", "decode/attn/core",
                      "decode/attn/gate", "decode/attn/out", "decode/mlp",
                      "decode/moe/router", "decode/moe/dispatch",
                      "decode/moe/experts", "decode/moe/shared",
                      "decode/moe/combine", "decode/final_norm",
                      "decode/lm_head", "decode/sample"}


def test_afmoe_serve_step_record_carries_the_experts_load():
    """`moe_touched` and `moe_max_load` leave the decode program with its
    tokens and reach the `serve_step` sink record and `serving.*` counters;
    a GPT record has neither."""
    from paddle_tpu.core import monitor
    from paddle_tpu.models import AfmoeForCausalLM, afmoe_tiny
    from paddle_tpu.observability import InMemorySink
    from paddle_tpu.serving import ServingEngine

    paddle.seed(0)
    sink = InMemorySink()
    eng = ServingEngine(AfmoeForCausalLM(afmoe_tiny()), slot_count=3,
                        ladder=(8,), max_seq_len=32, max_new_cap=8,
                        steps_per_dispatch=2, sink=sink)
    for n in (3, 5, 7):
        eng.submit(list(range(1, n + 1)), max_new_tokens=6)
    eng.run()
    steps = [r for r in sink.records if r["event"] == "serve_step"]
    assert steps
    for rec in steps:
        # 3 rows x top-2 of 8 experts: 2 to 6 of them see a row, and one
        # expert sees 1 to 3
        assert 2.0 <= rec["moe_touched"] <= 6.0
        assert 1.0 <= rec["moe_max_load"] <= 3.0
    assert monitor.stat("serving.moe_touched").get() == steps[-1]["moe_touched"]
    assert monitor.stat("serving.moe_max_load").peak() >= max(
        r["moe_max_load"] for r in steps)


@pytest.mark.parametrize("path, want", [
    ("jit(step)/transpose(jvp(attn))/qkv/jit(fwd)/dot_general",
     ("attn/qkv", True, None)),
    ("jit(step)/jvp(attn)/core/jit(fwd)/flash_fwd/pallas_call",
     ("attn/core", False, "flash_fwd")),
    ("jit(step)/transpose(jvp(attn))/core/flash_bwd_dkv/pallas_call",
     ("attn/core", True, "flash_bwd_dkv")),
    ("jit(step)/transpose(jvp(attn))/core/flash_bwd/pallas_call",
     ("attn/core", True, "flash_bwd")),
    ("jit(step)/transpose(jvp(lm_head_loss))/jit(fwd)/lm_head_loss/while/"
     "body/closed_call/dot_general", ("lm_head_loss", True, None)),
    ("jit(step_chunk)/decode/while/body/closed_call/attn/cache_write/"
     "scatter", ("decode/attn/cache_write", False, None)),
    ("jit(prefill)/prefill/sample/jit(_where)/select_n",
     ("prefill/sample", False, None)),
    ("jit(step)/optimizer/mul:", ("optimizer", False, None)),
    ("jit(chain)/dot_general:", (device_trace.UNNAMED, False, None)),
    ("jit(f)/transpose(jvp())/while/body/add",
     (device_trace.UNNAMED, True, None)),
    ("jit(step)/jvp(checkpoint(mlp))/dot_general", ("mlp", False, None)),
    ("", (device_trace.NO_METADATA, False, None)),
    (None, (device_trace.NO_METADATA, False, None)),
])
def test_scope_of(path, want):
    assert device_trace.scope_of(path) == want


# ------------------------------------------------- spans in the engines
def _count_syncs(monkeypatch):
    import jax

    calls = []
    real = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: (calls.append(1), real(x))[1])
    return calls


def test_train_step_span_tree(tiny_gpt, monkeypatch):
    tiny_gpt.train()
    eng = _train_engine(tiny_gpt)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, 1024, (2, 32)).astype(np.int64)
    x, y = paddle.to_tensor(ids), paddle.to_tensor(np.roll(ids, -1, 1))
    tr = get_tracer()
    assert not tr.enabled
    syncs = _count_syncs(monkeypatch)
    n0 = len(tr.events())
    eng.step(x, y)
    eng.step(x, y)
    new = tr.events()[n0:]
    assert not syncs                      # the spans add no device sync
    steps = [e for e in new if e["name"] == "engine.step"]
    assert [e["args"]["compiled"] for e in steps] == [True, False]
    assert [e["args"]["step"] for e in steps] == [1, 2]
    for step in steps:
        kids = [e for e in new if e.get("parent") == step["id"]]
        assert [k["name"] for k in kids] == ["engine.place_batch",
                                             "engine.dispatch"]
        assert step["parent"] is None
        for k in kids:
            assert step["ts"] <= k["ts"]
            assert k["ts"] + k["dur"] <= step["ts"] + step["dur"] + 1e-9
    assert len({e["id"] for e in new}) == len(new) == 6


def test_fsdp_step_opens_the_same_spans():
    """The grad_comm path (FSDP here, on four virtual devices) is the same
    `engine.step` with its two children; `engine.accum_step` is gone."""
    from paddle_tpu.distributed.engine import TrainStepEngine
    from paddle_tpu.distributed.mesh import (
        HybridCommunicateGroup, set_hybrid_communicate_group)
    from paddle_tpu.models import GPTForPretraining, gpt_tiny
    import jax

    hcg = HybridCommunicateGroup(dp_degree=4, devices=jax.devices()[:4])
    set_hybrid_communicate_group(hcg)
    try:
        paddle.seed(0)
        model = GPTForPretraining(gpt_tiny())
        opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                     parameters=model.parameters())
        eng = TrainStepEngine(model, opt, hcg=hcg, fsdp=True)
        ids = np.random.RandomState(0).randint(
            0, 1024, (4, 32)).astype(np.int64)
        tr = get_tracer()
        n0 = len(tr.events())
        eng.step(paddle.to_tensor(ids), paddle.to_tensor(ids))
        new = tr.events()[n0:]
    finally:
        set_hybrid_communicate_group(None)
    assert [e["name"] for e in new] == ["engine.place_batch",
                                        "engine.dispatch", "engine.step"]
    assert new[-1]["args"]["fsdp"] is True and new[-1]["args"]["compiled"]
    text = eng._execs.stash_map()
    assert text                              # the step ran through the stash


def test_serving_step_span_tree(tiny_gpt, monkeypatch):
    from paddle_tpu.observability import InMemorySink
    from paddle_tpu.serving import ServingEngine

    tiny_gpt.eval()
    sink = InMemorySink()
    # a slot stays free, so every step is enqueue, fetch, deliver; the tree
    # of a full engine, which enqueues ahead, is tests/test_decode_ahead.py's
    eng = ServingEngine(tiny_gpt, slot_count=3, ladder=(8, 16),
                        max_new_cap=8, steps_per_dispatch=2, sink=sink)
    reqs = [eng.submit(np.arange(1, 5 + i, dtype=np.int64), max_new_tokens=6,
                       temperature=0.0) for i in range(2)]
    tr = get_tracer()
    assert not tr.enabled
    syncs = _count_syncs(monkeypatch)
    n0 = len(tr.events())
    eng.step()
    first = tr.events()[n0:]
    eng.run()
    assert not syncs
    by_name = {}
    for e in first:
        by_name.setdefault(e["name"], []).append(e)
    assert sorted(by_name) == [
        "serve.admit", "serve.decode.dispatch", "serve.decode.fetch",
        "serve.emit", "serve.prefill.dispatch", "serve.prefill.sync",
        "serve.step"]
    (step,), (admit,) = by_name["serve.step"], by_name["serve.admit"]
    assert step["parent"] is None and admit["parent"] == step["id"]
    for name in ("serve.decode.dispatch", "serve.decode.fetch", "serve.emit"):
        (e,) = by_name[name]
        assert e["parent"] == step["id"]
    for name in ("serve.prefill.dispatch", "serve.prefill.sync"):
        assert [e["parent"] for e in by_name[name]] == [admit["id"]] * 2
        assert [e["args"]["request"] for e in by_name[name]] == [
            r.id for r in reqs]
    assert by_name["serve.decode.dispatch"][0]["args"]["requests"] == [
        r.id for r in reqs]
    for e in first:                       # children lie inside their parent
        if e["parent"] is not None:
            p = next(x for x in first if x["id"] == e["parent"])
            assert p["ts"] <= e["ts"]
            assert e["ts"] + e["dur"] <= p["ts"] + p["dur"] + 1e-9
    order = [by_name[n][0]["ts"] for n in (
        "serve.admit", "serve.decode.dispatch", "serve.decode.fetch",
        "serve.emit")]
    assert order == sorted(order)

    steps = [r for r in sink.records if r["event"] == "serve_step"]
    assert len(steps) >= 2
    assert steps[0]["host_gap_ms"] is None          # nothing before it
    assert all(r["host_gap_ms"] > 0 for r in steps[1:])
    s0 = steps[0]["spans_ms"]
    assert len(s0["prefill_sync"]) == len(s0["prefill_dispatch"]) == 2
    assert steps[1]["spans_ms"]["prefill_sync"] == []
    for key in ("admit", "decode_dispatch", "decode_fetch", "emit"):
        assert all(r["spans_ms"][key] >= 0 for r in steps)
    # host_gap: end of the last fetch to the end of this dispatch
    ring = tr.events()[n0:]
    fetch = [e for e in ring if e["name"] == "serve.decode.fetch"]
    disp = [e for e in ring if e["name"] == "serve.decode.dispatch"]
    want = (disp[1]["ts"] + disp[1]["dur"]
            - fetch[0]["ts"] - fetch[0]["dur"]) * 1e3
    assert steps[1]["host_gap_ms"] == pytest.approx(want, abs=1e-6)


# ------------------------------------------------ the benchmark's readers
def _reader(name):
    path = os.path.join(REPO, "benchmarks", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "lm_" + name.replace(".", "_"), path)
    import sys
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _serve_run(records):
    return {"window": (10.0, 20.0), "wall_minus_perf": 100.0,
            "sink": records}


@pytest.mark.parametrize("name", ["serve.host_gap_ms_p50.decode",
                                  "serve.host_gap_ms_p50.chat"])
def test_host_gap_reader(name):
    rec = [{"event": "serve_step", "ts": 100.0 + t, "host_gap_ms": g,
            "spans_ms": {"prefill_sync": []}}
           for t, g in ((9.0, 50.0), (11.0, None), (12.0, 9.0), (13.0, 11.0),
                        (14.0, 10.0), (21.0, 70.0))]
    assert _reader(name).read(_serve_run(rec)) == 10.0
    # a program without the field (the parent of PR 26): nothing, no raise
    old = [{"event": "serve_step", "ts": 112.0, "occupancy": 1.0}]
    assert _reader(name).read(_serve_run(old)) is None
    assert _reader(name).read({}) is None


def test_prefill_sync_reader():
    rec = [{"event": "serve_step", "ts": 100.0 + t,
            "spans_ms": {"prefill_sync": ms}}
           for t, ms in ((11.0, [3.0, 5.0]), (12.0, []), (13.0, [4.0]),
                         (30.0, [99.0]))]
    read = _reader("serve.prefill_sync_ms_p50").read
    assert read(_serve_run(rec)) == 4.0
    assert read(_serve_run([{"event": "serve_step", "ts": 112.0}])) is None


def test_host_dispatch_reader_reads_the_ring():
    read = _reader("train.host_dispatch_ms_p50").read
    tr = get_tracer()
    saved = list(tr._events)
    tr.clear()
    try:
        assert read({}) is None               # no engine.step span yet
        for ms, compiled in ((900.0, True), (2.0, False), (4.0, False),
                             (6.0, False)):
            tr.record_complete("engine.step", 1.0, 1.0 + ms * 1e-3,
                               {"step": 1, "compiled": compiled},
                               span_id=1, always=True)
        assert read({}) == pytest.approx(4.0)
    finally:
        tr.clear()
        tr._events.extend(saved)


def test_manifest_names_the_new_metrics():
    man = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    got = {m["name"]: m for m in man["per_layer"]}
    for name, moves in (
            ("serve.host_gap_ms_p50.decode", "serve_tokens_per_s"),
            ("serve.host_gap_ms_p50.chat", "tpot_p95_ms"),
            ("serve.prefill_sync_ms_p50", "ttft_p95_ms"),
            ("train.host_dispatch_ms_p50", "train_tokens_per_s")):
        assert got[name]["moves"] == moves
        assert got[name]["source"] == "program_span"
        mod = _reader(name)
        assert (mod.LAYER, mod.UNIT, mod.MOVES, mod.SOURCE) == (
            got[name]["layer"], "ms", moves, "program_span")


# ----------------------------------------------- compile classification
def test_rebuilt_evicted_entry_is_a_cold_compile(tmp_path):
    """`engine.compile_warm` was decided by whether the cache's entry count
    grew, so rebuilding an entry the size cap had evicted (the new entry
    pushes another out: the count stands still) read as warm. It is decided
    by whether the persistent cache missed."""
    import warnings

    import jax
    import jax.numpy as jnp

    from paddle_tpu.core import compile_cache

    if compile_cache.enabled():
        pytest.skip("suite launched with a compile cache configured")
    cap = jax.config.jax_compilation_cache_max_size
    x = jnp.ones((8, 8))

    # a new function object each time compiles anew in this process; the
    # persistent cache is keyed by the program, which is the same
    def f():
        return lambda a: a * 2 + 1

    def g():
        return lambda a: jnp.tanh(a) @ a + 3
    try:
        jax.config.update("jax_compilation_cache_max_size", 6000)
        paddle.set_flags({"compile_cache_dir": str(tmp_path / "cc")})

        def compile_and_classify(make):
            m0, e0 = compile_cache.misses(), compile_cache.entries()
            r0 = compile_cache._requests
            jax.jit(make())(x).block_until_ready()
            assert compile_cache._requests == r0 + 1   # it did compile
            return (compile_cache.note_compile(1, m0, compile_cache.misses()),
                    compile_cache.entries() - e0)

        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            assert compile_and_classify(f) == ("cold", 1)
            assert compile_and_classify(f) == ("warm", 0)
            assert compile_and_classify(g) == ("cold", 0)   # f evicted
            # f's entry is gone; rebuilding it evicts g: the count does not
            # grow, which the old rule read as warm
            assert compile_and_classify(f) == ("cold", 0)
            assert compile_and_classify(f) == ("warm", 0)
    finally:
        paddle.set_flags({"compile_cache_dir": ""})
        jax.config.update("jax_compilation_cache_max_size", cap)
    assert compile_cache.misses() == -1


# --------------------------------------- the reducer on a recorded trace
needs_probe = pytest.mark.skipif(not os.path.exists(PROBE),
                                 reason="no recorded trace")


@pytest.fixture(scope="module")
def reduced():
    return device_trace.reduce(PROBE, window="probe_window")


@needs_probe
def test_recorded_trace_is_small():
    assert os.path.getsize(PROBE) < 300 * 1024


@needs_probe
def test_wire_reader_agrees_with_profile_data():
    """The hand-written protobuf reader against jax's own: the same planes,
    lines, event counts, names and times."""
    import jax

    mine = {p["name"]: p for p in device_trace.read_xplane(PROBE)}
    theirs = jax.profiler.ProfileData.from_file(PROBE)
    seen = 0
    for plane in theirs.planes:
        lines = {l["name"]: l for l in mine[plane.name]["lines"]}
        for line in plane.lines:
            got = lines[line.name]["events"]
            want = list(line.events)
            assert len(got) == len(want)
            for (a, b, mid), e in zip(got, want):
                assert mine[plane.name]["events"][mid]["name"] == e.name
                assert a == pytest.approx(e.start_ns, abs=1.0)
                assert b - a == pytest.approx(e.duration_ns, abs=1.0)
                seen += 1
    assert seen > 100


@needs_probe
def test_scopes_sum_to_busy_time(reduced):
    r = reduced
    assert r["chips"] == 1 and 0 < r["busy_s"] < r["window_s"]
    total = sum(d["fwd"] + d["bwd"] for d in r["by_scope"].values())
    # one chip runs one instruction at a time: the rows add up to the busy
    # time (containers left out, so nothing is counted twice)
    assert total == pytest.approx(r["busy_s"], rel=0.01)
    named = total - sum(
        sum(r["by_scope"].get(k, {}).values())
        for k in (device_trace.UNNAMED, device_trace.NO_METADATA))
    assert named > 0.8 * r["busy_s"]
    for scope, d in r["by_scope"].items():      # each row, by opcode
        assert sum(r["detail"][scope].values()) == pytest.approx(
            d["fwd"] + d["bwd"])
    assert {"copy", "copy-done"} <= set(r["detail"][device_trace.NO_METADATA])
    # the train step's parts, forward and backward apart
    for scope in ("attn/qkv", "attn/core", "attn/out", "mlp", "lm_head_loss"):
        assert r["by_scope"][scope]["fwd"] > 0
        assert r["by_scope"][scope]["bwd"] > 0
    assert r["by_scope"]["optimizer"]["bwd"] == 0
    assert r["by_scope"]["optimizer"]["fwd"] > 0
    # and the serving programs under their roots
    for scope in ("decode/attn/cache_write", "decode/attn/core",
                  "decode/lm_head", "decode/sample", "prefill/attn/qkv"):
        assert r["by_scope"][scope]["fwd"] > 0


@needs_probe
def test_kernels_are_found_by_name(reduced):
    # the probe was recorded from PR 26's program, whose d=64 model ran the
    # [b*h, s, d] kernels: the packed paths' `flash_bwd` is not in it
    assert set(reduced["by_kernel"]) == set(device_trace.KERNELS) - {
        "flash_bwd"}
    assert all(v > 0 for v in reduced["by_kernel"].values())
    core = reduced["by_scope"]["attn/core"]
    assert reduced["by_kernel"]["flash_fwd"] <= core["fwd"]
    assert (reduced["by_kernel"]["flash_bwd_dkv"]
            + reduced["by_kernel"]["flash_bwd_dq"]) <= core["bwd"]


@needs_probe
def test_executables_and_idle_gaps(reduced):
    r = reduced
    assert {"jit_step", "jit_step_chunk", "jit_prefill"} <= set(
        r["by_executable"])
    # every idle gap is attributed, and the attributions are the idle time
    assert r["idle_s"] == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-6)
    assert sum(r["idle"].values()) == pytest.approx(r["idle_s"])
    assert set(r["idle"]) <= {
        device_trace.CALLER, "engine.step", "engine.place_batch",
        "engine.dispatch", "serve.step", "serve.admit",
        "serve.prefill.dispatch", "serve.prefill.sync",
        "serve.decode.dispatch", "serve.decode.fetch", "serve.emit"}
    # the sleeps between train steps belong to nobody's span; the host
    # blocked in the fetch while the device was between programs is the
    # engine's
    assert r["idle"][device_trace.CALLER] > 0.004
    assert r["idle"]["serve.decode.dispatch"] > 0
    assert "probe_window" not in r["idle"]


@needs_probe
def test_trace_summary_prints_the_table(capsys, tmp_path):
    import shutil
    import sys

    sys.path.insert(0, os.path.join(REPO, "tools"))
    spec = importlib.util.spec_from_file_location(
        "trace_summary", os.path.join(REPO, "tools", "trace_summary.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.main([PROBE]) == 0
    out = capsys.readouterr().out
    assert "attn/core" in out and "flash_bwd_dq" in out
    assert "idle of chip 0" in out
    # a Profiler directory holds host spans beside the device trace: both
    tr = Tracer()
    with tr.boundary("engine.step"):
        pass
    tr.export_chrome_trace(str(tmp_path / "host_1.json"))
    shutil.copy(PROBE, tmp_path / "t.xplane.pb")
    assert mod.main([str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "idle of chip 0" in out and '"kind": "chrome_trace"' in out
