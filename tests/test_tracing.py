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
                if f in ("flash_attention.py", "latent_decode.py",
                         "slot_decode.py", "block_decode.py",
                         "grouped_experts.py"):
                    # a kernel's name is its scope
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
    ("jit(step_chunk)/decode/while/body/closed_call/mla/core/"
     "jit(_call)/latent_decode/pallas_call",
     ("decode/mla/core", False, "latent_decode")),
    ("jit(step_chunk)/decode/while/body/closed_call/attn/core/"
     "jit(_call)/slot_decode/pallas_call",
     ("decode/attn/core", False, "slot_decode")),
    ("jit(block_chunk)/decode/while/body/closed_call/attn/core/"
     "jit(_call)/block_decode/pallas_call",
     ("decode/attn/core", False, "block_decode")),
    ("jit(block_chunk)/decode/while/body/closed_call/moe/experts/"
     "custom_vjp_call/jit(_call)/grouped_experts/pallas_call",
     ("decode/moe/experts", False, "grouped_experts")),
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
def _program_spans(events):
    """The engines' own spans: a step that compiles leaves `jit.*` and
    `exec.first_call` events in the ring beside them (PR 37)."""
    return [e for e in events
            if e["name"].startswith(device_trace.SPAN_PREFIXES)]


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
    new = _program_spans(tr.events()[n0:])
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
        new = _program_spans(tr.events()[n0:])
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
    first = _program_spans(tr.events()[n0:])
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


# --------------------------------------- start-up's events (PR 37)
JIT_COUNTERS = ("jit.trace_ms", "jit.lower_ms", "jit.backend_ms",
                "jit.cache_load_ms", "jit.traces")


def _jit_counters():
    from paddle_tpu.core import monitor

    return {k: monitor.stat(k).get() for k in JIT_COUNTERS}


def _jit_events(events):
    return [e for e in events if e["name"].startswith("jit.")]


@pytest.fixture
def listener_calls():
    """Counts every call jax makes to a duration or scalar listener."""
    import jax

    calls = []

    def on_duration(event, duration, **kw):
        calls.append(event)

    def on_scalar(event, value, **kw):
        calls.append(event)

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_scalar_listener(on_scalar)
    yield calls
    jax.monitoring.unregister_event_duration_listener(on_duration)
    jax.monitoring.unregister_scalar_listener(on_scalar)


def test_first_call_in_a_span_leaves_its_jit_phases_under_it(listener_calls):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def probe_fn(x):
        return jnp.tanh(x) @ x

    x = jnp.ones((8, 8))
    tr = get_tracer()
    n0, c0 = len(tr.events()), _jit_counters()
    with tr.boundary("serve.step") as span:
        probe_fn(x).block_until_ready()
    new = tr.events()[n0:]
    outer = [e for e in _jit_events(new) if e["parent"] == span.id]
    assert [e["name"] for e in outer] == ["jit.trace", "jit.lower",
                                          "jit.backend"]
    assert {e["args"]["fun"] for e in outer} == {"probe_fn"}
    step = next(e for e in new if e["name"] == "serve.step")
    for e in _jit_events(new):            # all lie inside the span
        assert step["ts"] <= e["ts"]
        assert e["ts"] + e["dur"] <= step["ts"] + step["dur"] + 1e-9
    c1 = _jit_counters()
    assert c1["jit.traces"] - c0["jit.traces"] == 1
    for e in outer:
        key = e["name"] + "_ms"
        assert c1[key] - c0[key] == pytest.approx(e["dur"] * 1e3)
    # steady state: no event, and jax calls no listener at all
    n1 = len(tr.events())
    del listener_calls[:]
    for _ in range(20):
        probe_fn(x).block_until_ready()
    assert len(tr.events()) == n1 and not listener_calls
    assert _jit_counters() == c1


def test_nested_jit_counts_its_trace_once():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def inner_fn(x):
        for _ in range(40):               # long enough to be kept (>= 1 ms)
            x = jnp.tanh(x) * 2 + 1
        return x

    @jax.jit
    def outer_fn(x):
        return jnp.where(x > 0, inner_fn(x), x * 3).sum()

    x = jnp.ones((8, 8))
    tr = get_tracer()
    n0, c0 = len(tr.events()), _jit_counters()
    t0 = time.perf_counter()
    outer_fn(x).block_until_ready()
    t1 = time.perf_counter()
    traces = [e for e in tr.events()[n0:] if e["name"] == "jit.trace"]
    outer = next(e for e in traces if e["args"]["fun"] == "outer_fn")
    inner = [e for e in traces if e is not outer]
    assert inner and all(e["parent"] == outer["id"] or e["parent"] in {
        i["id"] for i in inner} for e in inner)
    assert all(e["dur"] >= 1e-3 for e in inner)    # shorter ones are folded
    assert outer["args"]["inner"] > len(inner)     # ... into this count
    for e in inner:
        assert outer["ts"] <= e["ts"]
        assert e["ts"] + e["dur"] <= outer["ts"] + outer["dur"] + 1e-9
    c1 = _jit_counters()
    assert c1["jit.traces"] - c0["jit.traces"] == 1
    assert c1["jit.trace_ms"] - c0["jit.trace_ms"] == pytest.approx(
        outer["dur"] * 1e3)
    table = tr.phase_table(since=t0, until=t1)
    assert table["jit"]["jit.trace"]["count"] == 1
    assert table["jit"]["jit.trace"]["total_s"] == pytest.approx(outer["dur"])
    assert table["rows"]["jit.trace"]["count"] == 1 + len(inner)
    assert table["rows"]["jit.trace"]["total_s"] == pytest.approx(
        outer["dur"])
    assert table["jit"]["unregistered_s"] == pytest.approx(sum(
        table["jit"][n]["total_s"] for n in ("jit.trace", "jit.lower",
                                             "jit.backend")))


def test_a_trace_a_lowering_fires_is_folded_into_the_lowering():
    """On the chip a lowering fires a short trace event for every inner jit
    it meets (2,247 in a DeepSeek-V2 start): inside another phase, so neither
    counted as a trace nor kept; jax's two calls a phase, made by hand."""
    from paddle_tpu.core import compile_cache as cc

    trace, lower = (f"/jax/core/compile/{n}_duration"
                    for n in ("jaxpr_trace", "jaxpr_to_mlir_module"))
    tr = get_tracer()
    n0, c0 = len(tr.events()), _jit_counters()
    cc._on_jit_start(lower, 0.0, fun_name="jit(f)")
    for _ in range(3):
        cc._on_jit_start(trace, 0.0, fun_name="g")
        cc._on_jit_duration(trace, 1e-5, fun_name="g")
    cc._on_jit_duration(lower, 1e-3, fun_name="jit(f)")
    (event,) = tr.events()[n0:]
    assert event["name"] == "jit.lower"
    assert event["args"] == {"fun": "f", "inner": 3}
    c1 = _jit_counters()
    assert c1["jit.traces"] == c0["jit.traces"]
    assert c1["jit.trace_ms"] == c0["jit.trace_ms"]
    assert c1["jit.lower_ms"] - c0["jit.lower_ms"] == pytest.approx(
        event["dur"] * 1e3)
    # the cache's load has no start of its own: always kept and counted
    cc._on_jit_start(trace.replace("jaxpr_trace", "backend_compile"), 0.0)
    cc._on_jit_duration("/jax/compilation_cache/cache_retrieval_time_sec",
                        2e-4)
    cc._on_jit_duration(trace.replace("jaxpr_trace", "backend_compile"), 3e-4,
                        fun_name="jit(f)")
    load, backend = tr.events()[n0 + 1:]
    assert (load["name"], backend["name"]) == ("jit.cache_load", "jit.backend")
    assert load["parent"] == backend["id"] and backend["args"]["inner"] == 1
    assert _jit_counters()["jit.cache_load_ms"] - c0[
        "jit.cache_load_ms"] == pytest.approx(0.2, rel=0.2)


def test_retrace_inside_a_step_is_counted_and_named(tiny_gpt):
    """A helper jitted apart from the registry's executables retraces on a
    new shape inside `serve.step`: no compile counter of the engine sees
    it; `jit.traces` and the window's table do, by name."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.core import monitor
    from paddle_tpu.serving import ServingEngine

    @jax.jit
    def admission_helper(x):
        return x * 2

    tiny_gpt.eval()
    eng = ServingEngine(tiny_gpt, slot_count=2, ladder=(8,), max_new_cap=8,
                        steps_per_dispatch=2)
    real_step = eng._advance_step
    shapes = iter(range(3, 100))

    def leaky_step(*a, **k):        # a new shape a step (numpy: no eager op)
        admission_helper(np.ones((next(shapes),), np.float32))
        return real_step(*a, **k)

    eng._advance_step = leaky_step
    eng.submit(np.arange(1, 5, dtype=np.int64), max_new_tokens=8,
               temperature=0.0)
    eng.step()                      # compiles the engine's programs
    tr = get_tracer()
    compiles = [monitor.stat(k) for k in ("serving.prefill_compiles",
                                          "serving.decode_compiles")]
    c0, k0 = _jit_counters(), [c.get() for c in compiles]
    w0 = time.perf_counter()
    eng.step()
    eng.step()
    w1 = time.perf_counter()
    assert [c.get() for c in compiles] == k0
    assert _jit_counters()["jit.traces"] - c0["jit.traces"] == 2
    table = tr.phase_table(since=w0, until=w1)
    assert table["jit"]["jit.trace"]["count"] == 2
    named = [r for r in table["jit"]["largest"] if r["name"] == "jit.trace"]
    assert {r["fun"] for r in named} == {"admission_helper"}
    assert all(r["under"] == "serve.step" and not r["in_first_call"]
               for r in named)
    assert table["rows"].get("exec.first_call") is None


def _inside(e, spans):
    return any(s["tid"] == e["tid"] and s["ts"] <= e["ts"] + 1e-9
               and e["ts"] + e["dur"] <= s["ts"] + s["dur"] + 1e-9
               for s in spans)


def test_first_call_spans_agree_with_the_compile_counters(tiny_gpt,
                                                          tmp_path):
    import warnings

    from paddle_tpu.core import compile_cache, monitor
    from paddle_tpu.serving import ServingEngine

    if compile_cache.enabled():
        pytest.skip("suite launched with a compile cache configured")
    tiny_gpt.eval()
    stats = [monitor.stat(k) for k in ("engine.compile_cold",
                                       "engine.compile_warm")]
    tr = get_tracer()
    try:
        paddle.set_flags({"compile_cache_dir": str(tmp_path / "cc")})
        for want in ("cold", "warm"):     # the second engine loads
            n0, k0 = len(tr.events()), [c.get() for c in stats]
            with warnings.catch_warnings():
                warnings.simplefilter("ignore")
                eng = ServingEngine(tiny_gpt, slot_count=2, ladder=(8, 16),
                                    max_new_cap=8, steps_per_dispatch=2)
                for n in (4, 12):
                    eng.submit(np.arange(1, 1 + n, dtype=np.int64),
                               max_new_tokens=4, temperature=0.0)
                eng.run()
            new = tr.events()[n0:]
            calls = [e for e in new if e["name"] == "exec.first_call"]
            counted = sum(c.get() - k for c, k in zip(stats, k0))
            assert len(calls) == counted == 3       # two rungs, one decode
            assert {e["args"]["kind"] for e in calls} == {want}
            assert sorted(e["args"]["label"] for e in calls)[1:] == [
                "serve.prefill_b16", "serve.prefill_b8"]
            backend = [e for e in new if e["name"] == "jit.backend"
                       and _inside(e, calls)]
            assert len(backend) == counted
            if want == "warm":
                loads = [e for e in new if e["name"] == "jit.cache_load"]
                assert len([e for e in loads if _inside(e, backend)]) == 3
            table = tr.phase_table()["jit"]
            assert table["jit.backend"]["in_first_call_s"] > 0
    finally:
        paddle.set_flags({"compile_cache_dir": ""})


def test_engine_constructors_and_the_import_are_spans(tiny_gpt):
    from paddle_tpu.serving import ServingEngine

    tr = get_tracer()
    n0 = len(tr.events())
    _train_engine(tiny_gpt)
    tiny_gpt.eval()
    eng = ServingEngine(tiny_gpt, slot_count=2, ladder=(8,), max_new_cap=8,
                        steps_per_dispatch=2)
    new = tr.events()[n0:]
    spans = {e["name"]: e for e in new if not e["name"].startswith("jit.")}
    assert set(spans) == {"engine.init", "serve.engine.init"}
    # a jit event inside a constructor has it as parent
    for e in _jit_events(new):
        if e["args"].get("inner") is not None or e["name"] != "jit.trace":
            assert e["parent"] is not None
    table = eng.stats()["startup"]
    assert table["rows"]["serve.engine.init"]["count"] >= 1
    assert table["rows"]["serve.engine.init"]["self_s"] <= (
        table["rows"]["serve.engine.init"]["total_s"])
    # the package's import: recorded once, first, before the ring could drop
    if not tr.dropped:
        first = tr.events()[0]
        assert first["name"] == "startup.import" and first["dur"] > 0
        assert table["rows"]["startup.import"]["count"] == 1


def _hand_ring():
    """One thread: a root of 10 s holding two children, one of which holds a
    first call with the three jit phases, a trace nested in the trace and
    one in the lowering among them; a jit outside every span; a second
    thread's span."""
    ev = []

    def add(name, ts, dur, tid=1, **args):
        ev.append({"name": name, "ts": ts, "dur": dur, "tid": tid,
                   "args": args or None, "id": len(ev) + 1, "parent": None})

    add("serve.engine.init", 1.0, 2.0)
    add("jit.trace", 1.5, 0.5, fun="zeros")
    add("serve.step", 4.0, 10.0)
    add("serve.admit", 4.5, 6.0)
    add("exec.first_call", 5.0, 5.0, label="serve.prefill.b8", kind="cold")
    add("jit.trace", 5.0, 2.0, fun="prefill", inner=7)
    add("jit.trace", 5.5, 1.0, fun="attn")            # inside the outer trace
    add("jit.lower", 7.0, 1.0, fun="prefill")
    add("jit.trace", 7.25, 0.25, fun="a_lowering_fires_it")   # in the lowering
    add("jit.backend", 8.0, 2.0, fun="prefill")
    add("jit.cache_load", 8.5, 1.0)
    add("serve.emit", 12.0, 1.0)
    add("jit.backend", 15.0, 3.0, fun="_normal")      # under no span
    add("other.thread", 16.0, 1.0, tid=2)
    return ev


def test_span_table_self_times_add_up():
    from paddle_tpu.observability import tracer

    t = tracer.span_table(_hand_ring(), since=0.0, until=20.0)
    rows = t["rows"]
    assert rows["serve.step"] == {"count": 1, "total_s": 10.0, "self_s": 3.0}
    assert rows["serve.admit"]["self_s"] == pytest.approx(1.0)
    assert rows["exec.first_call"]["self_s"] == pytest.approx(0.0)
    assert rows["serve.engine.init"]["self_s"] == pytest.approx(1.5)
    # the nested traces, in a trace and in a lowering: the outermost alone
    # in the totals, so the phases share no second
    assert rows["jit.trace"]["count"] == 4
    assert rows["jit.trace"]["total_s"] == pytest.approx(2.5)
    assert rows["jit.trace"]["self_s"] == pytest.approx(2.75)
    assert rows["jit.lower"] == {"count": 1, "total_s": 1.0, "self_s": 0.75}
    assert rows["jit.backend"]["self_s"] == pytest.approx(4.0)
    assert rows["jit.cache_load"]["self_s"] == pytest.approx(1.0)
    # thread 1's self times and `caller` add up to the table's length
    one = sum(r["self_s"] for n, r in rows.items() if n != "other.thread")
    assert one == pytest.approx(t["total_s"]) and t["total_s"] == 20.0
    # the second thread's span covers [16, 17], which is inside the jit's
    assert rows["caller"]["total_s"] == pytest.approx(1.0 + 1.0 + 1.0 + 2.0)
    assert [(g["start"], g["dur_s"], g["before"], g["after"])
            for g in t["caller_longest"]] == [
        (18.0, 2.0, "jit.backend:_normal", "end"),
        (0.0, 1.0, "start", "serve.engine.init"),
        (3.0, 1.0, "serve.engine.init", "serve.step")]
    jit = t["jit"]
    assert jit["jit.trace"] == {"count": 2, "total_s": 2.5,
                                "in_first_call_s": 2.0, "outside_s": 0.5}
    assert jit["jit.backend"]["outside_s"] == pytest.approx(3.0)
    assert jit["jit.backend"]["in_first_call_s"] == pytest.approx(2.0)
    assert jit["jit.cache_load"]["in_first_call_s"] == pytest.approx(1.0)
    assert jit["unregistered_s"] == pytest.approx(3.5)
    assert [(r["fun"], r["under"]) for r in jit["largest"][:3]] == [
        ("_normal", "caller"), ("prefill", "serve.admit"),
        ("prefill", "serve.admit")]
    # clipped to a window: what straddles it counts by its part inside
    w = tracer.span_table(_hand_ring(), since=6.0, until=9.0)
    assert w["total_s"] == 3.0 and w["rows"]["caller"]["total_s"] == 0.0
    assert w["jit"]["jit.trace"] == {"count": 1, "total_s": 1.0,
                                     "in_first_call_s": 1.0,
                                     "outside_s": 0.0}
    assert sum(r["self_s"] for r in w["rows"].values()) == pytest.approx(3.0)
    assert tracer.span_table([])["total_s"] == 0.0


def test_chrome_export_carries_the_table(tmp_path, capsys):
    """tools/trace_summary.py prints the same table from an exported
    chrome trace: the same ring, the same export."""
    from paddle_tpu.observability import tracer

    tr, origin = Tracer(), tracer._ORIGIN
    for e in _hand_ring():
        tr.record_complete(e["name"], origin + e["ts"],
                           origin + e["ts"] + e["dur"], e["args"],
                           tid=e["tid"], span_id=e["id"], always=True)
    path = tr.export_chrome_trace(str(tmp_path / "host.json"))
    back = tracer.events_from_chrome(json.load(open(path)))
    want = tracer.span_table(tr.events())
    got = tracer.span_table(back)
    assert got["jit"]["unregistered_s"] == pytest.approx(
        want["jit"]["unregistered_s"])
    assert {n: r["count"] for n, r in got["rows"].items()} == {
        n: r["count"] for n, r in want["rows"].items()}
    spec = importlib.util.spec_from_file_location(
        "trace_summary", os.path.join(REPO, "tools", "trace_summary.py"))
    import sys
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        mod.main([path])
    finally:
        sys.path.remove(os.path.join(REPO, "tools"))
    out = capsys.readouterr().out
    assert "jit outside every first call: 3.500 s" in out
    assert re.search(r"exec\.first_call\s+1\s+5\.000\s+0\.000", out)
    assert ("caller 1.000 s at 3.000: after serve.engine.init, before "
            "serve.step") in out


STARTUP_METRICS = {
    "setup.import_s": 3.0, "setup.serve_engine_init_s": 1.5,
    "setup.train_engine_init_s": 0.25, "setup.jit_trace_s": 2.5,
    "setup.jit_lower_s": 1.0, "setup.jit_backend_s": 5.0,
    "setup.jit_unregistered_s": 3.5, "train.window_jit_traces": 1,
    "serve.window_jit_traces": 1, "serve.window_jit_traces.chat": 1}


@pytest.mark.parametrize("name", sorted(STARTUP_METRICS))
def test_startup_readers(name, monkeypatch):
    """Each reader on a hand-made ring, through `collected` as the serving
    runners (a `window`) and the train runner (sub-windows and a rate)
    give it; nothing without jit events, or on a program without the
    table."""
    from paddle_tpu.observability import tracer

    read = _reader(name).read
    from benchmarks.lib import startup_readers

    origin = tracer._ORIGIN
    tr = get_tracer()
    saved = list(tr._events)
    tr.clear()
    monkeypatch.setattr(startup_readers, "_tables", {})
    # the window is [20, 30] on the ring's axis, the process started at -3
    serve = {"window": (origin + 20.0, origin + 30.0), "setup_s": 23.0}
    train = {"windows": [(5.0, 4), (5.0, 4)], "setup_s": 23.0,
             "batch_per_chip": 8, "seq": 100, "tokens_per_s_per_chip": 640.0}
    monkeypatch.setattr(startup_readers, "_clock_origin",
                        lambda: origin - 3.0)
    try:
        run = train if name.startswith("train.") else serve
        assert startup_readers.window(run) == pytest.approx(
            (origin + 20.0, origin + 30.0))
        assert read(run) is None                  # an empty ring
        for e in _hand_ring():
            tr.record_complete(e["name"], origin + e["ts"],
                               origin + e["ts"] + e["dur"], e["args"],
                               tid=e["tid"], span_id=e["id"], always=True)
        tr.record_complete("startup.import", origin - 2.5, origin + 0.5,
                           span_id=90, always=True)
        tr.record_complete("engine.init", origin + 3.0, origin + 3.25,
                           span_id=91, always=True)
        tr.record_complete("jit.trace", origin + 25.0, origin + 25.5,
                           {"fun": "late"}, span_id=92, always=True)
        startup_readers._tables.clear()
        assert read(run) == pytest.approx(STARTUP_METRICS[name])
        assert read({}) is None
        # a program without the table (the parent of PR 37)
        startup_readers._tables.clear()
        monkeypatch.delattr(tracer, "phase_table")
        assert read(run) is None
    finally:
        tr.clear()
        tr._events.extend(saved)


def test_manifest_names_the_startup_metrics():
    man = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    got = {m["name"]: m for m in man["per_layer"]}
    cells = {w["name"] for w in man["workloads"]}
    e2e = {m["name"]: set(m.get("workloads", cells))
           for m in man["end_to_end"]}
    for name in STARTUP_METRICS:
        m = got[name]
        mod = _reader(name)
        assert (mod.LAYER, mod.UNIT, mod.MOVES, mod.SOURCE) == (
            m["layer"], m["unit"], m["moves"], m["source"])
        assert m["better"] == "lower" and set(m["workloads"]) <= e2e[
            m["moves"]]
    assert set(got["setup.jit_trace_s"]["workloads"]) == cells
    # one block, in the order PR 37 appended them (later PRs append theirs
    # behind it: an entry put in the middle reads as a change)
    first = list(got).index("setup.import_s")
    assert list(got)[first:first + len(STARTUP_METRICS)] == [
        "setup.import_s", "setup.serve_engine_init_s",
        "setup.train_engine_init_s", "setup.jit_trace_s",
        "setup.jit_lower_s", "setup.jit_backend_s",
        "setup.jit_unregistered_s", "train.window_jit_traces",
        "serve.window_jit_traces", "serve.window_jit_traces.chat"]


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
    # [b*h, s, d] kernels: the packed paths' `flash_bwd` is not in it, nor
    # the later kernels `latent_decode`, `slot_decode`, `grouped_experts`
    # and `block_decode` (test_scope_of has their op paths)
    assert set(reduced["by_kernel"]) == set(device_trace.KERNELS) - {
        "flash_bwd", "latent_decode", "slot_decode", "grouped_experts",
        "block_decode"}
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
