"""Profiler + observability subsystem (ISSUE 1).

Covers the make_scheduler state machine, RecordEvent/tracer span nesting,
chrome-trace export round-tripped through load_profiler_result, StepTelemetry
JSONL emission from a real CPU train step, compile/dispatch counters, and the
disabled-path overhead contract (no spans, no file I/O, no jax import).
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu.observability.step_telemetry import InMemorySink, JsonlSink
from paddle_tpu.profiler import (
    Benchmark, Profiler, ProfilerState, RecordEvent, export_chrome_tracing,
    get_event_stats, load_profiler_result, make_scheduler, reset_event_stats,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_tracer():
    tr = obs.get_tracer()
    tr.disable()
    tr.clear()
    tr.clear_stats()
    yield
    tr.disable()
    tr.clear()
    tr.clear_stats()


def _tiny_engine(seed=0):
    from paddle_tpu.distributed.engine import TrainStepEngine

    paddle.seed(seed)
    net = paddle.nn.Sequential(paddle.nn.Linear(16, 32), paddle.nn.ReLU(),
                               paddle.nn.Linear(32, 4))
    opt = paddle.optimizer.AdamW(learning_rate=0.01,
                                 parameters=net.parameters())
    return TrainStepEngine(net, opt, loss_fn=paddle.nn.CrossEntropyLoss())


def _batch(n=8):
    rng = np.random.RandomState(0)
    return (paddle.to_tensor(rng.randn(n, 16).astype(np.float32)),
            paddle.to_tensor(rng.randint(0, 4, (n,)).astype(np.int64)))


# ---------------- make_scheduler state machine ----------------

def test_scheduler_skip_first_and_cycle():
    sched = make_scheduler(closed=1, ready=1, record=2, skip_first=3)
    assert [sched(i) for i in range(3)] == [ProfilerState.CLOSED] * 3
    # one period: closed, ready, record, record_and_return
    assert sched(3) == ProfilerState.CLOSED
    assert sched(4) == ProfilerState.READY
    assert sched(5) == ProfilerState.RECORD
    assert sched(6) == ProfilerState.RECORD_AND_RETURN
    # cycles repeat indefinitely with repeat=0
    assert sched(7) == ProfilerState.CLOSED
    assert sched(10) == ProfilerState.RECORD_AND_RETURN


def test_scheduler_repeat_exhausts():
    sched = make_scheduler(closed=0, ready=1, record=1, repeat=2)
    assert sched(0) == ProfilerState.READY
    assert sched(1) == ProfilerState.RECORD_AND_RETURN
    assert sched(2) == ProfilerState.READY
    assert sched(3) == ProfilerState.RECORD_AND_RETURN
    # after `repeat` periods the profiler stays closed forever
    assert sched(4) == ProfilerState.CLOSED
    assert sched(100) == ProfilerState.CLOSED


def test_scheduler_single_record_is_record_and_return():
    sched = make_scheduler(closed=0, ready=0, record=1)
    assert sched(0) == ProfilerState.RECORD_AND_RETURN


# ---------------- tracer spans + RecordEvent ----------------

def test_record_event_nesting_and_aggregates():
    tr = obs.get_tracer()
    tr.enable()
    with RecordEvent("outer"):
        for _ in range(3):
            with RecordEvent("inner"):
                pass
    tr.disable()
    evs = tr.events()
    names = [e["name"] for e in evs]
    assert names.count("inner") == 3 and names.count("outer") == 1
    outer = next(e for e in evs if e["name"] == "outer")
    inners = [e for e in evs if e["name"] == "inner"]
    # nesting: every inner interval is contained in outer's, same thread
    for i in inners:
        assert i["tid"] == outer["tid"]
        assert outer["ts"] <= i["ts"]
        assert i["ts"] + i["dur"] <= outer["ts"] + outer["dur"] + 1e-9
    # aggregates (the summary() data source) saw the same counts
    st = get_event_stats()
    assert st["inner"][0] == 3 and st["outer"][0] == 1
    assert st["outer"][1] >= st["inner"][1]  # total time contains children


def test_record_event_aggregates_without_tracing():
    # aggregates are always on (summary works outside a trace window) but no
    # timeline events accumulate while disabled
    with RecordEvent("agg_only"):
        pass
    assert get_event_stats()["agg_only"][0] == 1
    assert obs.get_tracer().events() == []


def test_tracer_span_api_and_ring_buffer_bound():
    tr = obs.Tracer(capacity=4)
    tr.enable()
    for i in range(10):
        with tr.span("s", i=i):
            pass
    evs = tr.events()
    assert len(evs) == 4  # ring buffer dropped the oldest
    assert tr.dropped == 6
    assert [e["args"]["i"] for e in evs] == [6, 7, 8, 9]


def test_disabled_span_is_noop_singleton():
    tr = obs.Tracer()
    s1, s2 = tr.span("a"), tr.span("b")
    assert s1 is s2  # shared null object: no allocation on the off path
    assert tr.events() == [] and tr.stats() == {}


# ---------------- chrome trace export round-trip ----------------

def test_chrome_trace_roundtrip(tmp_path):
    tr = obs.get_tracer()
    tr.enable()
    with RecordEvent("step"):
        with RecordEvent("matmul"):
            pass
        with RecordEvent("matmul"):
            pass
    tr.disable()
    path = tr.export_chrome_trace(str(tmp_path / "host.json"))
    doc = json.load(open(path))
    assert "traceEvents" in doc
    complete = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    assert {e["name"] for e in complete} == {"step", "matmul"}
    assert all("ts" in e and "dur" in e and "tid" in e for e in complete)

    res = load_profiler_result(path)
    st = res.stats()
    assert st["matmul"][0] == 2 and st["step"][0] == 1
    # loaded aggregates match the live tracer's within export rounding
    live = get_event_stats()
    assert abs(live["step"][1] - st["step"][1]) < 1e-3
    t0, t1 = res.time_range()
    assert t1 >= t0


def test_load_profiler_result_from_directory(tmp_path):
    tr = obs.Tracer()
    tr.enable()
    with tr.span("a"):
        pass
    tr.export_chrome_trace(str(tmp_path / "w0.json"))
    tr.export_chrome_trace(str(tmp_path / "w1.json"))
    res = load_profiler_result(str(tmp_path))
    assert res.stats()["a"][0] == 2  # merged across worker files


def test_load_profiler_result_rejects_non_trace(tmp_path):
    p = tmp_path / "bad.json"
    p.write_text('{"not_a_trace": 1}')
    with pytest.raises(ValueError, match="traceEvents"):
        load_profiler_result(str(p))


# ---------------- export_chrome_tracing ordering fix ----------------

def test_export_dir_applied_at_construction(tmp_path):
    # the requested dir must be in force BEFORE the first trace window opens
    # (previously assigned on trace-ready, after _start_trace had already
    # written to the old directory)
    want = str(tmp_path / "requested")
    prof = Profiler(on_trace_ready=export_chrome_tracing(want),
                    scheduler=make_scheduler(closed=0, ready=0, record=1),
                    use_device_profiler=False)
    assert prof._export_dir == want
    prof.start()   # immediately RECORD_AND_RETURN: opens + closes one window
    with RecordEvent("in_window"):
        pass
    prof.step()
    prof.stop()
    files = os.listdir(want)
    assert any(f.endswith(".json") for f in files)
    res = load_profiler_result(want)
    assert "in_window" in res.stats()


def test_profiler_summary_reads_tracer(capsys):
    prof = Profiler(timer_only=True)
    prof.start()
    with RecordEvent("ev"):
        pass
    prof.step()
    prof.stop()
    prof.summary()
    out = capsys.readouterr().out
    assert "ips:" in out and "ev" in out


# ---------------- Benchmark reader_cost ----------------

def test_benchmark_tracks_reader_cost():
    b = Benchmark()
    b.begin()
    b.step(num_samples=4, reader_cost=0.01)
    b.step(num_samples=4, reader_cost=0.03)
    b.end()
    rep = b.report()
    assert rep["steps"] == 2
    assert rep["reader_cost"] == pytest.approx(0.02)  # tracked avg, not 0.0


def test_benchmark_reader_cost_defaults_to_zero():
    b = Benchmark()
    b.begin()
    b.step()
    rep = b.report()
    assert rep["reader_cost"] == 0.0


# ---------------- StepTelemetry + engine integration ----------------

def test_engine_step_telemetry_jsonl_and_trace(tmp_path):
    """The acceptance path: one CPU train step with telemetry on yields a
    loadable chrome trace AND a JSONL record with wall time, throughput,
    compile count, and memory stats."""
    e = _tiny_engine()
    jsonl = str(tmp_path / "steps.jsonl")
    e.enable_telemetry(path=jsonl)
    tr = obs.get_tracer()
    tr.enable()
    x, y = _batch()
    e.step(x, y)
    e.step(x, y)
    tr.disable()
    e.disable_telemetry()

    recs = [json.loads(l) for l in open(jsonl)]
    assert len(recs) == 2
    r0, r1 = recs
    assert r0["event"] == "train_step" and r0["step"] == 1
    assert r0["wall_time_s"] > 0
    assert r0["samples"] == 8 and r0["samples_per_sec"] > 0
    assert r0["jit_compiles"] >= 1  # first step compiled
    assert "device_memory" in r0  # {} on the CPU mesh, populated on TPU
    assert r0["dispatch_calls"] >= 1
    # second step hit the executable cache: no new compile
    assert r1.get("jit_compiles_delta", 0) == 0
    assert r0["loss"] == pytest.approx(float(np.asarray(e.last_loss._data)),
                                       rel=1.0)  # same scale, both finite

    # the same window produced a loadable chrome trace with the step span
    path = tr.export_chrome_trace(str(tmp_path / "host.json"))
    st = load_profiler_result(path).stats()
    assert "engine.step" in st and st["engine.step"][0] == 2


def test_engine_run_steps_telemetry():
    e = _tiny_engine()
    sink = InMemorySink()
    e.telemetry = obs.StepTelemetry(sink=sink)
    x, y = _batch()
    e.run_steps(x, y, steps=3)
    assert len(sink.records) == 1
    rec = sink.records[0]
    assert rec["steps_fused"] == 3
    assert rec["samples"] == 24  # 3 fused steps x batch 8
    assert rec["jit_compiles"] >= 1


def test_engine_telemetry_flop_model():
    e = _tiny_engine()
    e.enable_telemetry(sink=InMemorySink())
    # default model is parameter-only 6*N
    n_params = sum(int(np.prod(p.shape)) for p in e.model.parameters())
    assert e.telemetry.flops_per_token == 6 * n_params

    assert (obs.transformer_flops_per_token(
        n_params, num_layers=2, hidden_size=8, seq_len=4)
        == 6 * n_params + 12 * 2 * 8 * 4)  # the bench.py convention
    # clean numbers: 2 GFLOP/token, 2000 tok/s -> 4 TFLOP/s; peak 8 -> mfu 0.5
    tele = obs.StepTelemetry(sink=InMemorySink(),
                             flops_per_token=2_000_000_000, peak_flops=8e12)
    rec = tele.record_step(step=1, wall_time=0.5, tokens=1000)
    assert rec["tokens_per_sec"] == 2000.0
    assert rec["tflops_per_sec"] == pytest.approx(4.0)
    assert rec["mfu"] == pytest.approx(0.5)


def test_telemetry_off_no_spans_no_io(tmp_path, monkeypatch):
    """Overhead honesty: telemetry off means the step path records its three
    engine-boundary spans (always on, no sync, no I/O), no span per op, and
    opens no files."""
    monkeypatch.delenv("PADDLE_TPU_TELEMETRY_DIR", raising=False)
    e = _tiny_engine()
    assert e.telemetry is None  # env unset -> nothing attached
    tr = obs.get_tracer()
    n_before = len(tr.events())

    import builtins

    opened = []
    real_open = builtins.open

    def spy_open(file, *a, **k):
        opened.append(str(file))
        return real_open(file, *a, **k)

    monkeypatch.setattr(builtins, "open", spy_open)
    x, y = _batch()
    e.step(x, y)
    monkeypatch.setattr(builtins, "open", real_open)

    # tracer disabled: the engine's boundary spans and nothing per op; the
    # step compiled, which leaves its jit phases and the first call (PR 37)
    names = sorted(ev["name"] for ev in tr.events()[n_before:])
    assert [n for n in names if not n.startswith("jit.")] == [
        "engine.dispatch", "engine.place_batch", "engine.step",
        "exec.first_call"]
    # no telemetry/trace file writes on the step path (jax may read its own
    # package data; what matters is nothing under tmp and no .jsonl/.json)
    assert not any(p.endswith((".jsonl", ".json")) for p in opened)


def test_observability_is_stdlib_without_jax():
    """The disabled path must not even import jax: the observability modules
    are loadable standalone in a jax-free interpreter."""
    code = f"""
import importlib.util, os, sys
base = os.path.join({REPO!r}, "paddle_tpu", "observability")
mods = {{}}
for name in ("tracer", "step_telemetry", "flops", "metrics",
             "flight_recorder"):
    spec = importlib.util.spec_from_file_location(
        "obs_" + name, os.path.join(base, name + ".py"))
    m = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(m)
    mods[name] = m
t = mods["tracer"].Tracer()
with t.span("off"):
    pass          # disabled: no-op
t.enable()
with t.span("on"):
    pass
assert [e["name"] for e in t.events()] == ["on"]
s = mods["step_telemetry"].StepTelemetry(
    sink=mods["step_telemetry"].InMemorySink(), collect_memory=False)
h = mods["metrics"].MetricRegistry().histogram("lat_ms")
h.observe(1.5)
assert h.count == 1
fr = mods["flight_recorder"].FlightRecorder("/tmp/unused", capacity=4)
fr.record({{"event": "probe"}})
assert len(fr.records()) == 1
assert "jax" not in sys.modules, "observability pulled in jax"
"""
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True)
    assert r.returncode == 0, r.stderr


def test_env_var_attaches_jsonl_sink(tmp_path, monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_TELEMETRY_DIR", str(tmp_path))
    e = _tiny_engine()
    assert e.telemetry is not None
    assert isinstance(e.telemetry.sink, JsonlSink)
    x, y = _batch()
    e.step(x, y)
    recs = [json.loads(l)
            for l in open(tmp_path / "step_telemetry.jsonl")]
    assert len(recs) == 1 and recs[0]["step"] == 1


# ---------------- dispatch counters ----------------

def test_dispatch_counters_and_per_op_stats():
    from paddle_tpu.core import monitor

    calls = monitor.stat("dispatch.calls")
    before = calls.get()
    x = paddle.to_tensor(np.ones((4, 4), np.float32))
    (x @ x + x).sum()
    assert calls.get() > before
    rep = monitor.registry().report()
    per_op = [k for k in rep if k.startswith("dispatch.op.")]
    assert per_op, "per-op dispatch counters missing"


def test_dispatch_spans_when_traced():
    tr = obs.get_tracer()
    x = paddle.to_tensor(np.ones((4, 4), np.float32))
    tr.enable()
    y = x @ x
    tr.disable()
    names = [e["name"] for e in tr.events()]
    assert any(n.startswith("op::") for n in names)


def test_nan_inf_counter():
    from paddle_tpu.core import monitor

    hits = monitor.stat("dispatch.nan_inf_hits")
    before = hits.get()
    paddle.set_flags({"FLAGS_check_nan_inf": True})
    x = paddle.to_tensor(np.zeros((2,), np.float32))
    with pytest.raises(FloatingPointError):
        x / x  # 0/0 -> nan
    assert hits.get() == before + 1


# ---------------- hapi fit integration ----------------

def test_hapi_fit_telemetry_callback_and_reader_cost():
    from paddle_tpu.hapi.callbacks import TelemetryCallback

    paddle.seed(0)
    net = paddle.nn.Sequential(paddle.nn.Linear(4, 8), paddle.nn.ReLU(),
                               paddle.nn.Linear(8, 2))
    model = paddle.Model(net)
    model.prepare(optimizer=paddle.optimizer.SGD(
        learning_rate=0.1, parameters=net.parameters()),
        loss=paddle.nn.CrossEntropyLoss())
    rng = np.random.RandomState(0)
    data = [(rng.randn(4, 4).astype(np.float32),
             rng.randint(0, 2, (4,)).astype(np.int64)) for _ in range(3)]
    cb = TelemetryCallback()
    # batch_size names the per-batch sample count for logging (the loader
    # here yields prebaked batches of 4 — hapi convention)
    model.fit(data, epochs=1, batch_size=4, verbose=0, callbacks=[cb])
    recs = cb.telemetry.sink.records
    assert len(recs) == 3
    for r in recs:
        assert r["wall_time_s"] > 0
        assert r["samples"] == 4
        assert "reader_cost_s" in r  # tracked, not hard-coded
        assert isinstance(r["loss"], float)
