"""The benchmark's own tests: the rehearsals of both runners at gpt_tiny on
the CPU, the reference, the generator, the trace reduction, the linter, and
the proof that a cell and a metric are added by files alone. A CPU run shows
control flow and counts, never a speed."""
import json
import os
import shutil
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
HERE = os.path.join(ROOT, "benchmarks")

from benchmarks import lint_manifest, run as bench_run  # noqa: E402
from benchmarks.lib import peaks, reference, stats, trace, traffic  # noqa: E402

TINY = {"vocab_size": 1000, "padded_vocab_size": 1024, "n_positions": 128,
        "n_embd": 128, "n_layer": 2, "n_head": 4, "n_inner": None}
ANNOTATIONS = ("feed", "engine_step", "wait")


def tiny_run(cell, traf, seconds=0.5, trace_on=False, seed=3):
    resolved = {"cell": cell, "config": TINY, "traffic": traf}
    tracer = trace.Tracer(ANNOTATIONS, cell["chips"]) if trace_on else None
    return bench_run.Run(resolved, seed, seconds, trace_on, tracer)


def train_cell(chips=1, **engine_kw):
    return {"runner": "train", "chips": chips, "learning_rate": 1e-3,
            "weight_decay": 0.01, "engine_kw": engine_kw,
            "steps_per_sync": 2, "trace_seconds": 0.2}


TRAIN_TRAFFIC = {"kind": "batches", "batch_per_chip": 2, "seq_len": 64,
                 "distinct_batches": 4}
SERVE_ENGINE = {"slot_count": 4, "ladder": [16, 32], "max_new_cap": 16,
                "steps_per_dispatch": 4, "kv_layout": "contiguous"}
SAMPLING = {"temperature": 0.8, "top_k": 50, "top_p": 0.9}


def test_train_runner_at_tiny_size():
    runner = bench_run.load_module("runners", "train")
    ctx = tiny_run(train_cell(), TRAIN_TRAFFIC, trace_on=True)
    out = runner.run(ctx)
    assert out["correct"], out
    assert out["attempted"] >= 2 and out["failed"] == 0
    assert out["end_to_end"]["train_tokens_per_s"] > 0
    assert ctx.window_start is not None
    got = dict(out["collected"], trace=ctx.tracer.reduce(),
               device_kind="TPU v5 lite", chips=1)
    # every reader of the cell runs on what the runner collected; the CPU
    # has no device plane, so the trace readers find nothing and say so
    for name in ("train.step_ms_p50", "train.recompiles", "train.mfu",
                 "setup.compile_s"):
        assert bench_run.load_module("layer_metrics", name).read(got) \
            is not None, name
    for name in ("kernels.flash_share.train", "kernels.flash_roofline.train",
                 "device.idle_share.train"):
        assert bench_run.load_module("layer_metrics", name).read(got) is None


def test_train_runner_fsdp_on_four_virtual_devices():
    import jax

    assert len(jax.devices()) >= 4
    runner = bench_run.load_module("runners", "train")
    out = runner.run(tiny_run(train_cell(chips=4, fsdp=True), TRAIN_TRAFFIC))
    assert out["correct"], out
    assert out["collected"]["batch_per_chip"] == 2


@pytest.mark.parametrize("process", ["poisson", "backlog"])
def test_serve_runner_at_tiny_size(process):
    runner = bench_run.load_module("runners", "serve")
    traf = {"prompt_len": {"dist": "lognormal", "median": 10, "sigma": 0.5,
                           "min": 4, "max": 30},
            "max_new": {"dist": "uniform", "min": 4, "max": 12},
            "sampling": SAMPLING, "block": 8, "lead_in_s": 0.3}
    if process == "poisson":
        traf.update(arrival={"process": "poisson", "rate_rps": 6.0},
                    drain_s=30.0)
    else:
        traf.update(arrival={"process": "backlog", "depth": 4,
                             "max_rps": 2000}, stagger=4)
    cell = {"runner": "serve", "chips": 1, "engine": SERVE_ENGINE,
            "trace_seconds": 0.3}
    ctx = tiny_run(cell, traf, seconds=1.5, trace_on=True)
    out = runner.run(ctx)
    assert out["correct"], out
    assert out["attempted"] > 0 and out["failed"] == 0
    got = dict(out["collected"], trace=ctx.tracer.reduce(), chips=1)
    if process == "poisson":
        assert out["end_to_end"]["ttft_p95_ms"] > 0
        assert out["end_to_end"]["tpot_p95_ms"] > 0
        names = ("serve.queue_wait_p95_ms", "serve.prefill_ms_p50",
                 "serve.submit_lag_p95_ms", "serve.step_ms_p50.chat")
    else:
        assert out["end_to_end"]["serve_tokens_per_s"] > 0
        names = ("serve.step_ms_p50.decode", "serve.occupancy.decode")
    for name in names + ("setup.compile_s",):
        value = bench_run.load_module("layer_metrics", name).read(got)
        assert value is not None and value >= 0, name


def test_reference_agrees_with_the_model_at_tiny_size():
    """f32 against f32: the two share no code, so agreement to rounding says
    both compute GPT-2; the tolerance is float32 noise over two layers."""
    import jax

    bench_run.load_module("runners", "train")
    from benchmarks.runners import common

    model = common.build_model(TINY, seed=11)
    model.eval()
    cfg = model.config
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 1000, (3, 48), dtype=np.int64)
    labels = np.roll(ids, -1, 1)
    state = common.state_arrays(model)
    import paddle_tpu as paddle

    want_loss = float(model(paddle.to_tensor(ids),
                            paddle.to_tensor(labels)).item())
    got = np.asarray(jax.jit(lambda s, x, y: reference.loss_per_sequence(
        s, x, y, cfg.num_layers, cfg.num_heads))(state, ids, labels))
    assert abs(float(got.mean()) - want_loss) < 1e-4
    want_logits = model(paddle.to_tensor(ids)).numpy()
    pos = np.tile(np.arange(40, 48), (3, 1))
    got_logits = np.asarray(reference.logits_at(
        state, ids, pos, cfg.num_layers, cfg.num_heads))
    np.testing.assert_allclose(got_logits, want_logits[:, 40:48], atol=2e-4)


def test_schedule_is_the_seeds_and_stratified():
    traf = json.load(open(os.path.join(HERE, "traffic", "chat-poisson.json")))
    a = traffic.requests(traf, 2**31 + 7, 30.0, 50257)
    b = traffic.requests(traf, 2**31 + 7, 30.0, 50257)
    c = traffic.requests(traf, 5, 30.0, 50257)
    assert json.dumps(a) == json.dumps(b)
    assert json.dumps(a) != json.dumps(c)
    # another seed, the same work: every whole block holds the same lengths
    block = traf["block"]
    for key in ("max_new",):
        assert sorted(r[key] for r in a[:block]) == \
            sorted(r[key] for r in c[:block])
    assert sorted(len(r["prompt"]) for r in a[:block]) == \
        sorted(len(r["prompt"]) for r in c[:block])
    rate = traf["arrival"]["rate_rps"]
    horizon = traf["lead_in_s"] + 30.0 + traf["drain_s"]
    assert abs(len(a) - rate * horizon) <= block
    assert abs(a[block - 1]["due"] - block / rate) < 1e-6
    lens = [len(r["prompt"]) for r in a]
    assert min(lens) >= 16 and max(lens) <= 512
    assert all(0 <= t < 50257 for r in a[:8] for t in r["prompt"])


def test_spike_backlog_and_shared_prefix_need_no_new_code():
    base = {"prompt_len": {"dist": "fixed", "value": 20},
            "max_new": {"dist": "choice", "values": [4, 8]}, "block": 8}
    spike = dict(base, arrival={"process": "spike", "rate_rps": 10.0,
                                "spike_factor": 4.0}, lead_in_s=0.0)
    rows = traffic.requests(spike, 1, 30.0, 100)
    inside = sum(1 for r in rows if 10.0 <= r["due"] < 20.0)
    assert abs(inside - 400) <= 8 and abs(len(rows) - 600) <= 8
    backlog = dict(base, arrival={"process": "backlog", "depth": 4},
                   max_new={"dist": "fixed", "value": 8}, stagger=4,
                   shared_prefix={"groups": 2, "len": 12})
    rows = traffic.requests(backlog, 1, 30.0, 100, count=40)
    assert len(rows) == 40 and all(r["due"] == 0.0 for r in rows)
    assert [r["max_new"] for r in rows[:6]] == [2, 4, 6, 8, 8, 8]
    assert len({tuple(r["prompt"][:12]) for r in rows}) == 2


def test_trace_reduction_on_a_recorded_trace():
    """probe.xplane.pb: six rounds of {feed, engine_step, wait} round an
    8-matmul chain on one TPU v5e chip (recorded by hand, PR 25)."""
    path = os.path.join(HERE, "tests", "data", "probe.xplane.pb")
    got = trace.reduce_xplane(path, ANNOTATIONS)
    assert got["modules"]["jit_chain"] == pytest.approx(4.407e-3, rel=1e-3)
    assert got["ops"]["fusion"] == pytest.approx(4.331e-3, rel=1e-3)
    # a chain is 8 fusions back to back: busy is their union, not their sum
    assert got["busy_s"] == pytest.approx(4.639e-3, rel=1e-3)
    assert got["busy_s"] <= sum(got["ops"].values()) + 1e-9
    idle = got["window_s"] - got["busy_s"]
    assert sum(got["idle_gaps"].values()) == pytest.approx(idle, rel=1e-6)
    assert got["idle_gaps"]["feed"] > got["idle_gaps"]["wait"] > 0
    assert [k for k, _ in trace.top(got["ops"], 2)] == ["fusion", "copy"]


def test_op_key_reads_hlo_text():
    assert trace.op_key(
        '%fusion.7 = bf16[8,128]{1,0:T(8,128)(2,1)S(1)} fusion(bf16[8,128] '
        '%p), kind=kOutput, calls=%fc.15') == ("fusion", "fusion")
    assert trace.op_key(
        '%copy-start = (bf16[8]{0:T(8)S(1)}, bf16[8]{0}, u32[]{:S(2)}) '
        'copy-start(bf16[8]{0} %x.1)') == ("copy-start", "copy-start")
    assert trace.op_key(
        '%custom-call.3 = bf16[96,1024,64]{2,1,0} custom-call(bf16[96,1024,64]'
        ' %q), custom_call_target="tpu_custom_call"')[0] == "tpu_custom_call"
    assert trace.op_key('%while.2 = (s32[], f32[4]) while((s32[], f32[4]) '
                        '%t), condition=%c, body=%b')[1] == "while"


def test_yardstick_arithmetic():
    assert stats.percentile([1, 2, 3, 4, 5], 0.5) == 3
    assert stats.percentile(list(range(101)), 0.95) == 95
    assert stats.percentile([], 0.5) is None
    with pytest.raises(KeyError):
        peaks.peak("TPU v9")
    # GPT-2 medium, b8 s1024: attention is 6*L*h*s a token
    flops = peaks.causal_attention_flops(8, 16, 1024, 64)
    assert 24 * flops == 6 * 24 * 1024 * 1024 * 8 * 1024
    assert peaks.train_flops_per_token(10, 2, 3, 4) == 60 + 6 * 2 * 3 * 4
    least, bound = peaks.roofline_seconds(
        flops, peaks.causal_attention_bytes(8, 16, 1024, 64), "TPU v5 lite")
    assert bound == "compute" and least == pytest.approx(flops / 197e12)


def test_linter_passes_the_manifest_handed_in():
    assert lint_manifest.lint(ROOT) == []


def test_linter_refuses_a_layer_of_two_words(tmp_path):
    root = _copy(tmp_path)
    manifest = json.load(open(os.path.join(root, "BENCHMARK.json")))
    manifest["per_layer"][0]["layer"] = "train engine"
    json.dump(manifest, open(os.path.join(root, "BENCHMARK.json"), "w"))
    problems = lint_manifest.lint(root)
    assert any("layer" in p and "train engine" in p for p in problems)


def _copy(tmp_path):
    root = str(tmp_path / "checkout")
    os.makedirs(root)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(HERE, os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    return root


def test_a_cell_and_a_metric_are_added_by_files_alone(tmp_path):
    """A later PR adds a cell and a per-layer metric: new files, new
    entries, no file that is there edited (checked byte for byte)."""
    root = _copy(tmp_path)
    here = os.path.join(root, "benchmarks")

    def digest():
        out = {}
        for d, _, files in os.walk(here):
            for f in files:
                out[os.path.join(d, f)] = open(os.path.join(d, f), "rb").read()
        return out

    before = digest()
    cell = json.load(open(os.path.join(
        here, "workloads", "serve-gpt2-large-chat.json")))
    cell.update(traffic="throwaway-burst",
                layer_metrics=["serve.requests.throwaway",
                               "setup.compile_s"])
    json.dump(cell, open(os.path.join(
        here, "workloads", "serve-gpt2-large-throwaway.json"), "w"))
    traf = json.load(open(os.path.join(here, "traffic", "chat-poisson.json")))
    traf["arrival"] = {"process": "spike", "rate_rps": 4.0,
                       "spike_factor": 4.0}
    json.dump(traf, open(os.path.join(
        here, "traffic", "throwaway-burst.json"), "w"))
    with open(os.path.join(here, "layer_metrics",
                           "serve.requests.throwaway.py"), "w") as f:
        f.write('LAYER, UNIT, MOVES, SOURCE = "serving_engine", "count", '
                '"ttft_p95_ms", "program_counter"\n\n\n'
                'def read(run):\n    return len(run.get("requests", []))\n')
    manifest = json.load(open(os.path.join(root, "BENCHMARK.json")))
    name = "serve-gpt2-large-throwaway"
    manifest["workloads"].append({
        "name": name, "config": "gpt2-large", "traffic": "throwaway-burst",
        "chips": 1, "why": "a throw-away cell"})
    for m in manifest["end_to_end"]:
        if m["name"] in ("ttft_p95_ms", "tpot_p95_ms"):
            m["workloads"].append(name)
    manifest["per_layer"].append({
        "name": "serve.requests.throwaway", "unit": "count",
        "better": "higher", "source": "program_counter",
        "layer": "serving_engine", "moves": "ttft_p95_ms",
        "workloads": [name]})
    json.dump(manifest, open(os.path.join(root, "BENCHMARK.json"), "w"))

    assert lint_manifest.lint(root) == []
    resolved = bench_run.resolve(name, root)
    assert resolved["traffic"]["arrival"]["process"] == "spike"
    assert sorted(m["name"] for m in resolved["per_layer"]) == [
        "serve.requests.throwaway", "setup.compile_s"]
    assert {m["name"] for m in resolved["end_to_end"]} == {
        "ttft_p95_ms", "tpot_p95_ms", "setup_s"}
    reader = bench_run.load_module("layer_metrics",
                                   "serve.requests.throwaway", here)
    assert reader.read({"requests": [1, 2]}) == 2
    after = digest()
    assert all(after[p] == data for p, data in before.items())
