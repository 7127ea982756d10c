"""The cell `serve-sdar-30b-a3b-diffusion`: its files against the linter and
the catalog's row, its runner rehearsed at the small size on the CPU, its
readers on what the runner collected and on a recorded `collected`, the
bytes a forward must read against a hand count, and every control of its
check. A CPU run shows control flow and counts, never a speed."""
import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
HERE = os.path.join(ROOT, "benchmarks")

from benchmarks import lint_manifest, run as bench_run  # noqa: E402
from benchmarks.lib import trace  # noqa: E402
from benchmarks.lib.decode_bytes_sdar import forward_bytes  # noqa: E402

CELL = "serve-sdar-30b-a3b-diffusion"
NEW_METRICS = ("serve.step_ms_p50.sdar", "serve.occupancy.sdar",
               "serve.host_gap_ms_p50.sdar", "device.idle_share.sdar",
               "serve.prefill_share.sdar", "moe.experts_touched.sdar",
               "moe.max_load.sdar", "diffusion.forwards_per_block.sdar",
               "diffusion.tokens_per_forward.sdar",
               "serve.decode_bytes_roofline.sdar")
SHARED_METRICS = ("setup.compile_s", "setup.import_s",
                  "setup.serve_engine_init_s", "setup.jit_trace_s",
                  "setup.jit_lower_s", "setup.jit_backend_s",
                  "setup.jit_unregistered_s", "serve.window_jit_traces")
TINY = {"vocab_size": 256, "hidden_size": 64, "intermediate_size": 128,
        "moe_intermediate_size": 32, "num_hidden_layers": 3,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "num_experts": 8, "num_experts_per_tok": 2, "norm_topk_prob": True,
        "max_position_embeddings": 64, "rms_norm_eps": 1e-6,
        "rope_theta": 1000000, "block_length": 4, "mask_token_id": 255,
        "dtype": "float32",
        # large enough that the experts' output is of the stream's size, as
        # it is at the published widths: at 0.02 and hidden 64 it vanishes
        "initializer_range": 0.1}


def load(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        pytest.skip("the catalog is not on this machine")
    with open(path) as f:
        return next(r for r in map(json.loads, f)
                    if r["name"] == "SDAR-30B-A3B-Chat")


def test_the_new_files_pass_the_linter():
    """Nothing the linter says is about this cell, but for one line: its
    WIDTH pattern takes `hidden` in `num_hidden_layers` for a width, where
    the contract's own example lists that key (PERF.md §7)."""
    about = [p for p in lint_manifest.lint(ROOT)
             if "sdar" in p or "diffusion" in p]
    assert about == ["config sdar-30b-a3b: reduced names "
                     "'num_hidden_layers', a width or not a name"], about


def test_configuration_is_the_published_one_but_for_its_depth():
    conf = load("configs", "sdar-30b-a3b.json")
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(c for c in manifest["configs"] if c["name"] == "sdar-30b-a3b")
    row = catalog_row()
    assert entry["source"] == conf["source"] == row["source_url"]
    assert entry["reduced"] == conf["reduced"] == ["num_hidden_layers"]
    for key, value in row["config"].items():
        if key in conf["reduced"]:
            assert conf["published"][key] == value, key
        else:
            assert conf[key] == value, key
    # the floor: every layer is alike, so four layers or more; every expert,
    # every head, the whole vocabulary
    assert conf["num_hidden_layers"] == 6 >= 4
    assert (conf["block_length"], conf["mask_token_id"]) == (4, 151669)
    assert len(conf["assumed"]) >= 10
    assert "4,361M parameters, 8.72 GB" in conf["deployment"]
    assert "one chip shares each layer" in conf["deployment"]


def test_cell_traffic_and_engine_are_the_issues():
    cell = load("workloads", CELL + ".json")
    assert cell["chips"] == 1 and cell["runner"] == "serve_sdar"
    assert cell["traffic"] == "diffusion-backlog"
    assert cell["engine"] == {
        "slot_count": 64, "max_seq_len": 3072,
        "ladder": [256, 512, 1024, 2048], "max_new_cap": 1024,
        "steps_per_dispatch": 10, "kv_layout": "contiguous"}
    traffic = load("traffic", "diffusion-backlog.json")
    assert traffic["arrival"] == {"process": "backlog", "depth": 96,
                                  "max_rps": 8}
    assert traffic["prompt_len"] == {"dist": "lognormal", "median": 512,
                                     "sigma": 0.6, "min": 128, "max": 2048}
    assert traffic["max_new"] == {"dist": "fixed", "value": 1024}
    assert traffic["sampling"] == load(
        "traffic", "decode-backlog-long.json")["sampling"]
    assert traffic["generation"] == {
        "block_length": 4, "denoising_steps": 4,
        "remasking": "low_confidence_static"}
    assert (traffic["stagger"], traffic["block"], traffic["lead_in_s"]) == (
        64, 16, 10.0)
    # the longest prompt and its whole answer fit a slot
    assert 2048 + 1024 <= cell["engine"]["max_seq_len"]
    resolved = bench_run.resolve(CELL, ROOT)
    assert {m["name"] for m in resolved["per_layer"]} == set(
        NEW_METRICS) | set(SHARED_METRICS) == set(cell["layer_metrics"])
    assert [m["name"] for m in resolved["end_to_end"]] == [
        "serve_tokens_per_s", "setup_s"]
    # nothing the other cells report has changed
    for other in ("serve-trinity-mini-decode", "serve-deepseek-v2-decode"):
        names = {m["name"] for m in bench_run.resolve(other, ROOT)["per_layer"]}
        assert not names & set(NEW_METRICS)


def test_parameter_count_and_forward_bytes_against_a_hand_count():
    """18.87M an attention layer, 4.72M an expert, 623.1M a layer; 4,361M in
    all. A forward at 64 slots of 1,150 positions with every expert touched
    reads about 9 GB."""
    conf = load("configs", "sdar-30b-a3b.json")
    attention = 2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048
    expert = 3 * 2048 * 768
    layer = attention + 2 * 128 + 2048 * 128 + 128 * expert + 2 * 2048
    total = 6 * layer + 2 * 151936 * 2048 + 2048
    assert round(attention / 1e6, 2) == 18.87 and round(expert / 1e6, 2) == 4.72
    assert round(layer / 1e6, 1) == 623.1 and round(total / 1e6) == 4361
    parts = forward_bytes(conf, [1150] * 64, 128.0)
    assert parts["experts"] == 6 * 128 * expert * 2
    assert parts["attention_weights"] == 6 * attention * 2
    assert parts["head"] == 2048 * 151936 * 2
    assert parts["router"] == 6 * 2048 * 128 * 4
    assert parts["rows"] == 6 * 64 * 1150 * 2048
    assert parts["total"] == sum(v for k, v in parts.items() if k != "total")
    assert 9.0e9 < parts["total"] < 9.1e9
    # half the experts touched: half their bytes, nothing else moves
    half = forward_bytes(conf, [1150] * 64, 64.0)
    assert half["experts"] * 2 == parts["experts"]
    assert half["total"] - half["experts"] == parts["total"] - parts["experts"]


def _collected(records, **kw):
    out = {"window": (100.0, 140.0), "wall_minus_perf": 1000.0,
           "steps_per_dispatch": 10, "sink": records, "chips": 1,
           "device_kind": "TPU v5 lite",
           "config": load("configs", "sdar-30b-a3b.json"),
           "setup_counters": {"engine.compile_cold_ms": 1500,
                              "engine.compile_warm_ms": 500}}
    out.update(kw)
    return out


def _records():
    records = []
    for i in range(20):
        end = 1140.0 - 0.16 * (19 - i)
        records.append({
            "event": "serve_step", "ts": end, "steps_per_dispatch": 10,
            "occupancy": 0.95, "host_gap_ms": 4.0 + i % 2,
            "moe_touched": 128.0, "moe_touched_held": 128.0,
            "moe_max_load": 30, "contexts": [1150] * 64,
            "forwards": 640, "blocks_committed": 128, "tokens": 512,
            "positions_unmasked": 512,
            "spans_ms": {"decode_dispatch": 10.0, "decode_fetch": 140.0,
                         "emit": 0.0}})
    return records


def test_readers_return_numbers_from_a_recorded_collected():
    """Twenty dispatches of 160 ms in the last 3.2 s of a window."""
    conf = load("configs", "sdar-30b-a3b.json")
    records = _records()
    run = _collected(
        records, steps=[(100.0 + i, 100.16 + i, 0) for i in range(30)],
        trace={"window_s": 3.0, "busy_s": 2.8, "ops": {}, "idle_gaps": {},
               "modules": {"jit_block_chunk": 2.4, "jit_block_prefill": 0.35}})
    got = {n: bench_run.load_module("layer_metrics", n).read(run)
           for n in NEW_METRICS + ("setup.compile_s",)}
    assert got["serve.step_ms_p50.sdar"] == pytest.approx(16.0)
    assert got["serve.occupancy.sdar"] == pytest.approx(95.0)
    assert got["serve.host_gap_ms_p50.sdar"] == pytest.approx(4.5)
    assert got["device.idle_share.sdar"] == pytest.approx(100 * 0.2 / 3)
    assert got["serve.prefill_share.sdar"] == pytest.approx(12.5)
    assert got["moe.experts_touched.sdar"] == pytest.approx(100.0)
    assert got["moe.max_load.sdar"] == pytest.approx(30.0)
    assert got["diffusion.forwards_per_block.sdar"] == pytest.approx(5.0)
    assert got["diffusion.tokens_per_forward.sdar"] == pytest.approx(0.8)
    assert got["setup.compile_s"] == pytest.approx(2.0)
    need = forward_bytes(conf, [1150] * 64, 128.0)["total"]
    laps = 0.0
    for r in records:
        b = r["ts"]
        a = b - 0.15
        laps += max(0.0, min(b, 1140.0) - max(a, 1137.0)) / 0.15
    want = 100 * laps * 10 * need / 819e9 / 2.4
    assert got["serve.decode_bytes_roofline.sdar"] == pytest.approx(want)
    assert 0 < want < 100


def test_readers_find_nothing_where_there_is_nothing_to_read():
    """Records without the block step's fields, another configuration's
    run, an untraced run, a trace with no block-step executable: the new
    readers return nothing and raise nothing."""
    records = [{"event": "serve_step", "ts": 1139.0, "steps_per_dispatch": 8,
                "moe_touched": 80.0, "contexts": [100] * 16, "tokens": 128,
                "spans_ms": {"decode_dispatch": 10.0, "decode_fetch": 140.0}}]
    run = _collected(records, trace={"window_s": 3.0, "busy_s": 2.8,
                                     "ops": {}, "idle_gaps": {},
                                     "modules": {"jit_step_chunk": 2.4,
                                                 "jit_prefill": 0.3}})
    for name in ("serve.decode_bytes_roofline.sdar",
                 "moe.experts_touched.sdar", "moe.max_load.sdar",
                 "diffusion.forwards_per_block.sdar",
                 "diffusion.tokens_per_forward.sdar",
                 "serve.occupancy.sdar", "serve.host_gap_ms_p50.sdar"):
        assert bench_run.load_module("layer_metrics", name).read(run) is None
    other = dict(_collected(_records()),
                 config=load("configs", "trinity-mini.json"),
                 trace=run["trace"])
    for name in ("serve.decode_bytes_roofline.sdar",
                 "moe.experts_touched.sdar", "moe.max_load.sdar",
                 "diffusion.forwards_per_block.sdar",
                 "diffusion.tokens_per_forward.sdar"):
        assert bench_run.load_module("layer_metrics", name).read(other) is None
    mine = dict(_collected(_records()), trace=run["trace"])
    assert bench_run.load_module(
        "layer_metrics", "serve.decode_bytes_roofline.sdar").read(mine) is None
    for name in NEW_METRICS:
        assert bench_run.load_module("layer_metrics", name).read({}) is None


def test_runner_at_the_small_size():
    """The whole runner on the CPU: weights from the seed, the check against
    the reference on every rung (rows, tokens, confidences, choices), the
    backlog, the counters; then every reader of the cell on what it
    collected."""
    runner = bench_run.load_module("runners", "serve_sdar")
    traf = {"arrival": {"process": "backlog", "depth": 4, "max_rps": 400},
            "prompt_len": {"dist": "lognormal", "median": 10, "sigma": 0.5,
                           "min": 3, "max": 30},
            "max_new": {"dist": "fixed", "value": 12},
            "sampling": {"temperature": 0.8, "top_k": 50, "top_p": 0.9},
            "generation": {"block_length": 4, "denoising_steps": 4,
                           "remasking": "low_confidence_static"},
            "block": 8, "stagger": 4, "lead_in_s": 0.3}
    cell = {"runner": "serve_sdar", "chips": 1, "trace_seconds": 0.3,
            "engine": {"slot_count": 5, "max_seq_len": 64,
                       "ladder": [8, 16, 32], "max_new_cap": 16,
                       "steps_per_dispatch": 5, "kv_layout": "contiguous"}}
    resolved = {"cell": cell, "config": TINY, "traffic": traf}
    ctx = bench_run.Run(resolved, 2**31 + 11, 1.5, True,
                        trace.Tracer(runner.ANNOTATIONS, 1))
    out = runner.run(ctx)
    assert out["correct"], out
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["end_to_end"]["serve_tokens_per_s"] > 0
    steps = [r for r in out["collected"]["sink"]
             if r["event"] == "serve_step"]
    assert steps and all(
        0 < r["moe_touched_held"] <= 8 and r["forwards"] >= r["tokens"] / 4
        and r["positions_unmasked"] <= r["forwards"]
        and all(c % 4 == 0 for c in r["contexts"]) for r in steps)
    got = dict(out["collected"], trace=ctx.tracer.reduce(), chips=1,
               device_kind="TPU v5 lite", config=TINY)
    for name in ("serve.step_ms_p50.sdar", "serve.occupancy.sdar",
                 "serve.host_gap_ms_p50.sdar", "moe.experts_touched.sdar",
                 "moe.max_load.sdar", "diffusion.forwards_per_block.sdar",
                 "diffusion.tokens_per_forward.sdar", "setup.compile_s"):
        value = bench_run.load_module("layer_metrics", name).read(got)
        assert value is not None and value >= 0, name
    # a block of 4 under the static schedule at 4 steps: 5 forwards, but
    # for first blocks opened by given tokens, and budgets cut inside one
    assert 4.0 <= bench_run.load_module(
        "layer_metrics", "diffusion.forwards_per_block.sdar").read(got) <= 5.0
    assert 0.5 < bench_run.load_module(
        "layer_metrics", "diffusion.tokens_per_forward.sdar").read(got) <= 1.0
    # the CPU has no device plane: the trace readers find nothing to read
    for name in ("device.idle_share.sdar", "serve.prefill_share.sdar",
                 "serve.decode_bytes_roofline.sdar"):
        assert bench_run.load_module("layer_metrics", name).read(got) is None


def test_a_program_without_the_model_exits_at_once():
    """The parent of the PR that brought the model: the runner's import
    fails with a message and no model is built."""
    import subprocess
    import sys

    code = ("import sys; sys.modules['paddle_tpu.models'] = type(sys)('m'); "
            "import benchmarks.runners.serve_sdar")
    done = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          capture_output=True, text=True, timeout=120,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert done.returncode == 1
    assert "this program has no sdar model" in done.stderr


@pytest.fixture(scope="module")
def controls():
    """Every control of benchmarks/tests/controls_sdar.py through the
    runner's own `check_blocks`, at the small size."""
    from benchmarks.runners import serve_sdar
    from benchmarks.tests import controls_sdar

    return controls_sdar.readings(
        TINY, controls_sdar.TINY_ENGINE, 2**31 + 27,
        serve_sdar.request_kwargs(load("traffic", "diffusion-backlog.json")))


def test_the_plain_reference_passes_the_check(controls):
    plain = controls["plain"]
    assert plain["ok"], plain
    # float32 against float32: what the slots hold agrees to rounding
    assert plain["row_worst_first_layer"] < 1e-4
    assert plain["row_median_worst_layer"] < 1e-4
    assert plain["mean_gap"] < 1e-3
    assert plain["confidence_error_median"] < 1e-3
    assert plain["choice_shortfall_mean"] < 1e-3
    # 16 tokens: 5 blocks where given tokens open the first, else 4
    assert plain["contexts"] == [24, 28, 44]
    assert plain["forwards"] == [24, 20, 22, 23]


@pytest.mark.parametrize("name", [
    "float8", "rows_in_float8", "causal_mask", "rows_before_last_unmasking",
    "logit_shift_by_one", "confidence_untempered", "weights_not_normalised",
    "key_head_by_modulo", "qk_norm_dropped"])
def test_a_wrong_reference_fails_the_check(controls, name):
    """By the limits the chip's cell runs under. (`router_in_bfloat16` is
    read on the chip alone: at the small size eight experts' scores lie too
    far apart for bfloat16 to flip a choice.)"""
    assert not controls[name]["ok"], controls[name]


def test_the_check_reads_each_measure_where_it_should(controls):
    from benchmarks.runners import serve_sdar as runner

    # a commit that kept the rows of the forward before the last unmasking:
    # the mask token's row in the first layer, whatever the layers above do
    kept = controls["rows_before_last_unmasking"]
    assert kept["row_worst_first_layer"] > 0.5
    # the wrong mask leaves the first layer's rows alone (they follow from
    # the embedding) and moves the rows above and the tokens
    causal = controls["causal_mask"]
    assert causal["row_worst_first_layer"] < 1e-4
    assert causal["row_median_worst_layer"] > runner.ROW_MEDIAN_TOLERANCE
    # a shift by one: the rows are right, the tokens are another row's
    shift = controls["logit_shift_by_one"]
    assert shift["row_median_worst_layer"] < 1e-4
    assert shift["mean_gap"] > runner.MEAN_GAP_TOLERANCE
    # the untempered distribution: only the sampled request's confidences
    plain, conf = controls["plain"], controls["confidence_untempered"]
    assert conf["mean_gap"] == plain["mean_gap"]
    assert conf["row_median_worst_layer"] == plain["row_median_worst_layer"]
    assert conf["confidence_error_median"] > runner.CONFIDENCE_TOLERANCE
    # rows kept in float8: the rows themselves, in every layer
    low = controls["rows_in_float8"]
    assert low["row_worst_first_layer"] > runner.ROW_TOLERANCE
