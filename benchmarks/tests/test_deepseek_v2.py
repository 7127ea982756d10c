"""The cell `serve-deepseek-v2-decode`: its files against the linter and the
catalog's row, its runner rehearsed at the small size on the CPU, its readers
on what the runner collected and on a recorded `collected`, the bytes and
FLOPs functions against a hand count at the published shapes, and the
controls of its check. A CPU run shows control flow and counts, never a
speed."""
import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
HERE = os.path.join(ROOT, "benchmarks")

from benchmarks import lint_manifest, run as bench_run  # noqa: E402
from benchmarks.lib import trace  # noqa: E402
from benchmarks.lib.decode_bytes_mla import (  # noqa: E402
    attention_parameters, decode_step_bytes, decode_step_flops,
    expert_parameters, latent_row_bytes)
from benchmarks.lib.prefill_flops_mla import prefill_flops  # noqa: E402

CELL = "serve-deepseek-v2-decode"
NEW_METRICS = ("serve.step_ms_p50.deepseek", "serve.occupancy.deepseek",
               "serve.host_gap_ms_p50.deepseek", "device.idle_share.deepseek",
               "serve.prefill_share.deepseek", "moe.experts_touched.deepseek",
               "moe.max_load.deepseek", "serve.decode_bytes_roofline.deepseek",
               "serve.prefill_flops_roofline.deepseek")
# one chip's share at the small size: experts 4 to 7 of 16 (one routing
# group), a quarter of a vocabulary of 256
TINY = {"vocab_size": 64, "hidden_size": 64, "intermediate_size": 128,
        "moe_intermediate_size": 32, "num_hidden_layers": 4,
        "first_k_dense_replace": 1, "moe_layer_freq": 1,
        "num_attention_heads": 4, "q_lora_rank": 24, "kv_lora_rank": 32,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "n_routed_experts": 4, "experts_held": [4, 4],
        "published": {"n_routed_experts": 16, "vocab_size": 256},
        "num_experts_per_tok": 3, "n_shared_experts": 2, "n_group": 4,
        "topk_group": 2, "topk_method": "group_limited_greedy",
        "scoring_func": "softmax", "norm_topk_prob": False,
        "routed_scaling_factor": 16, "rms_norm_eps": 1e-6,
        "rope_theta": 10000,
        "rope_scaling": {"type": "yarn", "factor": 40, "beta_fast": 32,
                         "beta_slow": 1, "mscale": 0.707,
                         "mscale_all_dim": 0.707,
                         "original_max_position_embeddings": 16},
        "max_position_embeddings": 64, "dtype": "float32",
        # weights large enough that the softmax is far from uniform, as it
        # is at the published widths: a scale or a frequency then shows
        "initializer_range": 0.15}


def load(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        pytest.skip("the catalog is not on this machine")
    with open(path) as f:
        return next(r for r in map(json.loads, f)
                    if r["name"] == "DeepSeek-V2")


def test_the_new_files_pass_the_linter():
    """Nothing the linter says is about this cell, but for one line: its
    WIDTH pattern takes `hidden` in `num_hidden_layers` for a width, where
    the contract's own example lists that key (PERF.md §7)."""
    about = [p for p in lint_manifest.lint(ROOT)
             if "deepseek" in p or "mla" in p]
    assert about == ["config deepseek-v2: reduced names "
                     "'num_hidden_layers', a width or not a name"], about


def test_configuration_is_the_published_one_but_for_its_reduced_keys():
    conf = load("configs", "deepseek-v2.json")
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(c for c in manifest["configs"] if c["name"] == "deepseek-v2")
    row = catalog_row()
    assert entry["source"] == conf["source"] == row["source_url"]
    assert entry["reduced"] == conf["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    for key, value in row["config"].items():
        if key in conf["reduced"]:
            assert conf["published"][key] == value, key
        else:
            assert conf[key] == value, key
    assert (conf["num_hidden_layers"], conf["n_routed_experts"],
            conf["vocab_size"], conf["experts_held"]) == (5, 40, 25600,
                                                          [0, 40])
    # the floors: a leading dense layer and four that follow, 8 experts or
    # more, an eighth of the vocabulary or more; no width is cut
    assert conf["num_hidden_layers"] - conf["first_k_dense_replace"] >= 4
    assert conf["n_routed_experts"] >= 8
    assert conf["vocab_size"] * 8 >= conf["published"]["vocab_size"]
    assert conf["n_routed_experts"] % (160 // conf["n_group"]) == 0
    assert len(conf["assumed"]) >= 6
    assert "5,164M parameters, 10.33 GB" in conf["deployment"]
    assert "4 chips share each layer" in conf["deployment"]


def test_cell_traffic_and_engine_are_the_issues():
    cell = load("workloads", CELL + ".json")
    assert cell["chips"] == 1 and cell["runner"] == "serve_deepseek"
    assert cell["traffic"] == "decode-backlog-deep"
    assert cell["engine"] == {
        "slot_count": 64, "max_seq_len": 6144,
        "ladder": [512, 1024, 2048, 3072, 3584], "max_new_cap": 2048,
        "steps_per_dispatch": 8, "kv_layout": "contiguous"}
    assert cell["engine"]["ladder"] == load(
        "workloads", "serve-trinity-mini-decode.json")["engine"]["ladder"]
    traffic = load("traffic", "decode-backlog-deep.json")
    long = load("traffic", "decode-backlog-long.json")
    assert traffic["arrival"] == {"process": "backlog", "depth": 96,
                                  "max_rps": 8}
    assert traffic["prompt_len"] == long["prompt_len"] == {
        "dist": "lognormal", "median": 2048, "sigma": 0.5, "min": 512,
        "max": 3584}
    assert traffic["max_new"] == {"dist": "fixed", "value": 2048}
    assert traffic["sampling"] == long["sampling"] == {
        "temperature": 0.8, "top_k": 50, "top_p": 0.9}
    assert (traffic["stagger"], traffic["block"], traffic["lead_in_s"]) == (
        64, 16, 10.0)
    # the longest prompt and its whole answer fit a slot
    assert 3584 + 2048 <= cell["engine"]["max_seq_len"]
    resolved = bench_run.resolve(CELL, ROOT)
    assert {m["name"] for m in resolved["per_layer"]} == set(
        NEW_METRICS) | {"setup.compile_s"}
    assert [m["name"] for m in resolved["end_to_end"]] == [
        "serve_tokens_per_s", "setup_s"]
    # nothing the other cells report has changed
    for other in ("serve-trinity-mini-decode", "serve-olmo-hybrid-decode"):
        names = {m["name"] for m in bench_run.resolve(other, ROOT)["per_layer"]}
        assert not names & set(NEW_METRICS)


def test_parameter_counts_against_a_hand_count():
    """149.23M an attention layer, 23.59M an expert; 5,164M in all."""
    conf = load("configs", "deepseek-v2.json")
    assert attention_parameters(conf) == (
        5120 * 1536 + 1536 * 24576 + 5120 * 576 + 512 * 32768
        + 16384 * 5120) == 149225472
    assert expert_parameters(conf) == 3 * 5120 * 1536 == 23592960
    assert latent_row_bytes(conf) == 1152
    lite = dict(conf, q_lora_rank=None)
    assert attention_parameters(lite) == (
        5120 * 24576 + 5120 * 576 + 512 * 32768 + 16384 * 5120)
    total = (5 * 149225472 + 3 * 5120 * 12288 + 4 * (2 + 40) * 23592960
             + 4 * 5120 * 160 + 2 * 5120 * 25600)
    assert round(total / 1e6) == 5164 and 10.32e9 < 2 * total < 10.34e9


def test_decode_step_bytes_against_a_hand_count():
    """64 slots at contexts of 3,200 with 36 of the 40 held experts touched:
    experts 6.79 GB, attention matrices 1.49, dense and shared MLPs 0.75,
    head 0.26, latent rows 1.18: about 10.5 GB, 12.8 ms."""
    conf = load("configs", "deepseek-v2.json")
    parts = decode_step_bytes(conf, [3200] * 64, 36.0)
    assert parts["experts"] == 4 * 36 * 23592960 * 2
    assert parts["attention_weights"] == 5 * 149225472 * 2
    assert parts["shared_experts"] == 4 * 2 * 23592960 * 2
    assert parts["dense_mlp"] == 3 * 5120 * 12288 * 2
    assert parts["head"] == 5120 * 25600 * 2
    assert parts["router"] == 4 * 5120 * 160 * 4
    assert parts["latent_rows"] == 5 * 64 * 3200 * 1152
    assert 6.79e9 < parts["experts"] < 6.80e9
    assert 1.17e9 < parts["latent_rows"] < 1.19e9
    assert parts["total"] == sum(v for k, v in parts.items() if k != "total")
    assert parts["total"] / 819e9 == pytest.approx(12.8e-3, rel=0.02)
    # the rows follow the contexts, the experts what was touched: 71 times
    # less than keys and values a head would be
    short = decode_step_bytes(conf, [100] * 4, 12.5)
    assert short["latent_rows"] == 5 * 4 * 100 * 1152
    assert short["experts"] == 4 * 12.5 * 23592960 * 2
    assert 128 * (192 + 128) * 2 / 1152 == pytest.approx(71.1, abs=0.1)
    # the step's operations stand far under its bytes at these sizes
    flops = decode_step_flops(conf, [3200] * 64)
    per_row = (5 * 149225472 + 3 * 5120 * 12288
               + 4 * (2 + 1.5) * 23592960 + 4 * 5120 * 160 + 5120 * 25600)
    assert flops["matrices"] == 2 * per_row * 64
    assert flops["core"] == 5 * 64 * 3200 * 2 * 128 * (576 + 512)
    assert 0.28e12 < flops["core"] < 0.29e12
    assert flops["total"] / 197e12 < 0.25 * parts["total"] / 819e9


def test_prefill_flops_against_a_hand_count():
    """A prompt of 2,048: 2 x 1,268M matrix parameters x 2,048 = 5.2 TFLOP,
    causal attention at 128 heads of 192 + 128 0.86, the head 0.0003."""
    conf = load("configs", "deepseek-v2.json")
    parts = prefill_flops(conf, 2048)
    matrices = (5 * 149225472 + 3 * 5120 * 12288
                + 4 * (2 + 1.5) * 23592960 + 4 * 5120 * 160)
    assert parts["matrices"] == 2 * matrices * 2048
    assert parts["attention"] == 5 * 128 * 2048 * 2048 * 320
    assert parts["head"] == 2 * 5120 * 25600
    assert 5.1e12 < parts["matrices"] < 5.3e12
    assert 0.85e12 < parts["attention"] < 0.87e12
    assert 6.0e12 < parts["total"] < 6.1e12
    # the pad is not counted: the count follows the real length
    assert prefill_flops(conf, 1300)["matrices"] == 2 * matrices * 1300
    assert prefill_flops(conf, 1300)["attention"] * 4096 ** 2 \
        == prefill_flops(conf, 4096)["attention"] * 1300 ** 2


def _collected(records, **kw):
    out = {"window": (100.0, 140.0), "wall_minus_perf": 1000.0,
           "steps_per_dispatch": 8, "sink": records, "chips": 1,
           "device_kind": "TPU v5 lite",
           "config": load("configs", "deepseek-v2.json"),
           "setup_counters": {"engine.compile_cold_ms": 1500,
                              "engine.compile_warm_ms": 500}}
    out.update(kw)
    return out


def test_readers_return_numbers_from_a_recorded_collected():
    """Twenty dispatches of 160 ms in the last 3.2 s of a window and four
    prefills, one of them cut in half by the sub-window's start."""
    conf = load("configs", "deepseek-v2.json")
    records = []
    for i in range(20):
        end = 1140.0 - 0.16 * (19 - i)
        records.append({
            "event": "serve_step", "ts": end, "steps_per_dispatch": 8,
            "occupancy": 0.95, "host_gap_ms": 4.0 + i % 2,
            "moe_touched": 120.0, "moe_touched_held": 36.0,
            "moe_max_load": 9, "contexts": [3200] * 64,
            "spans_ms": {"decode_dispatch": 10.0, "decode_fetch": 140.0,
                         "emit": 0.0}})
    prefills = [(130.0, 130.1, 2048),          # before the sub-window
                (136.9, 137.1, 3000),          # half inside
                (138.0, 138.1, 1300), (139.0, 139.06, 600)]
    run = _collected(
        records, prefills=prefills,
        steps=[(100.0 + i, 100.16 + i, 0) for i in range(30)],
        trace={"window_s": 3.0, "busy_s": 2.8, "ops": {}, "idle_gaps": {},
               "modules": {"jit_step_chunk": 2.4, "jit_prefill": 0.35}})
    got = {n: bench_run.load_module("layer_metrics", n).read(run)
           for n in NEW_METRICS + ("setup.compile_s",)}
    assert got["serve.step_ms_p50.deepseek"] == pytest.approx(20.0)
    assert got["serve.occupancy.deepseek"] == pytest.approx(95.0)
    assert got["serve.host_gap_ms_p50.deepseek"] == pytest.approx(4.5)
    assert got["device.idle_share.deepseek"] == pytest.approx(100 * 0.2 / 3)
    assert got["serve.prefill_share.deepseek"] == pytest.approx(12.5)
    # of the 40 held, not of the 160 published
    assert got["moe.experts_touched.deepseek"] == pytest.approx(90.0)
    assert got["moe.max_load.deepseek"] == pytest.approx(9.0)
    assert got["setup.compile_s"] == pytest.approx(2.0)
    need = decode_step_bytes(conf, [3200] * 64, 36.0)["total"]
    laps = 0.0
    for r in records:
        b = r["ts"]
        a = b - 0.15
        laps += max(0.0, min(b, 1140.0) - max(a, 1137.0)) / 0.15
    want = 100 * laps * 8 * need / 819e9 / 2.4
    assert got["serve.decode_bytes_roofline.deepseek"] == pytest.approx(want)
    assert 0 < want < 100
    flops = (0.5 * prefill_flops(conf, 3000)["total"]
             + prefill_flops(conf, 1300)["total"]
             + prefill_flops(conf, 600)["total"])
    want = 100 * flops / 197e12 / 0.35
    assert got["serve.prefill_flops_roofline.deepseek"] == pytest.approx(want)
    assert 0 < want < 100
    # where the step's operations outweigh its bytes the reader takes them.
    # The absorbed core sits on the ridge (2 x 128 x 1,088 operations and
    # 1,152 B a position are 1.41 ns each), so it is the rows that tip it:
    # at 4,096 live slots the matrices' FLOP bound is the larger one
    for r in records:
        r["contexts"] = [100] * 4096
    long = [100] * 4096
    assert decode_step_flops(conf, long)["total"] / 197e12 \
        > decode_step_bytes(conf, long, 36.0)["total"] / 819e9
    want = 100 * laps * 8 * decode_step_flops(conf, long)["total"] \
        / 197e12 / 2.4
    assert bench_run.load_module(
        "layer_metrics", "serve.decode_bytes_roofline.deepseek").read(
        run) == pytest.approx(want)


def test_readers_find_nothing_where_there_is_nothing_to_read():
    """Records without `moe_touched_held` (the parent's), a run with no
    `prefills`, another configuration's run, an untraced run: the new
    readers return nothing and raise nothing."""
    records = [{"event": "serve_step", "ts": 1139.0, "steps_per_dispatch": 8,
                "moe_touched": 80.0, "contexts": [100] * 16,
                "spans_ms": {"decode_dispatch": 10.0, "decode_fetch": 140.0}}]
    run = _collected(records, trace={"window_s": 3.0, "busy_s": 2.8,
                                     "ops": {}, "idle_gaps": {},
                                     "modules": {"jit_step_chunk": 2.4,
                                                 "jit_prefill": 0.3}})
    for name in ("serve.decode_bytes_roofline.deepseek",
                 "serve.prefill_flops_roofline.deepseek",
                 "moe.experts_touched.deepseek", "moe.max_load.deepseek",
                 "serve.occupancy.deepseek",
                 "serve.host_gap_ms_p50.deepseek"):
        assert bench_run.load_module("layer_metrics", name).read(run) is None
    records[0]["moe_touched_held"] = 30.0
    records[0]["moe_max_load"] = 3
    other = dict(run, config=load("configs", "trinity-mini.json"),
                 prefills=[(139.0, 139.1, 600)])
    for name in ("serve.decode_bytes_roofline.deepseek",
                 "serve.prefill_flops_roofline.deepseek",
                 "moe.experts_touched.deepseek", "moe.max_load.deepseek"):
        assert bench_run.load_module("layer_metrics", name).read(other) is None
    for name in NEW_METRICS:
        assert bench_run.load_module("layer_metrics", name).read({}) is None


def test_runner_at_the_small_size():
    """The whole runner on the CPU with one chip's share (experts 4 to 7 of
    16, a quarter of the vocabulary): weights from the seed, the greedy
    check against the reference on every rung (tokens and latent rows), the
    backlog, the counters; then every reader of the cell on what it
    collected."""
    runner = bench_run.load_module("runners", "serve_deepseek")
    traf = {"arrival": {"process": "backlog", "depth": 4, "max_rps": 400},
            "prompt_len": {"dist": "lognormal", "median": 10, "sigma": 0.5,
                           "min": 3, "max": 30},
            "max_new": {"dist": "fixed", "value": 12},
            "sampling": {"temperature": 0.8, "top_k": 50, "top_p": 0.9},
            "block": 8, "stagger": 4, "lead_in_s": 0.3}
    cell = {"runner": "serve_deepseek", "chips": 1, "trace_seconds": 0.3,
            "engine": {"slot_count": 4, "max_seq_len": 48,
                       "ladder": [8, 16, 32], "max_new_cap": 16,
                       "steps_per_dispatch": 4, "kv_layout": "contiguous"}}
    resolved = {"cell": cell, "config": TINY, "traffic": traf}
    ctx = bench_run.Run(resolved, 2**31 + 11, 1.5, True,
                        trace.Tracer(runner.ANNOTATIONS, 1))
    out = runner.run(ctx)
    assert out["correct"], out
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["end_to_end"]["serve_tokens_per_s"] > 0
    assert out["collected"]["prefills"]
    assert all(b > a and 3 <= n <= 30
               for a, b, n in out["collected"]["prefills"])
    steps = [r for r in out["collected"]["sink"]
             if r["event"] == "serve_step"]
    assert steps and all(0 <= r["moe_touched_held"] <= 4
                         and r["moe_touched_held"] <= r["moe_touched"] <= 16
                         and r["latent_bytes"] > 0 for r in steps)
    got = dict(out["collected"], trace=ctx.tracer.reduce(), chips=1,
               device_kind="TPU v5 lite", config=TINY)
    for name in ("serve.step_ms_p50.deepseek", "serve.occupancy.deepseek",
                 "serve.host_gap_ms_p50.deepseek",
                 "moe.experts_touched.deepseek", "moe.max_load.deepseek",
                 "setup.compile_s"):
        value = bench_run.load_module("layer_metrics", name).read(got)
        assert value is not None and value >= 0, name
    assert bench_run.load_module(
        "layer_metrics", "moe.experts_touched.deepseek").read(got) <= 100
    # the CPU has no device plane: the trace readers find nothing to read
    for name in ("device.idle_share.deepseek", "serve.prefill_share.deepseek",
                 "serve.decode_bytes_roofline.deepseek",
                 "serve.prefill_flops_roofline.deepseek"):
        assert bench_run.load_module("layer_metrics", name).read(got) is None


def test_a_program_without_the_model_exits_at_once():
    """The parent of the PR that brought the model: the runner's import
    fails with a message and no model is built."""
    import subprocess
    import sys

    code = ("import sys; sys.modules['paddle_tpu.models'] = type(sys)('m'); "
            "import benchmarks.runners.serve_deepseek")
    done = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          capture_output=True, text=True, timeout=120,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert done.returncode == 1
    assert "this program has no deepseek_v2 model" in done.stderr


@pytest.fixture(scope="module")
def controls():
    """Every control of benchmarks/tests/controls_deepseek_v2.py through
    the runner's own `check_greedy`, at the small size."""
    from benchmarks.tests import controls_deepseek_v2

    return controls_deepseek_v2.readings(
        TINY, controls_deepseek_v2.TINY_ENGINE, 2**31 + 27,
        load("traffic", "decode-backlog-deep.json")["sampling"])


def test_the_plain_reference_passes_the_greedy_check(controls):
    plain = controls["plain"]
    assert plain["ok"], plain
    # float32 against float32: what the slots hold agrees to rounding
    assert plain["row_worst_before_experts"] < 1e-4
    assert plain["row_median_worst_layer"] < 1e-4
    assert plain["mean_gap"] < 1e-3
    assert plain["contexts"] == [21, 29, 45] and plain["beside"] == 1


@pytest.mark.parametrize("name", [
    "float8", "latent_cache_in_float8", "scale_without_mscale",
    "plain_rope_without_yarn", "top_k_without_groups", "weights_normalised",
    "shared_expert_left_out", "values_of_the_wrong_head",
    "one_expert_layer_dropped"])
def test_a_wrong_reference_fails_the_greedy_check(controls, name):
    """By the limits the chip's cell runs under."""
    assert not controls[name]["ok"], controls[name]


def test_the_check_reads_each_measure_where_it_should(controls):
    from benchmarks.runners import serve_deepseek as runner

    # a latent cache kept in float8: the rows themselves, in every layer
    low = controls["latent_cache_in_float8"]
    assert low["row_worst_before_experts"] > runner.ROW_TOLERANCE
    assert low["row_median_worst_layer"] > runner.ROW_MEDIAN_TOLERANCE
    # plain RoPE: the rotated key of a row, from the pairs YaRN slows
    rope = controls["plain_rope_without_yarn"]
    assert rope["row_worst_before_experts"] > runner.ROW_TOLERANCE
    # the routing and the shared expert leave the dense layer's and the
    # first expert layer's rows alone and move the layers above them
    for name in ("top_k_without_groups", "weights_normalised",
                 "shared_expert_left_out"):
        c = controls[name]
        assert c["row_worst_before_experts"] < 1e-4, name
        assert c["row_median_worst_layer"] > runner.ROW_MEDIAN_TOLERANCE, name
    # the last layer's rows dropped: no row above it to move, the tokens,
    # a little each: the 75th percentile of their gaps
    last = controls["one_expert_layer_dropped"]
    assert last["row_median_worst_layer"] < 1e-4
    assert last["gap_p75"] > runner.P75_GAP_TOLERANCE
    assert controls["plain"]["gap_p75"] == 0.0
