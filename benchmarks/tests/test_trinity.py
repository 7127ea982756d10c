"""The cell `serve-trinity-mini-decode`: its files against the linter and the
catalog's row, its runner rehearsed at the small size on the CPU, its readers
on what the runner collected and on a recorded `collected`, and the bytes a
decode step must read against a hand count. A CPU run shows control flow and
counts, never a speed."""
import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
HERE = os.path.join(ROOT, "benchmarks")

from benchmarks import lint_manifest, run as bench_run  # noqa: E402
from benchmarks.lib import trace  # noqa: E402
from benchmarks.lib.decode_bytes import decode_step_bytes  # noqa: E402

CELL = "serve-trinity-mini-decode"
NEW_METRICS = ("serve.step_ms_p50.trinity", "serve.occupancy.trinity",
               "serve.host_gap_ms_p50.trinity", "device.idle_share.trinity",
               "moe.experts_touched.trinity", "moe.max_load.trinity",
               "serve.prefill_share.trinity",
               "serve.decode_bytes_roofline.trinity")
# the catalog's row (model-configs guide, architectures.jsonl, Trinity-Mini)
PUBLISHED = {
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_size": 2048,
    "intermediate_size": 6144, "load_balance_coeff": 0.001,
    "max_position_embeddings": 131072, "moe_intermediate_size": 1024,
    "n_group": 1, "num_attention_heads": 32, "num_dense_layers": 2,
    "num_expert_groups": 1, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 32, "num_key_value_heads": 4,
    "num_limited_groups": 1, "num_shared_experts": 1, "rms_norm_eps": 1e-05,
    "rope_theta": 10000, "route_scale": 2.826, "sliding_window": 2048,
    "topk_group": 1, "vocab_size": 200192}
TINY = {"vocab_size": 256, "hidden_size": 64, "intermediate_size": 128,
        "moe_intermediate_size": 32, "num_hidden_layers": 5,
        "num_dense_layers": 1, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16,
        "layer_types": ["sliding_attention"] * 4 + ["full_attention"],
        "sliding_window": 8, "num_experts": 8, "num_experts_per_tok": 2,
        "num_shared_experts": 1, "max_position_embeddings": 64,
        "route_norm": True, "route_scale": 2.826, "rms_norm_eps": 1e-5,
        "rope_theta": 10000, "mup_enabled": True, "score_func": "sigmoid",
        "dtype": "float32", "expert_bias_std": 0.01}


def load(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def test_the_new_files_pass_the_linter():
    """Nothing the linter says is about this cell, but for one line: its
    WIDTH pattern takes `hidden` in `num_hidden_layers` for a width, where
    the contract's own example lists that key (PERF.md §7)."""
    about = [p for p in lint_manifest.lint(ROOT)
             if "trinity" in p or "afmoe" in p or "backlog-long" in p]
    assert about == ["config trinity-mini: reduced names "
                     "'num_hidden_layers', a width or not a name"], about


def test_configuration_is_the_published_one_but_for_its_reduced_keys():
    conf = load("configs", "trinity-mini.json")
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(c for c in manifest["configs"] if c["name"] == "trinity-mini")
    assert entry["reduced"] == conf["reduced"] == [
        "num_hidden_layers", "num_dense_layers", "layer_types"]
    for key, value in PUBLISHED.items():
        if key in conf["reduced"]:
            assert conf["published"][key] == value
        else:
            assert conf[key] == value, key
    assert conf["num_hidden_layers"] == len(conf["layer_types"]) == 5
    assert conf["num_dense_layers"] == 1
    assert conf["layer_types"] == ["sliding_attention"] * 4 + ["full_attention"]
    assert len(conf["assumed"]) >= 5 and conf["deployment"] and conf["source"]


def test_cell_traffic_and_engine_are_the_issues():
    cell = load("workloads", CELL + ".json")
    traf = load("traffic", cell["traffic"] + ".json")
    assert cell["chips"] == 1 and cell["runner"] == "serve_afmoe"
    assert cell["engine"] == {
        "slot_count": 16, "max_seq_len": 4096,
        "ladder": [512, 1024, 2048, 3072, 3584], "max_new_cap": 512,
        "steps_per_dispatch": 8, "kv_layout": "contiguous"}
    assert traf["arrival"]["process"] == "backlog"
    assert traf["arrival"]["depth"] == 32
    assert traf["prompt_len"] == {"dist": "lognormal", "median": 2048,
                                  "sigma": 0.5, "min": 512, "max": 3584}
    assert traf["max_new"] == {"dist": "fixed", "value": 512}
    assert traf["sampling"] == {"temperature": 0.8, "top_k": 50, "top_p": 0.9}
    assert (traf["block"], traf["stagger"], traf["lead_in_s"]) == (64, 16, 10.0)
    resolved = bench_run.resolve(CELL, ROOT)
    assert {m["name"] for m in resolved["per_layer"]} == set(
        NEW_METRICS) | {"setup.compile_s"}
    assert [m["name"] for m in resolved["end_to_end"]] == [
        "serve_tokens_per_s", "setup_s"]
    # 72% of the requests pass the window before they finish
    from benchmarks.lib import traffic

    rows = traffic.requests(traf, 2**31 + 5, 40.0, 200192, count=128)
    passed = sum(len(r["prompt"]) + r["max_new"] > 2048 for r in rows[16:])
    assert 0.65 <= passed / len(rows[16:]) <= 0.8


def test_decode_step_bytes_against_a_hand_count():
    """16 slots at contexts of 3,000, 82 experts touched: about 5.7 GB."""
    conf = load("configs", "trinity-mini.json")
    parts = decode_step_bytes(conf, [3000] * 16, 82)
    mb = 2048 * 1024 * 3 * 2                              # one expert, bf16
    assert parts["experts"] == 4 * 82 * mb == 4 * 82 * 12582912
    assert parts["head"] == 2048 * 200192 * 2
    assert parts["attention_weights"] == 5 * 27262976 * 2
    assert parts["shared_experts"] == 4 * mb
    assert parts["dense_mlp"] == 3 * 2048 * 6144 * 2
    # a slot reads 3,000 rows of the full layer and 2,048 of each of four
    # window layers, 2,048 bytes a row (k and v, 4 heads of 128, bf16)
    assert parts["cache_rows"] == 16 * (3000 + 4 * 2048) * 2048
    assert 5.6e9 < parts["total"] < 5.8e9
    assert parts["total"] / 819e9 == pytest.approx(7.0e-3, rel=0.02)
    short = decode_step_bytes(conf, [100] * 16, 82)["cache_rows"]
    assert short == 16 * 5 * 100 * 2048


def _collected(records, **kw):
    out = {"window": (100.0, 140.0), "wall_minus_perf": 1000.0,
           "steps_per_dispatch": 8, "sink": records, "chips": 1,
           "device_kind": "TPU v5 lite",
           "config": load("configs", "trinity-mini.json"),
           "setup_counters": {"engine.compile_cold_ms": 1500,
                              "engine.compile_warm_ms": 500}}
    out.update(kw)
    return out


def test_readers_return_numbers_from_a_recorded_collected():
    """Twenty dispatches of 160 ms in the last 3.2 s of a window; the traced
    sub-window is the last 3 s, so the first counts for a fifth of itself
    less than one."""
    records = []
    for i in range(20):
        end = 1140.0 - 0.16 * (19 - i)
        records.append({
            "event": "serve_step", "ts": end, "steps_per_dispatch": 8,
            "occupancy": 0.95, "host_gap_ms": 4.0 + i % 2,
            "moe_touched": 82.0, "moe_max_load": 5.0,
            "contexts": [3000] * 16,
            "spans_ms": {"decode_dispatch": 10.0, "decode_fetch": 140.0,
                         "emit": 0.0}})
    need = decode_step_bytes(load("configs", "trinity-mini.json"),
                             [3000] * 16, 82)["total"]
    run = _collected(
        records, steps=[(100.0 + i, 100.16 + i, 0) for i in range(30)],
        trace={"window_s": 3.0, "busy_s": 2.8, "ops": {}, "idle_gaps": {},
               "modules": {"jit_step_chunk": 2.4, "jit_prefill": 0.35}})
    got = {n: bench_run.load_module("layer_metrics", n).read(run)
           for n in NEW_METRICS + ("setup.compile_s",)}
    assert got["serve.step_ms_p50.trinity"] == pytest.approx(20.0)
    assert got["serve.occupancy.trinity"] == pytest.approx(95.0)
    assert got["serve.host_gap_ms_p50.trinity"] == pytest.approx(4.5)
    assert got["device.idle_share.trinity"] == pytest.approx(100 * 0.2 / 3)
    assert got["moe.experts_touched.trinity"] == pytest.approx(100 * 82 / 128)
    assert got["moe.max_load.trinity"] == pytest.approx(5.0)
    assert got["serve.prefill_share.trinity"] == pytest.approx(12.5)
    assert got["setup.compile_s"] == pytest.approx(2.0)
    # 18 whole dispatches and 0.12 / 0.15 of two more... the first two are
    # cut by the sub-window's start at 1137.0
    laps = 0.0
    for r in records:
        b = r["ts"]
        a = b - 0.15
        laps += max(0.0, min(b, 1140.0) - max(a, 1137.0)) / 0.15
    want = 100 * laps * 8 * need / 819e9 / 2.4
    assert got["serve.decode_bytes_roofline.trinity"] == pytest.approx(want)
    assert 0 < want < 100


def test_readers_find_nothing_in_a_program_without_the_counters():
    """The parent's `serve_step` records carry neither `moe_touched` nor
    `contexts`, and an untraced run has no trace: every new reader returns
    nothing and raises nothing."""
    records = [{"event": "serve_step", "ts": 1139.0, "steps_per_dispatch": 8,
                "spans_ms": {"decode_dispatch": 10.0, "decode_fetch": 140.0}}]
    run = _collected(records, trace={"window_s": 3.0, "busy_s": 2.8,
                                     "ops": {}, "idle_gaps": {},
                                     "modules": {"jit_step_chunk": 2.4}})
    for name in ("moe.experts_touched.trinity", "moe.max_load.trinity",
                 "serve.decode_bytes_roofline.trinity",
                 "serve.occupancy.trinity", "serve.host_gap_ms_p50.trinity"):
        assert bench_run.load_module("layer_metrics", name).read(run) is None
    for name in NEW_METRICS:
        assert bench_run.load_module("layer_metrics", name).read({}) is None


def test_runner_at_the_small_size():
    """The whole runner on the CPU: weights from the seed, the greedy check
    against the reference on every rung (window 8, so prefill and decode both
    pass it), the backlog, the counters; then every reader of the cell on
    what it collected."""
    runner = bench_run.load_module("runners", "serve_afmoe")
    traf = {"arrival": {"process": "backlog", "depth": 4, "max_rps": 400},
            "prompt_len": {"dist": "lognormal", "median": 10, "sigma": 0.5,
                           "min": 3, "max": 30},
            "max_new": {"dist": "fixed", "value": 12},
            "sampling": {"temperature": 0.8, "top_k": 50, "top_p": 0.9},
            "block": 8, "stagger": 4, "lead_in_s": 0.3}
    cell = {"runner": "serve_afmoe", "chips": 1, "trace_seconds": 0.3,
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
    got = dict(out["collected"], trace=ctx.tracer.reduce(), chips=1,
               device_kind="TPU v5 lite", config=TINY)
    for name in ("serve.step_ms_p50.trinity", "serve.occupancy.trinity",
                 "serve.host_gap_ms_p50.trinity",
                 "moe.experts_touched.trinity", "moe.max_load.trinity",
                 "setup.compile_s"):
        value = bench_run.load_module("layer_metrics", name).read(got)
        assert value is not None and value >= 0, name
    touched = bench_run.load_module(
        "layer_metrics", "moe.experts_touched.trinity").read(got)
    assert 25.0 <= touched <= 100.0        # 4 rows x top-2 of 8 experts
    # the CPU has no device plane: the trace readers find nothing to read
    for name in ("device.idle_share.trinity", "serve.prefill_share.trinity",
                 "serve.decode_bytes_roofline.trinity"):
        assert bench_run.load_module("layer_metrics", name).read(got) is None


@pytest.fixture(scope="module")
def controls():
    """Every control of benchmarks/tests/controls_afmoe.py through the
    runner's own `check_greedy`, at the small size."""
    from benchmarks.tests import controls_afmoe

    return controls_afmoe.readings(TINY, controls_afmoe.TINY_ENGINE,
                                   2**31 + 27)


def test_the_plain_reference_passes_the_greedy_check(controls):
    plain = controls["plain"]
    assert plain["ok"], plain
    # float32 against float32: the rows of rings and cache agree to rounding
    assert plain["row_worst_before_experts"] < 1e-5
    assert plain["row_median_worst_layer"] < 1e-5
    assert plain["contexts"] == [21, 29, 45]          # window 8: all pass it


@pytest.mark.parametrize("name", [
    "float8", "one_row_from_elsewhere", "window_off_by_one",
    "rope_on_the_full_layer", "gate_dropped", "qk_norm_dropped",
    "route_scale_dropped", "key_head_by_modulo"])
def test_a_wrong_reference_fails_the_greedy_check(controls, name):
    """By the limits the chip's cell runs under. (The bias used as a weight
    moves less than they allow, here as on the chip: tests/test_afmoe.py
    sees it in float32 at 1e-4.)"""
    assert not controls[name]["ok"], controls[name]


def test_the_check_reads_each_measure_where_it_should(controls):
    from benchmarks.runners import serve_afmoe as runner

    # one token from another row: the tokens' worst gap alone
    planted = controls["one_row_from_elsewhere"]
    assert planted["worst_gap"] > runner.GAP_TOLERANCE
    assert planted["row_median_worst_layer"] < 1e-5
    # RoPE where it does not belong: the full layer's rows alone
    rope = controls["rope_on_the_full_layer"]
    assert rope["row_median_worst_layer"] > runner.ROW_MEDIAN_TOLERANCE
    assert rope["row_worst_before_experts"] < 1e-5
    # a wrong window edge: already in the rows under the first expert layer
    edge = controls["window_off_by_one"]
    assert edge["row_worst_before_experts"] > runner.ROW_TOLERANCE
