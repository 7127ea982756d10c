"""The cell `serve-olmo-hybrid-decode`: its files against the linter and the
catalog's row, its runner rehearsed at the small size on the CPU, its readers
on what the runner collected and on a recorded `collected`, the two
rooflines' counting functions against a hand count at the published shapes,
and the controls of its check. A CPU run shows control flow and counts,
never a speed."""
import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
HERE = os.path.join(ROOT, "benchmarks")

from benchmarks import lint_manifest, run as bench_run  # noqa: E402
from benchmarks.lib import trace  # noqa: E402
from benchmarks.lib.decode_bytes_hybrid import (  # noqa: E402
    decode_step_bytes, full_mixer_parameters, linear_mixer_parameters,
    mlp_parameters, state_bytes)
from benchmarks.lib.prefill_flops_hybrid import prefill_flops  # noqa: E402

CELL = "serve-olmo-hybrid-decode"
NEW_METRICS = ("serve.step_ms_p50.olmo", "serve.occupancy.olmo",
               "serve.host_gap_ms_p50.olmo", "device.idle_share.olmo",
               "serve.prefill_share.olmo", "serve.decode_bytes_roofline.olmo",
               "serve.prefill_flops_roofline.olmo")
TINY = {"vocab_size": 256, "hidden_size": 64, "intermediate_size": 128,
        "num_hidden_layers": 8, "num_attention_heads": 4,
        "num_key_value_heads": 4, "linear_num_key_heads": 4,
        "linear_num_value_heads": 4, "linear_key_head_dim": 8,
        "linear_value_head_dim": 16, "linear_conv_kernel_dim": 4,
        "linear_allow_neg_eigval": True, "rms_norm_eps": 1e-6,
        "layer_types": (["linear_attention"] * 3 + ["full_attention"]) * 2,
        "max_position_embeddings": 64, "rope_parameters": {"rope_theta": None},
        "dtype": "float32"}


def load(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        pytest.skip("the catalog is not on this machine")
    with open(path) as f:
        return next(r for r in map(json.loads, f)
                    if r["name"] == "Olmo-Hybrid-7B")


def test_the_new_files_pass_the_linter():
    """Nothing the linter says is about this cell, but for one line: its
    WIDTH pattern takes `hidden` in `num_hidden_layers` for a width, where
    the contract's own example lists that key (PERF.md §7)."""
    about = [p for p in lint_manifest.lint(ROOT)
             if "olmo" in p or "hybrid" in p]
    assert about == ["config olmo-hybrid-7b: reduced names "
                     "'num_hidden_layers', a width or not a name"], about


def test_configuration_is_the_published_one_but_for_its_reduced_keys():
    conf = load("configs", "olmo-hybrid-7b.json")
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "olmo-hybrid-7b")
    row = catalog_row()
    assert entry["source"] == conf["source"] == row["source_url"]
    assert entry["reduced"] == conf["reduced"] == ["num_hidden_layers",
                                                   "layer_types"]
    for key, value in row["config"].items():
        if key in conf["reduced"]:
            assert key in conf["published"]
        else:
            assert conf[key] == value, key
    assert conf["published"]["num_hidden_layers"] == 32
    assert conf["layer_types"] == row["config"]["layer_types"][:12]
    assert conf["num_hidden_layers"] == len(conf["layer_types"]) == 12
    assert len(conf["assumed"]) >= 8 and conf["deployment"]
    assert "3268M parameters, 6.54 GB" in conf["deployment"]


def test_cell_traffic_and_engine_are_the_issues():
    cell = load("workloads", CELL + ".json")
    trinity = load("workloads", "serve-trinity-mini-decode.json")
    assert cell["chips"] == 1 and cell["runner"] == "serve_hybrid"
    assert cell["traffic"] == trinity["traffic"] == "decode-backlog-long"
    assert cell["engine"] == trinity["engine"] == {
        "slot_count": 16, "max_seq_len": 4096,
        "ladder": [512, 1024, 2048, 3072, 3584], "max_new_cap": 512,
        "steps_per_dispatch": 8, "kv_layout": "contiguous"}
    assert cell["trace_seconds"] == 3.0
    resolved = bench_run.resolve(CELL, ROOT)
    assert {m["name"] for m in resolved["per_layer"]} == set(
        NEW_METRICS) | {"setup.compile_s"}
    assert [m["name"] for m in resolved["end_to_end"]] == [
        "serve_tokens_per_s", "setup_s"]
    # nothing the other cells report has changed
    other = bench_run.resolve("serve-trinity-mini-decode", ROOT)
    assert not {m["name"] for m in other["per_layer"]} & set(NEW_METRICS)


def test_parameter_and_state_counts_against_a_hand_count():
    """3,268M parameters, 6.54 GB in bfloat16; 2.28 MB a state."""
    conf = load("configs", "olmo-hybrid-7b.json")
    lin = linear_mixer_parameters(conf)
    assert lin == (3840 * 11520 + 4 * 11520 + 2 * 3840 * 30
                   + 3840 * 5760 + 5760 * 3840) == 88750080
    assert full_mixer_parameters(conf) == 4 * 3840 * 3840 == 58982400
    assert mlp_parameters(conf) == 3 * 3840 * 11008 == 126812160
    matrices = (9 * lin + 3 * full_mixer_parameters(conf)
                + 12 * mlp_parameters(conf) + 2 * 3840 * 100352)
    # the model's own count, 3,268,268,508, has the norms, A_log and
    # dt_bias besides
    assert matrices == 3268147200
    assert 6.53e9 < 2 * matrices < 6.54e9
    one = state_bytes(conf)
    assert one == {"matrix": 30 * 96 * 192 * 4, "tail": 3 * 11520 * 2,
                   "total": 2280960}


def test_decode_step_bytes_against_a_hand_count():
    """16 slots at contexts of 2,800: 5.77 GB of weights, 2.06 GB of rows,
    0.33 GB of state read and 0.33 GB written: about 8.5 GB, 10.4 ms."""
    conf = load("configs", "olmo-hybrid-7b.json")
    parts = decode_step_bytes(conf, [2800] * 16)
    assert parts["linear_mixers"] == 9 * 88750080 * 2
    assert parts["full_mixers"] == 3 * 58982400 * 2
    assert parts["mlps"] == 12 * 126812160 * 2
    assert parts["head"] == 3840 * 100352 * 2
    weights = sum(parts[k] for k in ("linear_mixers", "full_mixers", "mlps",
                                     "head"))
    assert 5.76e9 < weights < 5.78e9
    # a slot reads 2,800 rows of each of three full layers, 15,360 bytes a
    # row (k and v, 30 heads of 128, bf16)
    assert parts["cache_rows"] == 16 * 2800 * 3 * 15360
    assert parts["state_read"] == parts["state_written"] == 9 * 16 * 2280960
    assert parts["total"] == weights + parts["cache_rows"] + 2 * 328458240
    assert parts["total"] / 819e9 == pytest.approx(10.4e-3, rel=0.02)
    # rows follow the contexts, state follows the live slots
    short = decode_step_bytes(conf, [100] * 4)
    assert short["cache_rows"] == 4 * 100 * 3 * 15360
    assert short["state_read"] == 9 * 4 * 2280960


def test_prefill_flops_against_a_hand_count():
    """A prompt of 2,048: 2 x 2,497.6M matrix parameters x 2,048 = 10.2
    TFLOP, causal attention 0.097, the delta rule 0.061, the head 0.0008."""
    conf = load("configs", "olmo-hybrid-7b.json")
    parts = prefill_flops(conf, 2048)
    matrices = 9 * 88750080 + 3 * 58982400 + 12 * 126812160
    assert parts["matrices"] == 2 * matrices * 2048
    assert parts["attention"] == 3 * 2 * 2048 * 2048 * 3840
    assert parts["delta_rule"] == 9 * 2048 * 30 * 6 * 96 * 192
    assert parts["head"] == 2 * 3840 * 100352
    assert 10.2e12 < parts["total"] < 10.5e12
    # the pad is not counted: the count follows the real length
    assert prefill_flops(conf, 1300)["matrices"] == 2 * matrices * 1300
    assert prefill_flops(conf, 1300)["attention"] * 4096 ** 2 \
        == prefill_flops(conf, 4096)["attention"] * 1300 ** 2


def _collected(records, **kw):
    out = {"window": (100.0, 140.0), "wall_minus_perf": 1000.0,
           "steps_per_dispatch": 8, "sink": records, "chips": 1,
           "device_kind": "TPU v5 lite",
           "config": load("configs", "olmo-hybrid-7b.json"),
           "setup_counters": {"engine.compile_cold_ms": 1500,
                              "engine.compile_warm_ms": 500}}
    out.update(kw)
    return out


def test_readers_return_numbers_from_a_recorded_collected():
    """Twenty dispatches of 160 ms in the last 3.2 s of a window and four
    prefills, one of them cut in half by the sub-window's start."""
    conf = load("configs", "olmo-hybrid-7b.json")
    records = []
    for i in range(20):
        end = 1140.0 - 0.16 * (19 - i)
        records.append({
            "event": "serve_step", "ts": end, "steps_per_dispatch": 8,
            "occupancy": 0.95, "host_gap_ms": 4.0 + i % 2,
            "state_absmax": 3.0, "contexts": [2800] * 16,
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
    assert got["serve.step_ms_p50.olmo"] == pytest.approx(20.0)
    assert got["serve.occupancy.olmo"] == pytest.approx(95.0)
    assert got["serve.host_gap_ms_p50.olmo"] == pytest.approx(4.5)
    assert got["device.idle_share.olmo"] == pytest.approx(100 * 0.2 / 3)
    assert got["serve.prefill_share.olmo"] == pytest.approx(12.5)
    assert got["setup.compile_s"] == pytest.approx(2.0)
    need = decode_step_bytes(conf, [2800] * 16)["total"]
    laps = 0.0
    for r in records:
        b = r["ts"]
        a = b - 0.15
        laps += max(0.0, min(b, 1140.0) - max(a, 1137.0)) / 0.15
    want = 100 * laps * 8 * need / 819e9 / 2.4
    assert got["serve.decode_bytes_roofline.olmo"] == pytest.approx(want)
    assert 0 < want < 100
    flops = (0.5 * prefill_flops(conf, 3000)["total"]
             + prefill_flops(conf, 1300)["total"]
             + prefill_flops(conf, 600)["total"])
    want = 100 * flops / 197e12 / 0.35
    assert got["serve.prefill_flops_roofline.olmo"] == pytest.approx(want)
    assert 0 < want < 100


def test_readers_find_nothing_where_there_is_nothing_to_read():
    """Records without `contexts`, a run with no `prefills`, another
    configuration's run, an untraced run: the new readers return nothing and
    raise nothing."""
    records = [{"event": "serve_step", "ts": 1139.0, "steps_per_dispatch": 8,
                "spans_ms": {"decode_dispatch": 10.0, "decode_fetch": 140.0}}]
    run = _collected(records, trace={"window_s": 3.0, "busy_s": 2.8,
                                     "ops": {}, "idle_gaps": {},
                                     "modules": {"jit_step_chunk": 2.4,
                                                 "jit_prefill": 0.3}})
    for name in ("serve.decode_bytes_roofline.olmo",
                 "serve.prefill_flops_roofline.olmo",
                 "serve.occupancy.olmo", "serve.host_gap_ms_p50.olmo"):
        assert bench_run.load_module("layer_metrics", name).read(run) is None
    records[0]["contexts"] = [100] * 16
    other = dict(run, config=load("configs", "trinity-mini.json"),
                 prefills=[(139.0, 139.1, 600)])
    for name in ("serve.decode_bytes_roofline.olmo",
                 "serve.prefill_flops_roofline.olmo"):
        assert bench_run.load_module("layer_metrics", name).read(other) is None
    for name in NEW_METRICS:
        assert bench_run.load_module("layer_metrics", name).read({}) is None


def test_runner_at_the_small_size():
    """The whole runner on the CPU: weights from the seed, the greedy check
    against the reference on every rung (tokens, states, tails, rows), the
    backlog, the counters; then every reader of the cell on what it
    collected."""
    runner = bench_run.load_module("runners", "serve_hybrid")
    traf = {"arrival": {"process": "backlog", "depth": 4, "max_rps": 400},
            "prompt_len": {"dist": "lognormal", "median": 10, "sigma": 0.5,
                           "min": 3, "max": 30},
            "max_new": {"dist": "fixed", "value": 12},
            "sampling": {"temperature": 0.8, "top_k": 50, "top_p": 0.9},
            "block": 8, "stagger": 4, "lead_in_s": 0.3}
    cell = {"runner": "serve_hybrid", "chips": 1, "trace_seconds": 0.3,
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
    assert all(r.get("state_absmax", 0) > 0 and r["state_bytes"] > 0
               for r in out["collected"]["sink"]
               if r["event"] == "serve_step")
    got = dict(out["collected"], trace=ctx.tracer.reduce(), chips=1,
               device_kind="TPU v5 lite", config=TINY)
    for name in ("serve.step_ms_p50.olmo", "serve.occupancy.olmo",
                 "serve.host_gap_ms_p50.olmo", "setup.compile_s"):
        value = bench_run.load_module("layer_metrics", name).read(got)
        assert value is not None and value >= 0, name
    # the CPU has no device plane: the trace readers find nothing to read
    for name in ("device.idle_share.olmo", "serve.prefill_share.olmo",
                 "serve.decode_bytes_roofline.olmo",
                 "serve.prefill_flops_roofline.olmo"):
        assert bench_run.load_module("layer_metrics", name).read(got) is None


@pytest.fixture(scope="module")
def controls():
    """Every control of benchmarks/tests/controls_olmo_hybrid.py through
    the runner's own `check_greedy`, at the small size."""
    from benchmarks.tests import controls_olmo_hybrid

    return controls_olmo_hybrid.readings(
        TINY, controls_olmo_hybrid.TINY_ENGINE, 2**31 + 27,
        load("traffic", "decode-backlog-long.json")["sampling"])


def test_the_plain_reference_passes_the_greedy_check(controls):
    plain = controls["plain"]
    assert plain["ok"], plain
    # float32 against float32: what the slots hold agrees to rounding
    for key in ("prefill_state", "prefill_tail", "first_state", "first_tail",
                "state", "tail", "row_median", "row_worst"):
        assert plain[key] < 1e-4, (key, plain[key])
    assert plain["contexts"] == [21, 29, 45]


@pytest.mark.parametrize("name", [
    "float8", "pad_not_masked", "tail_from_the_pad", "beta_not_doubled",
    "decay_after_update", "tail_off_by_one", "qk_not_normalised",
    "state_in_bfloat16", "last_layer_beta_not_doubled",
    "late_layer_decay_after_update"])
def test_a_wrong_reference_fails_the_greedy_check(controls, name):
    """By the limits the chip's cell runs under."""
    assert not controls[name]["ok"], controls[name]


def test_the_check_reads_each_measure_where_it_should(controls):
    from benchmarks.runners import serve_hybrid as runner

    # the pad not masked: the state alone (the tail is cut at the position,
    # the tokens are the positions before the pad)
    pad = controls["pad_not_masked"]
    assert pad["prefill_state"] > runner.PREFILL_STATE_TOLERANCE
    assert pad["first_state"] > runner.FIRST_STATE_TOLERANCE
    assert pad["state"] > runner.STATE_TOLERANCE
    assert pad["tail"] < 1e-4 and pad["worst_gap"] < 1e-3
    # the tail taken from the pad: the tails alone
    tail = controls["tail_from_the_pad"]
    assert tail["prefill_tail"] > runner.FIRST_TAIL_TOLERANCE
    assert tail["first_tail"] > runner.FIRST_TAIL_TOLERANCE
    assert tail["tail"] > runner.TAIL_TOLERANCE
    assert tail["first_state"] < 1e-4 and tail["state"] < 1e-4
    # the convolution off by one: the first layer's state (the reference's
    # tail is what it was handed, so that stays)
    early = controls["tail_off_by_one"]
    assert early["prefill_state"] > runner.PREFILL_STATE_TOLERANCE
    assert early["first_tail"] < 1e-4
    # a state kept in bfloat16: the first layer's state after a prefill
    # alone, where nothing hides it; every looser limit lets it pass
    low = controls["state_in_bfloat16"]
    assert low["prefill_state"] > runner.PREFILL_STATE_TOLERANCE
    # one later layer wrong alone: the first layer's measures read as the
    # plain reference's, the all-layer ones hold it out
    last = controls["last_layer_beta_not_doubled"]
    assert last["prefill_state"] < 1e-4 and last["first_state"] < 1e-4
    assert last["state"] > runner.STATE_TOLERANCE
    # but not a later layer that alone keeps its state in bfloat16: on the
    # chip that reads as the served path does, and it passes (PERF.md §7)
    hidden = controls["late_layer_state_in_bfloat16"]
    assert hidden["ok"] and 1e-4 < hidden["state"] < runner.STATE_TOLERANCE
