"""The `afmoe` decoder (models/afmoe.py), its expert layer
(nn.RoutedExperts) and the engine's per-layer state interface
(serving/kv_state.py), against the plain reference (tests/reference_afmoe.py)
at a small size on the CPU: hidden 64, 4 query / 2 key heads of 16, window 8,
8 experts top-2 + 1 shared, 1 dense + 4 expert layers in the pattern
S,S,S,S,F, vocabulary 256, float32, seeded random weights.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import reference_afmoe as ref
from paddle_tpu import nn
from paddle_tpu.models import (AfmoeForCausalLM, GPTConfig,
                               GPTForPretraining, afmoe_tiny, gpt_tiny)
from paddle_tpu.models.afmoe import AfmoeMoE
from paddle_tpu.serving import ServingEngine, kv_state

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 1e-4          # f32 against f32: the model's logits and the reference's


def ref_config(cfg) -> dict:
    """The reference reads the published keys; the model's config has them
    as attributes."""
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "rms_norm_eps", "rope_theta", "sliding_window",
            "num_experts", "num_experts_per_tok", "num_shared_experts",
            "route_norm", "route_scale", "num_hidden_layers",
            "num_dense_layers", "layer_types", "mup_enabled")
    return {k: getattr(cfg, k) for k in keys}


def state_of(model) -> dict:
    return {k: v._data for k, v in model.state_dict(
        include_non_persistable_buffer=True).items()}


@pytest.fixture(scope="module")
def tiny():
    paddle.seed(3)
    model = AfmoeForCausalLM(afmoe_tiny())
    model.eval()
    return model, state_of(model), ref_config(model.config)


def _ids(n, seed=0, vocab=256):
    return np.random.default_rng(seed).integers(0, vocab, (n,), dtype=np.int64)


# ------------------------------------------------------ model vs reference
def test_logits_match_reference_and_routing_sets_are_equal(tiny, monkeypatch):
    model, state, rcfg = tiny
    chosen = []
    route = nn.RoutedExperts.route

    def recording(self, m):
        sel, w = route(self, m)
        chosen.append(np.sort(np.asarray(sel), axis=-1))
        return sel, w

    monkeypatch.setattr(nn.RoutedExperts, "route", recording)
    ids = _ids(24)                      # three windows long
    got = model(paddle.to_tensor(ids[None]))._data[0]
    hidden, infos = ref.hidden_states(state, jnp.asarray(ids), rcfg)
    want = ref.head(state, hidden, rcfg)
    assert float(jnp.abs(got - want).max()) <= TOL
    sels = [np.sort(np.asarray(i["sel"]), axis=-1) for i in infos if "sel" in i]
    assert len(sels) == len(chosen) == 4
    for mine, theirs in zip(chosen, sels):
        assert (mine == theirs).all()


@pytest.mark.parametrize("block", [5, 8])
def test_prefill_attention_in_query_blocks(tiny, monkeypatch, block):
    """Above 512 tokens the prefill attends a block of queries at a time
    against the keys it can see; with the block cut to 5 or 8 (the window),
    24 tokens take several blocks with cut key ranges, and nothing moves."""
    from paddle_tpu.ops import slot_attention

    model, state, rcfg = tiny
    monkeypatch.setattr(slot_attention, "QUERY_BLOCK", block)
    ids = _ids(24, seed=4)
    got = model(paddle.to_tensor(ids[None]))._data[0]
    assert float(jnp.abs(got - ref.logits(state, jnp.asarray(ids), rcfg)
                         ).max()) <= TOL


def test_published_configuration_builds_its_cache_spec():
    """The benchmark's configuration file through `AfmoeConfig.from_dict`:
    the published widths, and 0.40 GB of cache at 16 slots x 4,096."""
    import json

    from paddle_tpu.models import AfmoeConfig
    from paddle_tpu.models.afmoe import AfmoeForCausalLM as Model

    with open(os.path.join(REPO, "benchmarks", "configs",
                           "trinity-mini.json")) as f:
        cfg = AfmoeConfig.from_dict(json.load(f))
    assert (cfg.hidden_size, cfg.num_attention_heads, cfg.num_key_value_heads,
            cfg.head_dim, cfg.num_experts, cfg.num_experts_per_tok,
            cfg.sliding_window, cfg.vocab_size) == (
        2048, 32, 4, 128, 128, 8, 2048, 200192)
    assert (cfg.num_layers, cfg.num_dense_layers, cfg.dtype) == (
        5, 1, "bfloat16")
    assert cfg.max_seq_len == 131072 and cfg.experts_held == (0, 128)
    spec = Model.kv_cache_spec(type("M", (), {"config": cfg})(), 4096)
    assert [(s.kind, s.rows) for s in spec] == [("window", 2048)] * 4 + [
        ("full", 4096)]
    rows = sum(s.rows * s.kv_heads * s.head_dim for s in spec)
    assert 16 * rows * 2 * 2 == 402653184                  # 0.40 GB in bf16


def _with_wider_window(s, layer_type, window):
    return WINDOW_MASK(s, layer_type, window + 1)


def _bias_weighs(scores, sel, bias, cfg):
    return ROUTE_WEIGHTS(scores + bias, sel, bias, cfg)


def _no_route_scale(scores, sel, bias, cfg):
    return ROUTE_WEIGHTS(scores, sel, bias, dict(cfg, route_scale=1.0))


WINDOW_MASK, ROUTE_WEIGHTS = ref.window_mask, ref.route_weights
MUTATIONS = {
    "window_off_by_one": ("window_mask", _with_wider_window),
    "rope_on_the_full_layer": ("uses_rope", lambda layer_type: True),
    "gate_dropped": ("apply_gate", lambda o, g: o),
    "qk_norm_dropped": ("qk_norm", lambda x, w, eps: x),
    "bias_used_as_a_weight": ("route_weights", _bias_weighs),
    "route_scale_dropped": ("route_weights", _no_route_scale),
    "key_head_by_modulo": ("kv_head_of", lambda i, nh, kvh: i % kvh),
}


@pytest.mark.parametrize("name", sorted(MUTATIONS))
def test_a_wrong_reference_fails_the_comparison(tiny, monkeypatch, name):
    """The comparison is tight enough to see each of these: with the piece
    replaced the reference departs from the model by more than TOL."""
    model, state, rcfg = tiny
    ids = _ids(24)
    got = model(paddle.to_tensor(ids[None]))._data[0]
    assert float(jnp.abs(got - ref.logits(state, jnp.asarray(ids), rcfg)
                         ).max()) <= TOL
    piece, wrong = MUTATIONS[name]
    monkeypatch.setattr(ref, piece, wrong)
    off = float(jnp.abs(got - ref.logits(state, jnp.asarray(ids), rcfg)).max())
    assert off > 10 * TOL, (name, off)


# ------------------------------------------------------- the expert layer
def _moe_state(layer) -> dict:
    return {k: v._data for k, v in layer.state_dict(
        include_non_persistable_buffer=True).items()}


def test_dropless_under_skew(tiny):
    """All rows to one pair of experts: every row is computed, none dropped."""
    model, _, rcfg = tiny
    paddle.seed(11)
    layer = AfmoeMoE(model.config)
    bias = np.zeros(8, np.float32)
    bias[[2, 5]] = 10.0                        # the bias chooses
    layer.expert_bias._data = jnp.asarray(bias)
    m = jax.random.normal(jax.random.key(1), (40, 64), jnp.float32)
    out, touched, max_load = layer(m)
    want, sel, _ = ref.moe(_moe_state(layer), m, rcfg)
    assert set(np.asarray(sel).ravel()) == {2, 5}
    assert float(touched) == 2.0 and int(max_load) == 40
    assert float(jnp.abs(out - want).max()) <= TOL
    # ... and it does not weigh: the weights are the unbiased scores'
    layer.expert_bias._data = jnp.asarray(bias * 2)
    again, _, _ = layer(m)
    assert float(jnp.abs(again - out).max()) <= 1e-6


def test_shares_add_up_to_the_uncut_layer(tiny):
    """The guide's share test: the parts that the shares (0,2) (2,2) (4,2)
    (6,2) give, the shared expert counted once, add up to what the uncut
    reference gives for the whole layer; and each part is what the reference
    gives for the same share."""
    model, _, rcfg = tiny
    paddle.seed(5)
    whole = AfmoeMoE(model.config)
    full = _moe_state(whole)
    m = jax.random.normal(jax.random.key(2), (33, 64), jnp.float32)
    want, _, _ = ref.moe(full, m, rcfg)
    total = whole.shared_experts(m)
    for first in (0, 2, 4, 6):
        part = AfmoeMoE(afmoe_tiny(experts_held=(first, 2)))
        for k, t in part.state_dict(
                include_non_persistable_buffer=True).items():
            src = full[k]
            t._data = src[first:first + 2] if k.startswith("experts.") else src
        assert part.experts.w_gate._data.shape[0] == 2
        mine, _, _ = part.routed(m)
        theirs, _, _ = ref.moe(full, m, rcfg, experts=(first, 2),
                               shared=False)
        assert float(jnp.abs(mine - theirs).max()) <= TOL
        cut, _, _ = ref.moe(_moe_state(part), m, rcfg, shared=False,
                            base=first)
        assert float(jnp.abs(mine - cut).max()) <= TOL
        total = total + mine
    assert float(jnp.abs(total - want).max()) <= TOL
    with pytest.raises(ValueError, match="experts held"):
        AfmoeMoE(afmoe_tiny(experts_held=(6, 4)))


# ------------------------------------------ through the serving engine
def _engine(model, **kw):
    args = dict(slot_count=3, ladder=(4, 16, 32), max_seq_len=48,
                max_new_cap=16, steps_per_dispatch=4)
    args.update(kw)
    return ServingEngine(model, **args)


def _worst_gap(state, rcfg, reqs):
    """Each served greedy token against the reference's full forward pass
    over the same prefix: how far under the reference's best logit it lies,
    as a share of that position's (max - mean) spread."""
    worst = 0.0
    for r in reqs:
        out = r.output_ids()
        rows = np.asarray(ref.logits(state, jnp.asarray(out), rcfg))
        for j, tok in enumerate(r.tokens):
            row = rows[len(r.prompt_ids) - 1 + j]
            worst = max(worst, float((row.max() - row[tok])
                                     / (row.max() - row.mean())))
    return worst


def test_prefill_then_decode_match_the_reference(tiny):
    """Greedy requests through submit/step: prompts shorter than the window
    of 8 whose context passes it during decode, prompts longer than it (the
    ring is filled by prefill), three slots at different depths, a slot
    reused; every token is the reference's choice over the same prefix."""
    model, state, rcfg = tiny
    eng = _engine(model)
    prompts = [_ids(n, seed=n) for n in (3, 13, 30, 7, 16, 2)]
    reqs = [eng.submit(p, max_new_tokens=new, temperature=0.0)
            for p, new in zip(prompts, (16, 12, 16, 9, 16, 16))]
    eng.run()
    assert all(r.done and r.outcome == "length" for r in reqs)
    assert [len(r.tokens) for r in reqs] == [16, 12, 16, 9, 16, 16]
    assert _worst_gap(state, rcfg, reqs) <= 1e-3
    assert all(0 <= t < 256 for r in reqs for t in r.tokens)


def test_cache_holds_window_rows_on_window_layers(tiny):
    model, _, _ = tiny
    eng = _engine(model)
    spec = kv_state.spec_of(model, 48)
    assert [s.kind for s in spec] == ["window"] * 4 + ["full"]
    assert [s.rows for s in spec] == [8, 8, 8, 8, 48]
    assert all((s.kv_heads, s.head_dim) == (2, 16) for s in spec)
    assert [k.shape for k in eng._kcs] == [(3, 8, 2, 16)] * 4 + [(3, 48, 2, 16)]
    # stored with the head size padded to the chip's 128 lanes (ISSUE 34)
    assert [k.shape for k in eng.slot_cache.k_stored] == (
        [(3, 8, 2, 128)] * 4 + [(3, 48, 2, 128)])
    per_row = 2 * 2 * 128 * 4                      # k and v, f32
    assert eng.kv_cache_bytes() == 3 * (4 * 8 + 48) * per_row
    assert eng.stats()["kv_cache_bytes"] == eng.kv_cache_bytes()


def test_the_full_layer_asks_the_door_and_keeps_the_plain_core(
        tiny, monkeypatch):
    """A decode step's full layer goes through ops/slot_attention's door,
    which refuses its two query heads a key head even where kernels compile
    and the rows are whole tiles: `attn.calls.slot` counts the step,
    `attn.calls.slot_kernel` does not. A sliding layer's ring never asks."""
    from paddle_tpu.nn.kv_cache import RingKV, SlotKV
    from paddle_tpu.observability import metrics
    from paddle_tpu.ops.pallas import _common

    model, _, _ = tiny
    monkeypatch.setattr(_common, "_target", lambda: "interpret")

    def count():
        return [metrics.default_registry().counter("attn.calls." + f).value
                for f in ("slot", "slot_kernel")]

    a = jax.random.normal(jax.random.key(0), (3, 1, 64))
    offset = jnp.asarray([2, 5, 9], jnp.int32)
    layers = model.model.layers
    before = count()
    layers[4].self_attn(a, cache=SlotKV(jnp.zeros((3, 64, 8, 128)),
                                        jnp.zeros((3, 64, 8, 128)), offset))
    assert count() == [before[0] + 1, before[1]]
    layers[0].self_attn(a, cache=RingKV(jnp.zeros((3, 8, 8, 128)),
                                        jnp.zeros((3, 8, 8, 128)), offset))
    assert count() == [before[0] + 1, before[1]]


def test_what_window_layers_cannot_do_is_refused_by_name(tiny):
    model, _, _ = tiny
    with pytest.raises(ValueError, match="paged.*window"):
        _engine(model, kv_layout="paged")
    paddle.seed(0)
    draft = GPTForPretraining(GPTConfig(
        vocab_size=256, hidden_size=32, num_layers=1, num_heads=2,
        max_seq_len=64))
    with pytest.raises(ValueError, match="speculative.*ring"):
        _engine(model, draft_model=draft)
    with pytest.raises(ValueError, match="speculate_k.*ring"):
        _engine(model).submit([1, 2, 3], speculate_k=2)


def test_weights_are_held_once_in_the_serving_dtype():
    """A bf16 model is drawn straight into bf16 (the router stays f32), and
    the engine's snapshot is the model's own arrays, not copies."""
    paddle.seed(1)
    model = AfmoeForCausalLM(afmoe_tiny(dtype="bfloat16"))
    for name, t in model.state_dict().items():
        f32 = name.endswith(("router.weight", "expert_bias"))
        assert t._data.dtype == (jnp.float32 if f32 else jnp.bfloat16), name
    eng = _engine(model)
    assert eng._cache_dtype == jnp.bfloat16
    for name, t in model.state_dict().items():
        assert eng._params[name] is t._data, name
    with paddle.amp.auto_cast(dtype="bfloat16"):
        eng.refresh_params()
    kept = [n for n, t in model.state_dict().items()
            if eng._params[n] is t._data]
    assert len(kept) == len(eng._params) - 4       # all but the four routers
    req = eng.submit(_ids(5), max_new_tokens=6)
    eng.run()
    assert req.done and len(req.tokens) == 6


def test_gpt_declares_full_layers_and_keeps_its_cache():
    """GPT-2 through the state interface: `full` x L, the bytes it had."""
    paddle.seed(0)
    cfg = gpt_tiny()
    model = GPTForPretraining(cfg)
    spec = kv_state.spec_of(model, 64)
    assert spec == [kv_state.KVLayerSpec("full", 64, 4, 32)] * 2
    eng = ServingEngine(model, slot_count=2, ladder=(8, 16), max_seq_len=64,
                        max_new_cap=8)
    # stored [2, 64, 4, 128]: the head size padded 32 -> 128 (ISSUE 34)
    assert eng.kv_cache_bytes() == 2 * 2 * (2 * 64 * 4 * 128) * 4
    layer, prefix = model.serving_backbone()
    assert layer is model.gpt and prefix == "gpt."
    assert model.serving_step_stats == {}


def test_scatter_prefill_fills_the_ring():
    """A prompt longer than the window leaves its last `rows` positions in
    the ring, position p in row p % rows."""
    layer = kv_state.KVLayerSpec("window", 8, 1, 1)
    local = jnp.arange(32, dtype=jnp.float32).reshape(1, 32, 1, 1)
    big = jnp.full((2, 8, 1, 1), -1.0)
    for plen in (3, 8, 9, 21, 32):
        out = kv_state.scatter_prefill(layer, big, local, jnp.int32(1),
                                       jnp.int32(plen))
        ring = np.asarray(out[1, :, 0, 0])
        for p in range(max(0, plen - 8), plen):
            assert ring[p % 8] == p, (plen, p, ring)
        assert (np.asarray(out[0]) == -1).all()


def test_the_two_copies_of_the_reference_are_one_text():
    with open(os.path.join(REPO, "tests", "reference_afmoe.py")) as a, \
            open(os.path.join(REPO, "benchmarks", "lib",
                              "reference_afmoe.py")) as b:
        assert a.read() == b.read()
