"""The `deepseek_v2` decoder (models/deepseek_v2.py), its two attention cores
(ops/latent_attention.py), the group-limited softmax router of
nn.RoutedExperts and the slot cache's `latent` kind (nn/kv_cache.py,
serving/kv_state.py), against the plain reference
(tests/reference_deepseek_v2.py) at a small size on the CPU: hidden 64, 4
heads with d_n 16, d_r 8, d_v 16, low-rank queries 24, latent 32, 1 dense +
3 expert layers, 16 experts in 4 groups, top 3 of 2 groups, 2 shared,
vocabulary 256, float32, seeded random weights.
"""
import json
import math
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import reference_deepseek_v2 as ref
from paddle_tpu import nn
from paddle_tpu.core import monitor
from paddle_tpu.models import (DeepseekV2Config, DeepseekV2ForCausalLM,
                               GPTConfig, GPTForPretraining, afmoe_tiny,
                               deepseek_v2_tiny)
from paddle_tpu.models import deepseek_v2 as dsv2
from paddle_tpu.models.deepseek_v2 import DeepseekV2MoE
from paddle_tpu.nn.kv_cache import (ChunkLatent, LatentLayerSpec, SlotLatent,
                                    latent_width)
from paddle_tpu.observability import device_trace, metrics
from paddle_tpu.ops import latent_attention
from paddle_tpu.ops.pallas import latent_decode
from paddle_tpu.serving import ServingEngine, kv_state

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 1e-4          # f32 against f32: the model's logits and the reference's
REF_KEYS = ("hidden_size", "num_attention_heads", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim", "kv_lora_rank", "q_lora_rank",
            "rms_norm_eps", "rope_theta", "rope_scaling", "n_routed_experts",
            "num_experts_per_tok", "n_shared_experts", "n_group",
            "topk_group", "topk_method", "scoring_func", "norm_topk_prob",
            "routed_scaling_factor", "num_hidden_layers",
            "first_k_dense_replace")


def ref_config(cfg) -> dict:
    """The reference reads the published keys; the model's config has them
    as attributes."""
    return {k: getattr(cfg, k) for k in REF_KEYS}


def state_of(model) -> dict:
    return {k: v._data for k, v in model.state_dict(
        include_non_persistable_buffer=True).items()}


def _build(**kw):
    paddle.seed(3)
    model = DeepseekV2ForCausalLM(deepseek_v2_tiny(**kw))
    model.eval()
    return model, state_of(model), ref_config(model.config)


@pytest.fixture(scope="module")
def tiny():
    return _build()


def _ids(n, seed=0, vocab=256):
    return np.random.default_rng(seed).integers(0, vocab, (n,), dtype=np.int64)


def _published() -> dict:
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "deepseek-v2.json")) as f:
        return json.load(f)


# ---------------------------------------------- 1. model vs the reference
@pytest.mark.parametrize("q_lora_rank", [24, None])
def test_logits_match_reference_and_routing_sets_are_equal(
        monkeypatch, q_lora_rank):
    """With the low-rank queries and without them (the Lite sibling's
    branch), and the experts each token was sent to are the reference's."""
    model, state, rcfg = _build(q_lora_rank=q_lora_rank)
    assert ("model.layers.0.self_attn.q_proj.weight" in state) == (
        q_lora_rank is None)
    chosen = []
    route = nn.RoutedExperts.route

    def recording(self, m):
        sel, w = route(self, m)
        chosen.append(np.sort(np.asarray(sel), axis=-1))
        return sel, w

    monkeypatch.setattr(nn.RoutedExperts, "route", recording)
    ids = _ids(24)
    got = model(paddle.to_tensor(ids[None]))._data[0]
    hidden, infos = ref.hidden_states(state, jnp.asarray(ids), rcfg)
    assert float(jnp.abs(got - ref.head(state, hidden, rcfg)).max()) <= TOL
    sels = [np.sort(np.asarray(i["sel"]), axis=-1) for i in infos if "sel" in i]
    assert len(sels) == len(chosen) == 3
    for mine, theirs in zip(chosen, sels):
        assert (mine == theirs).all()


def _no_mscale(cfg):
    return 1.0 / math.sqrt(cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"])


def _plain_rope(cfg):
    return INV_FREQ(dict(cfg, rope_scaling=None))


def _normalised(scores, sel, cfg):
    return ROUTE_WEIGHTS(scores, sel, dict(cfg, norm_topk_prob=True))


INV_FREQ, ROUTE_WEIGHTS = ref.inv_freq, ref.route_weights
MUTATIONS = {
    "scale_without_mscale": ("softmax_scale", _no_mscale),
    "plain_rope_without_yarn": ("inv_freq", _plain_rope),
    "top_k_of_all_without_groups": ("group_limited", lambda s, cfg: s),
    "weights_normalised": ("route_weights", _normalised),
    "shared_expert_left_out": ("shared_expert", lambda p, m: 0.0),
    "values_of_the_wrong_head": ("value_head_of", lambda i: (i + 1) % 4),
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


# ------------------------------------- 2. the absorbed form vs the expanded
@pytest.mark.parametrize("s", [1, 2, 13])
def test_absorbed_form_is_the_expanded_form(s, monkeypatch):
    """The same inputs through both cores, the absorbed one against rows
    stored wider than they are used (zeros behind), at lengths 1, 2 and one
    that is not a multiple of the query block."""
    monkeypatch.setattr(latent_attention, "QUERY_BLOCK", 4)
    b, h, dn, dr, dv, r = 2, 4, 16, 8, 16, 32
    keys = jax.random.split(jax.random.key(s), 5)
    q_n = jax.random.normal(keys[0], (b, s, h, dn))
    q_r = jax.random.normal(keys[1], (b, s, h, dr))
    n = jax.random.normal(keys[2], (b, s, r))
    k_r = jax.random.normal(keys[3], (b, s, dr))
    w = jax.random.normal(keys[4], (r, h, dn + dv)) * 0.2
    kv = jnp.einsum("bsr,rhd->bshd", n, w)
    want = latent_attention.expanded(q_n, q_r, kv[..., :dn], k_r,
                                     kv[..., dn:], 0.3)
    rows = jnp.pad(jnp.concatenate([n, k_r], -1), [(0, 0), (0, 0), (0, 24)])
    q_l = jnp.einsum("bshd,rhd->bshr", q_n, w[..., :dn])
    mask = jnp.arange(s)[None, None, :] <= jnp.arange(s)[None, :, None]
    o_l = latent_attention.absorbed(q_l, q_r, rows, mask, 0.3)
    assert o_l.shape == (b, s, h, r)
    got = jnp.einsum("bshr,rhd->bshd", o_l, w[..., dn:])
    assert float(jnp.abs(got - want).max()) <= 1e-5


def test_calls_are_counted_by_form(tiny, monkeypatch):
    model, _, _ = tiny
    reg = metrics.default_registry()

    def count(form):
        return reg.counter("mla.calls." + form).value

    before = count("expanded"), count("absorbed"), count("absorbed_kernel")
    model(paddle.to_tensor(_ids(5)[None]))
    assert (count("expanded"), count("absorbed")) == (before[0] + 4,
                                                      before[1])
    # a chunk behind rows that are held (here none yet, but not `fresh`)
    cache = ChunkLatent(jnp.zeros((1, 8, 40)), jnp.int32(0))
    model.model.layers[0].self_attn(jnp.zeros((1, 1, 64)), cache=cache)
    assert count("absorbed") == before[1] + 1
    # one position a slot of a slot cache is the absorbed form too; that it
    # took the kernel is counted beside, and the CPU does not take it unasked
    slots = SlotLatent(jnp.zeros((2, 16, 128)), jnp.asarray([3, 7], jnp.int32))
    model.model.layers[0].self_attn(jnp.zeros((2, 1, 64)), cache=slots)
    assert (count("absorbed"), count("absorbed_kernel")) == (before[1] + 2,
                                                             before[2])
    monkeypatch.setattr(latent_decode, "_target", lambda: "interpret")
    monkeypatch.setattr(latent_decode, "BLOCK_ROWS", 16)
    model.model.layers[0].self_attn(jnp.zeros((2, 1, 64)), cache=slots)
    assert (count("absorbed"), count("absorbed_kernel")) == (before[1] + 3,
                                                             before[2] + 1)


# ----------------------------------------------------- 3. YaRN by hand
def test_yarn_numbers_at_the_published_size():
    cfg = _published()
    c = DeepseekV2Config.from_dict(cfg)
    scaling = cfg["rope_scaling"]
    assert dsv2.yarn_correction_range(64, 10000.0, scaling) == (10, 23)
    assert dsv2.yarn_mscale(40, 0.707) == pytest.approx(1.26080, abs=1e-5)
    assert c.softmax_scale == pytest.approx(0.11472, abs=1e-5)
    assert c.softmax_scale == pytest.approx(
        192 ** -0.5 * (0.1 * 0.707 * math.log(40) + 1) ** 2)
    assert c.rope_amplitude == 1.0
    inv = dsv2.rope_inv_freq(64, 10000.0, scaling)
    f = [10000.0 ** (-2 * i / 64) for i in range(32)]
    want = [f[i] if i <= 10 else f[i] / 40 if i >= 23
            else f[i] * (1 - (i - 10) / 13) + f[i] / 40 * (i - 10) / 13
            for i in range(32)]
    assert np.allclose(inv, want, rtol=1e-6)
    assert inv[0] == 1.0 and inv[31] == pytest.approx(f[31] / 40)
    # the reference computes its own
    rcfg = dict(cfg, n_routed_experts=160)
    assert np.allclose(np.asarray(ref.inv_freq(rcfg)), want, rtol=1e-5)
    assert ref.softmax_scale(rcfg) == pytest.approx(c.softmax_scale)
    assert np.allclose(dsv2.rope_inv_freq(64, 10000.0), f, rtol=1e-6)


# ------------------------------------------------------- 4. the router
def _router(**kw):
    paddle.seed(0)
    args = dict(hidden_size=8, expert_width=4, num_experts=8, top_k=2,
                route_norm=False, route_scale=16.0, score_func="softmax",
                n_group=4, topk_group=2)
    args.update(kw)
    layer = nn.RoutedExperts(**args)
    # the router reads its input's first 8 values as the experts' logits
    layer.router.weight._data = jnp.eye(8, dtype=jnp.float32)
    return layer


def test_group_limited_choice_by_hand():
    """Groups {0,1} {2,3} {4,5} {6,7}. Logits put the two best experts over
    all, 0 and 1, in ONE group; the best groups are that one and {4,5}, so
    the choice is 0 then 1 all the same; in the second row the best two
    experts, 2 and 6, lie in two groups that both stay; in the third the
    second best over all (3) lies in a group whose best (also 3) ranks third
    among the groups' bests... so it is masked and 7 is taken."""
    layer = _router(top_k=2, topk_group=2)
    logits = jnp.asarray([[5.0, 4.0, 0.0, 0.0, 1.0, 0.0, 0.0, 0.0],
                          [0.0, 0.0, 5.0, 0.0, 0.0, 0.0, 4.0, 0.0],
                          [9.0, 0.0, 0.0, 6.0, 0.0, 0.0, 6.5, 5.0]])
    sel, w = layer.route(logits)
    assert np.asarray(sel).tolist() == [[0, 1], [2, 6], [0, 6]]
    scores = np.asarray(jax.nn.softmax(logits, axis=-1))
    assert np.allclose(np.asarray(w), 16 * np.take_along_axis(
        scores, np.asarray(sel), axis=-1), rtol=1e-6)
    # one group kept: the best experts over all groups are NOT the choice
    one = _router(top_k=2, topk_group=1)
    sel, w = one.route(logits)
    assert np.asarray(sel).tolist() == [[0, 1], [2, 3], [0, 1]]
    assert np.allclose(np.asarray(w)[1], 16 * scores[1, [2, 3]], rtol=1e-6)
    # no groups: the best of all
    sel, _ = _router(n_group=1, topk_group=1).route(logits)
    assert np.asarray(sel).tolist() == [[0, 1], [2, 6], [0, 6]]
    flat = _router(n_group=1, topk_group=1)
    sel, _ = flat.route(jnp.asarray([[9.0, 0, 0, 6.0, 0, 0, 5.0, 5.5]]))
    assert np.asarray(sel).tolist() == [[0, 3]]
    # normalised weights sum to the scale; sigmoid scores are today's rule
    norm = _router(route_norm=True, route_scale=1.0)
    assert np.allclose(np.asarray(norm.route(logits)[1]).sum(-1), 1.0)
    sig = _router(score_func="sigmoid", n_group=1, topk_group=1)
    _, w = sig.route(logits)
    assert np.allclose(np.asarray(w)[0], 16 * np.asarray(
        jax.nn.sigmoid(logits[0, :2])), rtol=1e-6)


def test_router_arguments_are_checked_by_name():
    with pytest.raises(ValueError, match="score_func 'tanh'"):
        _router(score_func="tanh")
    with pytest.raises(ValueError, match="8 experts in 3 groups"):
        _router(n_group=3)
    with pytest.raises(ValueError, match="top_k 3 of 1 groups of 2"):
        _router(top_k=3, topk_group=1)
    with pytest.raises(ValueError, match="topk_method 'noaux_tc'"):
        deepseek_v2_tiny(topk_method="noaux_tc")
    with pytest.raises(ValueError, match="rope_scaling.type 'linear'"):
        deepseek_v2_tiny(rope_scaling={"type": "linear", "factor": 2})
    assert deepseek_v2_tiny(topk_method="greedy").n_group == 1
    assert deepseek_v2_tiny(rope_scaling=None).softmax_scale == 24 ** -0.5


def test_touched_held_counts_the_experts_held(tiny):
    """`moe_touched` counts all published experts that received a row,
    `touched_held` those of the share: a layer that holds experts 4 to 7
    while every row goes to experts 0, 1 (not held) and 5."""
    model, _, _ = tiny
    paddle.seed(11)
    layer = DeepseekV2MoE(deepseek_v2_tiny(experts_held=(4, 4)))
    bias = np.zeros(16, np.float32)
    bias[[0, 1, 5]] = 10.0
    layer.expert_bias._data = jnp.asarray(bias)
    m = jax.random.normal(jax.random.key(1), (40, 64), jnp.float32)
    out, touched, held, max_load = layer(m)
    assert (float(touched), float(held), int(max_load)) == (3.0, 1.0, 40)
    whole = DeepseekV2MoE(model.config)
    _, touched, held, _ = whole(m)
    assert float(touched) == float(held) > 3
    # Trinity's layer, which holds all: the counts it always reported
    from paddle_tpu.models.afmoe import AfmoeMoE

    _, touched, max_load = AfmoeMoE(afmoe_tiny())(m)
    assert 2 <= float(touched) <= 8 and int(max_load) >= 10


# -------------------------------------------------- 5. the shares add up
def _moe_state(layer) -> dict:
    return {k: v._data for k, v in layer.state_dict(
        include_non_persistable_buffer=True).items()}


def test_shares_add_up_to_the_uncut_layer(tiny):
    """The guide's share test: the parts that the four shares of 4 experts
    (one routing group each) give, the shared expert counted once, add up to
    what the uncut reference gives for the whole layer; and each part is
    what the reference gives for the same share."""
    model, _, rcfg = tiny
    paddle.seed(5)
    whole = DeepseekV2MoE(model.config)
    full = _moe_state(whole)
    m = jax.random.normal(jax.random.key(2), (33, 64), jnp.float32)
    want, _, _ = ref.moe(full, m, rcfg)
    total = whole.shared_experts(m)
    for first in (0, 4, 8, 12):
        part = DeepseekV2MoE(deepseek_v2_tiny(experts_held=(first, 4)))
        for k, t in part.state_dict(
                include_non_persistable_buffer=True).items():
            src = full[k]
            t._data = src[first:first + 4] if k.startswith("experts.") else src
        assert part.experts.w_gate._data.shape[0] == 4
        mine, load = part.routed_load(m)
        assert int(load.sum()) == 33 * 3          # routed over all 16
        theirs, _, _ = ref.moe(full, m, rcfg, experts=(first, 4),
                               shared=False)
        assert float(jnp.abs(mine - theirs).max()) <= TOL
        cut, _, _ = ref.moe(_moe_state(part), m, rcfg, shared=False,
                            base=first)
        assert float(jnp.abs(mine - cut).max()) <= TOL
        total = total + mine
    assert float(jnp.abs(total - want).max()) <= TOL
    with pytest.raises(ValueError, match="experts held"):
        DeepseekV2MoE(deepseek_v2_tiny(experts_held=(14, 4)))


def _share_of(model, first, count, v0, vn):
    """A model that holds experts [first, first + count) and the
    vocabulary's rows [v0, v0 + vn) of `model`'s weights."""
    part = DeepseekV2ForCausalLM(deepseek_v2_tiny(
        experts_held=(first, count), vocab_size=vn))
    part.eval()
    full = state_of(model)
    for k, t in part.state_dict(include_non_persistable_buffer=True).items():
        src = full[k]
        if ".experts." in k:
            src = src[first:first + count]
        elif k == "lm_head.weight":
            src = src[:, v0:v0 + vn]
        elif k == "model.embed_tokens.weight":
            src = src[v0:v0 + vn]
        t._data = src
    return part


def test_the_heads_slices_add_up_and_a_share_model_is_the_references(tiny):
    """The logits of the four slices of the vocabulary, side by side, are
    the uncut reference's; and a model that holds one chip's share (experts
    4 to 7, ids and logits 64 to 127) gives what the reference gives when
    told the same share."""
    model, state, rcfg = tiny
    ids = _ids(20, seed=7)
    hidden, _ = ref.hidden_states(state, jnp.asarray(ids), rcfg)
    whole = ref.head(state, hidden, rcfg)
    parts = [ref.head(state, hidden, rcfg, vocab=(v0, 64))
             for v0 in (0, 64, 128, 192)]
    assert float(jnp.abs(jnp.concatenate(parts, -1) - whole).max()) <= 1e-6
    for v0, part in zip((0, 64, 128, 192), parts):
        got = model._head_logits(model.model.norm(hidden))._data
        assert float(jnp.abs(got[:, v0:v0 + 64] - part).max()) <= TOL
    share = _share_of(model, 4, 4, 64, 64)
    mine = _ids(20, seed=8, vocab=64)         # ids drawn from the slice
    got = share(paddle.to_tensor(mine[None]))._data[0]
    want = ref.logits(state, jnp.asarray(mine + 64), rcfg, experts=(4, 4),
                      vocab=(64, 64))
    assert got.shape == (20, 64)
    assert float(jnp.abs(got - want).max()) <= TOL
    cut = ref.logits(state_of(share), jnp.asarray(mine), rcfg, base=4)
    assert float(jnp.abs(got - cut).max()) <= TOL


# ------------------------------------------ 6. through the serving engine
def _engine(model, **kw):
    args = dict(slot_count=3, ladder=(4, 16, 32), max_seq_len=48,
                max_new_cap=16, steps_per_dispatch=4)
    args.update(kw)
    return ServingEngine(model, **args)


def _recording_head(model, monkeypatch):
    """Every logit row the engine's programs compute, as they leave the
    device: [(rows, vocab)]."""
    seen = []
    head = type(model)._head_logits

    def recording(self, h):
        out = head(self, h)
        jax.debug.callback(lambda x: seen.append(np.asarray(x)), out._data)
        return out

    monkeypatch.setattr(type(model), "_head_logits", recording)
    return seen


def test_prefill_then_decode_match_the_reference(tiny, monkeypatch):
    """Greedy requests through submit/step with prompts just under, at and
    just over a rung (15, 16, 17 of rung 16; 3, 4, 5 of rung 4), right-padded
    by the engine, prefilled in the expanded form and decoded in the
    absorbed form through the latent rows: every logit row the programs
    computed is the reference's over the same prefix, every token its
    argmax, and every latent row a slot holds is the reference's
    [n_t | rope(k_r,t)]."""
    model, state, rcfg = tiny
    seen = _recording_head(model, monkeypatch)
    eng = _engine(model, slot_count=1, steps_per_dispatch=2)
    for n in (15, 16, 17, 3, 4, 5):
        del seen[:]
        req = eng.submit(_ids(n, seed=n), max_new_tokens=8, temperature=0.0)
        eng.run()
        jax.effects_barrier()
        assert req.done and len(req.tokens) == 8
        out = req.output_ids()
        want = np.asarray(ref.logits(state, jnp.asarray(out), rcfg))
        # one row from the prefill, then one a decode step (steps past the
        # budget run idle and are not compared)
        rows = [seen[0][0]] + [s[0] for s in seen[1:8]]
        for j, row in enumerate(rows):
            assert np.abs(row - want[n - 1 + j]).max() <= TOL, (n, j)
            assert int(row.argmax()) == req.tokens[j]
        _, infos = ref.hidden_states(state, jnp.asarray(out), rcfg)
        held = len(out) - 1
        for mine, info in zip(eng.slot_cache.latent, infos):
            assert float(jnp.abs(mine[0, :held] - info["row"][:held]
                                 ).max()) <= 1e-5


def _worst_gap(state, rcfg, reqs):
    worst = 0.0
    for r in reqs:
        out = r.output_ids()
        rows = np.asarray(ref.logits(state, jnp.asarray(out), rcfg))
        for j, tok in enumerate(r.tokens):
            row = rows[len(r.prompt_ids) - 1 + j]
            worst = max(worst, float((row.max() - row[tok])
                                     / (row.max() - row.mean())))
    return worst


# --------------------------- 7. slots at depths, a slot reused, run-ahead
def test_slots_at_different_depths_and_a_slot_seated_again(tiny):
    """Three slots at different depths in one batch, six requests so every
    slot is retired and seated again; every token is the reference's choice
    over the same prefix, and the request that reused a slot gives the
    tokens of a fresh engine."""
    model, state, rcfg = tiny
    eng = _engine(model)
    prompts = [_ids(n, seed=n) for n in (3, 13, 30, 7, 16, 2)]
    budgets = (16, 12, 16, 9, 16, 16)
    reqs = [eng.submit(p, max_new_tokens=new, temperature=0.0)
            for p, new in zip(prompts, budgets)]
    eng.run()
    assert all(r.done and r.outcome == "length" for r in reqs)
    assert [len(r.tokens) for r in reqs] == list(budgets)
    assert _worst_gap(state, rcfg, reqs) <= 1e-3
    assert len({r.slot for r in reqs}) == 3
    fresh = _engine(model)
    alone = fresh.submit(prompts[4], max_new_tokens=budgets[4],
                         temperature=0.0)
    fresh.run()
    assert alone.tokens == reqs[4].tokens


def test_the_kernel_forced_gives_the_plain_forms_tokens(tiny, monkeypatch):
    """Slots at different depths and one seated again, with the decode
    steps' absorbed core through the Pallas kernel
    (ops/pallas/latent_decode.py; interpreted here, in blocks of 16 of the
    48 rows): greedy tokens are the plain form's, which the test above
    holds to the reference, and the slots hold the same latent rows."""
    model, _, _ = tiny
    prompts = [_ids(n, seed=n) for n in (3, 13, 30, 7)]
    budgets = (8, 5, 8, 8)      # the fourth request takes the second's slot

    def serve():
        eng = _engine(model)
        reqs = [eng.submit(p, max_new_tokens=new, temperature=0.0)
                for p, new in zip(prompts, budgets)]
        eng.run()
        assert all(r.done and r.outcome == "length" for r in reqs)
        return eng, reqs

    reg = metrics.default_registry()
    plain_eng, plain = serve()
    monkeypatch.setattr(latent_decode, "_target", lambda: "interpret")
    monkeypatch.setattr(latent_decode, "BLOCK_ROWS", 16)
    before = [reg.counter("mla.calls." + f).value
              for f in ("absorbed", "absorbed_kernel")]
    eng, forced = serve()
    traced = [reg.counter("mla.calls." + f).value - b
              for f, b in zip(("absorbed", "absorbed_kernel"), before)]
    # every absorbed core of a decode program, one a layer a program
    assert traced[0] == traced[1] > 0 and traced[1] % 4 == 0
    assert [r.tokens for r in forced] == [r.tokens for r in plain]
    for mine, theirs in zip(eng.slot_cache.latent, plain_eng.slot_cache.latent):
        assert float(jnp.abs(mine - theirs).max()) <= 1e-5


@pytest.mark.parametrize("sampling", [dict(temperature=0.0),
                                      dict(temperature=0.8, top_k=20,
                                           top_p=0.9)])
def test_run_ahead_gives_the_same_tokens(tiny, monkeypatch, sampling):
    model, _, _ = tiny
    prompts = [_ids(n, seed=n) for n in (5, 12, 9)]

    def serve(ahead):
        eng = _engine(model)
        if not ahead:
            monkeypatch.setattr(eng, "_may_run_ahead", lambda: False)
        reqs = [eng.submit(p, max_new_tokens=16, seed=i, **sampling)
                for i, p in enumerate(prompts)]
        eng.run()
        return eng, [r.tokens for r in reqs]

    a0 = monitor.stat("serving.decode_ahead").get()
    eng, ahead = serve(True)
    assert monitor.stat("serving.decode_ahead").get() - a0 > 0
    assert eng.stats()["decode_ahead_share"] > 0
    _, plain = serve(False)
    assert ahead == plain


# ------------------------------------------------------- 8. the refusals
def test_what_latent_layers_cannot_do_is_refused_by_name(tiny):
    model, _, _ = tiny
    # the paged layout and its prefix cache (only PagedSlotCache builds one)
    with pytest.raises(ValueError, match=r"paged.*\[0, 1, 2, 3\].*latent "
                                         r"rows.*page of keys.*prefix cache"):
        _engine(model, kv_layout="paged")
    paddle.seed(0)
    draft = GPTForPretraining(GPTConfig(
        vocab_size=256, hidden_size=32, num_layers=1, num_heads=2,
        max_seq_len=64))
    with pytest.raises(ValueError, match=r"speculative decoding.*\[0, 1, 2, "
                                         r"3\].*latent rows.*absorbed form"):
        _engine(model, draft_model=draft)
    paddle.seed(0)
    target = GPTForPretraining(GPTConfig(
        vocab_size=256, hidden_size=32, num_layers=1, num_heads=2,
        max_seq_len=64))
    with pytest.raises(ValueError, match="draft model's cache.*latent rows"):
        ServingEngine(target, slot_count=2, ladder=(8,), max_seq_len=32,
                      max_new_cap=8, draft_model=model)
    with pytest.raises(ValueError, match="speculate_k > 0 needs a draft"):
        _engine(model).submit([1, 2, 3], speculate_k=2)


# ------------------------------------------------------------ 9. the bytes
def test_cache_bytes_are_the_arithmetic(tiny):
    model, _, _ = tiny
    eng = _engine(model)
    spec = kv_state.spec_of(model, 48)
    assert spec == [LatentLayerSpec("latent", 48, 32, 8)] * 4
    assert latent_width(spec[0]) == 128
    kv = eng.slot_cache
    assert kv.n_args == 3 and len(kv.args()) == 3
    assert kv.k_stored == kv.v_stored == kv.state == kv.tail == []
    assert [a.shape for a in kv.latent_stored] == [(3, 48, 128)] * 4
    assert [a.shape for a in kv.latent] == [(3, 48, 40)] * 4
    assert kv.latent_bytes() == 4 * 3 * 48 * 128 * 4
    assert eng.kv_cache_bytes() == kv.nbytes() == kv.latent_bytes()
    assert eng.stats()["kv_cache_bytes"] == kv.latent_bytes()
    assert kv.gauges() == {"latent_bytes": kv.latent_bytes()}
    assert eng._donate(1, kv) == (1, 2, 3)


def test_published_sizes_are_the_arithmetic():
    """The benchmark's configuration through `from_dict`, counted from
    shapes and never built: 5,164M parameters, 149.23M of them a layer's
    attention; 1,152 B of latent a position a layer, 2.26 GB at 64 slots x
    6,144 rows and 2.52 GB as stored."""
    cfg = DeepseekV2Config.from_dict(_published())
    assert (cfg.hidden_size, cfg.num_attention_heads, cfg.q_lora_rank,
            cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim, cfg.moe_intermediate_size, cfg.intermediate_size,
            cfg.num_experts_per_tok, cfg.n_shared_experts) == (
        5120, 128, 1536, 512, 128, 64, 128, 1536, 12288, 6, 2)
    assert (cfg.num_layers, cfg.first_k_dense_replace, cfg.dtype) == (
        5, 1, "bfloat16")
    assert (cfg.n_routed_experts, cfg.experts_held, cfg.n_group,
            cfg.topk_group, cfg.vocab_size) == (160, (0, 40), 8, 3, 25600)
    assert cfg.max_seq_len == 163840

    shapes = jax.eval_shape(lambda: state_of(DeepseekV2ForCausalLM(cfg)))
    count = {k: int(np.prod(v.shape)) for k, v in shapes.items()
             if not k.endswith("expert_bias")}
    attn = sum(n for k, n in count.items() if ".layers.1.self_attn." in k
               and "layernorm" not in k)
    assert attn == 149_225_472
    experts = sum(n for k, n in count.items() if ".layers.1.mlp.experts." in k)
    assert experts == 40 * 3 * 5120 * 1536
    total = sum(count.values())
    matrices = sum(n for k, n in count.items() if "norm" not in k)
    assert matrices == (337_969_152 + 4 * (149_225_472 + 47_185_920 + 819_200
                                           + 943_718_400) + 262_144_000)
    assert round(total / 1e6) == 5164 and total - matrices < 1e5
    dtypes = {str(v.dtype) for k, v in shapes.items() if "router" in k}
    assert dtypes == {"float32"}
    assert all(str(v.dtype) == "bfloat16" for k, v in shapes.items()
               if "router" not in k and "expert_bias" not in k)

    spec = DeepseekV2ForCausalLM.kv_cache_spec(
        type("M", (), {"config": cfg})(), 6144)
    assert spec == [LatentLayerSpec("latent", 6144, 512, 64)] * 5
    assert 2 * (spec[0].latent_dim + spec[0].rope_dim) == 1152
    assert latent_width(spec[0]) == 640
    kv = jax.eval_shape(lambda: kv_state.SlotCache(
        spec, 64, 6144, jnp.bfloat16).latent_stored)
    stored = sum(int(np.prod(a.shape)) * 2 for a in kv)
    assert stored == 64 * 6144 * 5 * 640 * 2 == 2_516_582_400
    assert 64 * 6144 * 5 * 1152 == 2_264_924_160


def test_latent_handles_write_and_hide_rows():
    """`SlotLatent` writes each slot's row at its own offset, padded with
    zeros; `ChunkLatent` at one offset; rows past what is held report
    positions no query has reached."""
    rows = jnp.full((2, 6, 128), 7.0)
    cache = SlotLatent(rows, jnp.asarray([1, 4], jnp.int32))
    assert np.asarray(cache.positions(1)).tolist() == [[1], [4]]
    new = jnp.ones((2, 1, 40))
    out, held, after = cache.update(new)
    assert out.shape == (2, 6, 128) and held.shape == (1, 1, 6)
    assert float(out[0, 1, :40].min()) == 1.0 == float(out[1, 4, :40].max())
    assert float(jnp.abs(out[0, 1, 40:]).max()) == 0.0     # the pad
    assert float(out[0, 0].min()) == 7.0 == float(out[1, 5].min())
    assert np.asarray(after.offset).tolist() == [2, 5]
    seen = np.asarray(held <= cache.positions(1)[:, :, None])
    assert seen[0, 0].tolist() == [True, True, False, False, False, False]
    assert seen[1, 0].tolist() == [True] * 5 + [False]
    spec = LatentLayerSpec("latent", 6, 32, 8)
    chunk = ChunkLatent.zeros(1, spec, jnp.float32, rows=4)
    assert chunk.fresh and chunk.rows.shape == (1, 4, 40)
    out, held, after = chunk.update(jnp.ones((1, 3, 40)))
    assert not after.fresh and int(after.offset) == 3
    assert float(out[0, :3].min()) == 1.0 and float(out[0, 3].max()) == 0.0
    leaves, tree = jax.tree_util.tree_flatten(chunk)
    assert jax.tree_util.tree_unflatten(tree, leaves).fresh


def test_serve_step_record_carries_the_load_and_the_gauge(tiny):
    model, _, _ = tiny

    class Sink:
        records = []

        def write(self, rec):
            self.records.append(rec)

        def close(self):
            pass

    eng = _engine(model, sink=Sink())
    eng.submit(_ids(9), max_new_tokens=8)
    eng.submit(_ids(5), max_new_tokens=8)
    eng.run()
    steps = [r for r in Sink.records if r["event"] == "serve_step"]
    assert steps
    for r in steps:
        assert 1 <= r["moe_touched"] <= 16
        assert r["moe_touched_held"] == pytest.approx(r["moe_touched"])
        assert r["moe_max_load"] >= 1 and "contexts" in r
        assert r["latent_bytes"] == eng.slot_cache.latent_bytes()
    assert model.serving_step_stats == {
        "moe_touched": "mean", "moe_touched_held": "mean",
        "moe_max_load": "max"}


# ----------------------------------------------------------- 10. the scopes
class _NullScope:
    def __init__(self, name):
        pass

    def __call__(self, fn):
        return fn

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def _lower(eng, which):
    s = eng.slot_count

    def vec(dtype):
        return jnp.zeros((s,), dtype)

    cache = eng.slot_cache.args()
    if which == "decode":
        return eng._build_decode("sample").lower(
            eng._params, *cache, vec(jnp.int32), vec(jnp.int32),
            vec(jnp.bool_), vec(jnp.float32), vec(jnp.int32),
            vec(jnp.float32), vec(jnp.int32), vec(jnp.int32), vec(jnp.int32))
    return eng._build_prefill(16).lower(
        eng._params, *cache, jnp.zeros((1, 16), jnp.int64), jnp.int32(9),
        jnp.int32(0), jnp.float32(0.0), jnp.int32(0), jnp.float32(1.0),
        jnp.int32(0))


@pytest.mark.parametrize("which", ["decode", "prefill"])
def test_program_stablehlo_identical_without_scopes(tiny, monkeypatch, which):
    model, _, _ = tiny
    eng = _engine(model, slot_count=2, ladder=(8, 16), max_seq_len=32,
                  max_new_cap=8, steps_per_dispatch=2)
    scoped = _lower(eng, which)
    with monkeypatch.context() as m:
        m.setattr(jax, "named_scope", _NullScope)
        bare = _lower(eng, which)
    assert scoped.as_text() == bare.as_text()
    names = set(re.findall(r'op_name="([^"]+)"', scoped.compile().as_text()))
    scopes = {device_trace.scope_of(n)[0] for n in names}
    mla = {"q_lora", "kv_latent", "rope", "core", "out"} | (
        {"absorb", "unabsorb", "cache_write"} if which == "decode"
        else {"expand"})
    moe = {"router", "dispatch", "experts", "shared", "combine"}
    assert scopes >= ({f"{which}/mla/{s}" for s in mla}
                      | {f"{which}/moe/{s}" for s in moe}
                      | {f"{which}/{s}" for s in (
                          "embed", "mlp", "final_norm", "lm_head", "sample")})
    assert device_trace.SCOPES >= {"mla", "q_lora", "kv_latent", "expand",
                                   "absorb", "unabsorb"}
    other = "expand" if which == "decode" else "absorb"
    assert not any(f"/{other}" in s for s in scopes)


def test_the_two_copies_of_the_reference_are_one_text():
    with open(os.path.join(REPO, "tests", "reference_deepseek_v2.py")) as a, \
            open(os.path.join(REPO, "benchmarks", "lib",
                              "reference_deepseek_v2.py")) as b:
        assert a.read() == b.read()


# --------- the other families' serving programs are the parent's, to a byte
@pytest.mark.parametrize("family", ["gpt", "afmoe", "olmo"])
def test_the_other_families_serving_programs_are_the_parents(family):
    """The `latent` kind, the router's new arguments and `routed_load` leave
    GPT-2's, Trinity's and Olmo-Hybrid's prefill and decode programs the
    text they had before them (tests/data/serving_program_digests.json,
    taken at PR 34), so no cell of theirs compiles anew; so does the slot
    kernel's door (PR 38: the CPU lowers the plain cores). A PR that changes
    one of those programs on purpose takes the digests again:
    `python tools/serving_program_digests.py --write AT`."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import serving_program_digests as tool
    finally:
        sys.path.pop(0)
    with open(tool.FILE) as f:
        want = json.load(f)["digests"]
    assert tool.digests(family) == {k: v for k, v in want.items()
                                    if k.startswith(family + ".")}
