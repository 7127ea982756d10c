"""The `sdar_moe` decoder (models/sdar.py), the block-causal mask
(nn/kv_cache.py), the block-step decode program and its host side
(serving/engine.py, serving/diffusion.py) and the sampler's probability
(serving/sampling.py), against the plain reference (tests/reference_sdar.py)
at a small size on the CPU: hidden 64, 4 / 2 heads of 16, 3 layers of 8
experts top-2, vocabulary 256, blocks of 4, float32, seeded random weights.
"""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import reference_sdar as ref
from paddle_tpu.core import monitor
from paddle_tpu.models import (GPTForPretraining, SdarConfig,
                               SdarForCausalLM, gpt_tiny, sdar_tiny)
from paddle_tpu.nn.kv_cache import BlockDiffusion, block_causal_mask
from paddle_tpu.observability import metrics
from paddle_tpu.ops.pallas import block_decode
from paddle_tpu.serving import (ServingEngine, diffusion, sample_tokens,
                                sample_tokens_with_prob)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 1e-4          # f32 against f32: the model's logits and the reference's
B, MASK = 4, 255
REF_KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "rms_norm_eps", "rope_theta", "num_experts",
            "num_experts_per_tok", "norm_topk_prob", "num_hidden_layers")
ENGINE = dict(slot_count=3, ladder=(8, 16), max_seq_len=48, max_new_cap=16,
              steps_per_dispatch=5)


def ref_config(cfg) -> dict:
    return {k: getattr(cfg, k) for k in REF_KEYS}


def state_of(model) -> dict:
    return {k: v._data for k, v in model.state_dict(
        include_non_persistable_buffer=True).items()}


@pytest.fixture(scope="module")
def model():
    paddle.seed(6)
    m = SdarForCausalLM(sdar_tiny())
    m.eval()
    return m


@pytest.fixture(scope="module")
def ref_forward(model):
    """The reference's forward, compiled once a length."""
    cfg, programs = ref_config(model.config), {}

    def forward(params, ids, block_length, _cfg):
        n = int(ids.shape[0])
        if n not in programs:
            programs[n] = jax.jit(
                lambda p, x: ref.forward(p, x, block_length, cfg))
        return programs[n](params, ids)

    return forward


def prompt_of(n: int, seed: int = 0):
    return np.random.default_rng(seed).integers(0, MASK - 1, (n,))


def reference_run(model, ref_forward, prompt, n_new, steps=4,
                  remasking="low_confidence_static", threshold=0.9,
                  eos=None):
    return ref.generate(state_of(model), prompt, n_new, B, steps, remasking,
                        threshold, ref_config(model.config), MASK,
                        eos_token_id=eos, forward_fn=ref_forward)


def states_of(req):
    return [(s["offset"], s["block"], s["committed"])
            for s in req.block_states]


def trace_of(trace):
    return [(t["offset"], t["block"], t["committed"]) for t in trace]


# ------------------------------------------------------------------ the mask
def test_block_causal_mask_is_the_written_out_matrix():
    want = np.array([[1, 1, 1, 1, 0, 0, 0, 0, 0, 0],
                     [1, 1, 1, 1, 0, 0, 0, 0, 0, 0],
                     [1, 1, 1, 1, 0, 0, 0, 0, 0, 0],
                     [1, 1, 1, 1, 0, 0, 0, 0, 0, 0],
                     [1, 1, 1, 1, 1, 1, 1, 1, 0, 0],
                     [1, 1, 1, 1, 1, 1, 1, 1, 0, 0],
                     [1, 1, 1, 1, 1, 1, 1, 1, 0, 0],
                     [1, 1, 1, 1, 1, 1, 1, 1, 0, 0],
                     [1, 1, 1, 1, 1, 1, 1, 1, 1, 1],
                     [1, 1, 1, 1, 1, 1, 1, 1, 1, 1]], bool)
    held = jnp.arange(10)[None, None, :]
    got = block_causal_mask(held, jnp.arange(10)[None, :], 4)
    assert (np.asarray(got[0]) == want).all()
    assert (np.asarray(ref.block_causal_mask(10, 4)) == want).all()
    # a slot at its own offset: the block's four queries see the rows before
    # it and its own, nothing past it; with blocks of 1 it is the causal test
    got = block_causal_mask(held, jnp.asarray([[4, 5, 6, 7], [0, 1, 2, 3]]),
                            4)
    assert (np.asarray(got[0]) == want[4:8]).all()
    assert (np.asarray(got[1]) == want[0:4]).all()
    causal = block_causal_mask(held, jnp.arange(10)[None, :], 1)[0]
    assert (np.asarray(causal) == np.tril(np.ones((10, 10), bool))).all()


# --------------------------------------------------------------- the forward
def test_forward_logits_match_the_reference(model):
    ids = prompt_of(22, 1)
    got = np.asarray(model(paddle.to_tensor(ids[None]))._data[0])
    want = np.asarray(ref.forward(state_of(model), jnp.asarray(ids), B,
                                  ref_config(model.config)))
    assert np.abs(got - want).max() < TOL * np.abs(want).max()
    # the mask matters: under the causal one the logits are another model's
    causal = np.asarray(ref.forward(state_of(model), jnp.asarray(ids), 1,
                                    ref_config(model.config)))
    assert np.abs(got - causal).max() > 100 * TOL * np.abs(want).max()


def test_config_refuses_by_name_what_it_does_not_compute():
    for kw, word in ((dict(use_sliding_window=True), "use_sliding_window"),
                     (dict(rope_scaling={"type": "yarn"}), "rope_scaling"),
                     (dict(attention_bias=True), "attention_bias"),
                     (dict(tie_word_embeddings=True), "tie_word_embeddings"),
                     (dict(mlp_only_layers=[0]), "mlp_only_layers"),
                     (dict(decoder_sparse_step=2), "decoder_sparse_step"),
                     (dict(mask_token_id=256), "mask_token_id"),
                     (dict(block_length=3), "block_length")):
        with pytest.raises(ValueError, match=word):
            sdar_tiny(**kw)
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "sdar-30b-a3b.json")) as f:
        published = json.load(f)
    cfg = SdarConfig.from_dict(published)
    assert (cfg.num_experts, cfg.num_hidden_layers, cfg.block_length,
            cfg.mask_token_id) == (128, 6, 4, 151669)


# --------------------------------------------------------------- the sampler
def test_sample_tokens_with_prob_is_the_draw_and_its_probability(
        monkeypatch):
    rng = np.random.default_rng(2)
    logits = jnp.asarray(rng.normal(size=(6, 64)) * 3, jnp.float32)
    keys = jax.random.split(jax.random.key(5), 6)
    temps = jnp.asarray([0.0, 0.0, 0.8, 0.8, 1.3, 0.5], jnp.float32)
    top_k = jnp.asarray([0, 5, 0, 7, 50, 3], jnp.int32)
    top_p = jnp.asarray([1.0, 0.5, 1.0, 0.9, 0.7, 1.0], jnp.float32)
    tok, prob = sample_tokens_with_prob(logits, keys, temps, top_k, top_p)
    assert (np.asarray(tok) == np.asarray(
        sample_tokens(logits, keys, temps, top_k, top_p))).all()
    for i in range(6):
        want = ref.confidence(logits[i], int(tok[i]), float(temps[i]),
                              int(top_k[i]), float(top_p[i]))
        assert abs(float(prob[i]) - float(want)) < 1e-5
        assert float(prob[i]) > 0
    # at SDAR's width the filter searches the candidates (every row asks
    # for top_k <= 64 or nothing): the draws and probabilities of the
    # search over the whole row, and the reference's
    from paddle_tpu.serving import sampling

    wide = jnp.asarray(rng.normal(size=(6, 151_936)) * 3, jnp.float32)
    tok, prob = sample_tokens_with_prob(wide, keys, temps, top_k, top_p)
    with monkeypatch.context() as m:
        m.setattr(sampling, "takes_narrowed", lambda vocab: False)
        was_tok, was = sample_tokens_with_prob(wide, keys, temps, top_k,
                                               top_p)
    np.testing.assert_array_equal(tok, was_tok)
    np.testing.assert_allclose(prob, was, rtol=1e-6)
    for i in range(6):
        want = ref.confidence(wide[i], int(tok[i]), float(temps[i]),
                              int(top_k[i]), float(top_p[i]))
        assert abs(float(prob[i]) - float(want)) < 1e-5 * float(want)


def test_unmask_chooses_as_the_reference_selects():
    rng = np.random.default_rng(3)
    for trial in range(40):
        masked = rng.random(4) < 0.7
        conf = np.round(rng.random(4), 1)          # ties happen
        n = int(rng.integers(0, 4))
        thr = float(rng.choice([0.25, 0.55, 0.95]))
        for r, name in enumerate(diffusion.REMASKING):
            got = diffusion.unmask(
                jnp.asarray(masked[None]), jnp.asarray(conf[None], jnp.float32),
                jnp.asarray([n], jnp.int32), jnp.asarray([r], jnp.int32),
                jnp.asarray([thr], jnp.float32))
            want = ref.select(list(masked), list(conf), n, name, thr)
            assert sorted(np.nonzero(np.asarray(got[0]))[0]) == want, \
                (masked, conf, n, thr, name)
    for steps in (1, 2, 3, 4, 6):
        want = ref.transfer_counts(4, steps)
        got = diffusion.share(4, jnp.full((steps,), steps, jnp.int32),
                              jnp.arange(steps, dtype=jnp.int32))
        assert list(np.asarray(got)) == want


# ----------------------------------------------------------- through serving
CASES = [   # (prompt length, new tokens, steps, remasking, threshold)
    (8, 8, 4, "low_confidence_static", 0.9),        # P mod B == 0
    (9, 7, 4, "low_confidence_static", 0.9),        # 1, a budget inside a block
    (11, 10, 4, "low_confidence_static", 0.9),      # 3
    (2, 5, 4, "low_confidence_static", 0.9),        # P < B: no prefill
    (9, 12, 4, "sequential", 0.9),
    (10, 12, 2, "low_confidence_static", 0.9),      # two positions a forward
    (9, 12, 4, "low_confidence_dynamic", 0.02),     # blocks end early
    (13, 9, 3, "low_confidence_dynamic", 0.05),
]


@pytest.mark.parametrize("case", CASES, ids=[
    f"P{c[0]}-N{c[1]}-{c[2]}steps-{c[3]}-{c[4]}" for c in CASES])
def test_served_blocks_tokens_and_rows_match_the_reference(
        model, ref_forward, case):
    """Every committed token, the block after every forward (so the ORDER
    in which positions were unmasked) and every row the slot holds, against
    the reference's `generate` and its keys and values."""
    plen, n_new, steps, remasking, thr = case
    prompt = prompt_of(plen, plen)
    eng = ServingEngine(model, **ENGINE)
    req = eng.submit(prompt, max_new_tokens=n_new, denoising_steps=steps,
                     remasking=remasking, confidence_threshold=thr,
                     record_blocks=True)
    eng.run()
    want, trace = reference_run(model, ref_forward, prompt, n_new, steps,
                                remasking, thr)
    assert req.tokens == want and req.finish_reason == "length"
    assert states_of(req) == trace_of(trace)
    # the rows: everything before the last committed block's end
    held = trace[-1]["offset"] + B
    seq = list(prompt[:plen // B * B]) + [
        t for e in trace if e["committed"] for t in e["block"]]
    assert len(seq) == held
    _, infos = ref.hidden_states(state_of(model), jnp.asarray(seq),
                                 ref_config(model.config), B)
    kv = eng.slot_cache
    for l, info in enumerate(infos):
        for mine, theirs in ((kv.k[l], info["k"]), (kv.v[l], info["v"])):
            got = np.asarray(mine[req.slot][:held], np.float32)
            assert np.abs(got - np.asarray(theirs)).max() < TOL * max(
                1.0, float(np.abs(np.asarray(theirs)).max()))


def test_dynamic_blocks_end_in_two_and_three_forwards(model, ref_forward):
    """A threshold under every confidence ends a block in two forwards (all
    at once, then the commit), one between them in three: and a block of
    each sits in one dispatch beside a static one in another phase."""
    eng = ServingEngine(model, **ENGINE)
    prompts = [prompt_of(8, 20), prompt_of(9, 21), prompt_of(12, 22)]
    plans = [("low_confidence_dynamic", 0.0), ("low_confidence_dynamic", 0.5),
             ("low_confidence_static", 0.9)]
    reqs = [eng.submit(p, max_new_tokens=12, remasking=r,
                       confidence_threshold=t, record_blocks=True)
            for p, (r, t) in zip(prompts, plans)]
    eng.run()
    for req, p, (r, t) in zip(reqs, prompts, plans):
        want, trace = reference_run(model, ref_forward, p, 12, 4, r, t)
        assert req.tokens == want
        assert states_of(req) == trace_of(trace)
    per_block = [[len(req.block_states) / sum(s["committed"]
                                              for s in req.block_states)]
                 for req in reqs]
    assert per_block[0] == [2.0] and per_block[2] == [5.0]
    assert 2.0 < per_block[1][0] < 5.0
    st = eng.stats()
    assert st["forwards"] == sum(len(r.block_states) for r in reqs)
    assert st["blocks_committed"] == sum(
        s["committed"] for r in reqs for s in r.block_states)
    assert st["positions_unmasked"] == 3 * 12 + (4 - 1)   # P mod B == 1 once


def test_an_end_token_inside_a_block_cuts_the_output(model, ref_forward):
    prompt = prompt_of(9, 30)
    free, _ = reference_run(model, ref_forward, prompt, 12)
    eos = free[5]                       # inside the second block
    want, _ = reference_run(model, ref_forward, prompt, 12, eos=eos)
    eng = ServingEngine(model, **ENGINE)
    req = eng.submit(prompt, max_new_tokens=12, eos_token_id=eos)
    other = eng.submit(prompt_of(8, 31), max_new_tokens=12)
    eng.run()
    assert req.tokens == want and req.tokens[-1] == eos
    assert req.finish_reason == "eos" and len(req.tokens) <= 6
    assert other.finish_reason == "length" and len(other.tokens) == 12


@pytest.mark.parametrize("ahead", [True, False])
def test_admissions_between_dispatches_and_run_ahead(model, ref_forward,
                                                     ahead, monkeypatch):
    """Seven requests through three slots, admitted as slots come free, with
    run-ahead on and off: the same tokens as the reference's, and the
    counter behind `serve_tokens_per_s` counts exactly the tokens the
    requests hold."""
    eng = ServingEngine(model, **dict(ENGINE, steps_per_dispatch=3))
    if not ahead:
        monkeypatch.setattr(eng, "_may_run_ahead", lambda: False)
    sizes = [(8, 16), (9, 16), (11, 16), (2, 5), (13, 9), (16, 16), (5, 3)]
    tokens0 = monitor.stat("serving.tokens").get()
    reqs = [eng.submit(prompt_of(p, 40 + p), max_new_tokens=n)
            for p, n in sizes]
    eng.run()
    for req, (p, n) in zip(reqs, sizes):
        want, _ = reference_run(model, ref_forward, prompt_of(p, 40 + p), n)
        assert req.tokens == want and req.done
    assert monitor.stat("serving.tokens").get() - tokens0 == sum(
        len(r.tokens) for r in reqs) == sum(n for _, n in sizes)
    st = eng.stats()
    assert (st["decode_ahead_share"] > 0) == ahead
    assert st["tokens_per_forward"] == pytest.approx(
        sum(n for _, n in sizes) / st["forwards"])
    assert st["decode_executables"] == 1


def test_sampled_requests_finish_and_record_their_draws(model):
    eng = ServingEngine(model, **ENGINE)
    reqs = [eng.submit(prompt_of(9 + k, 50 + k), max_new_tokens=8, seed=k,
                       temperature=0.8, top_k=20, top_p=0.9,
                       record_blocks=True) for k in range(3)]
    greedy = eng.submit(prompt_of(8, 60), max_new_tokens=8)
    eng.run()
    assert all(r.done and len(r.tokens) == 8 for r in reqs + [greedy])
    assert all(0 <= t < MASK for r in reqs for t in r.tokens)
    for r in reqs:
        for before, after in zip(r.block_states, r.block_states[1:]):
            if before["committed"] or after["committed"]:
                continue
            # a position that was unmasked took this forward's draw
            for i in range(B):
                if before["block"][i] == MASK and after["block"][i] != MASK:
                    assert after["block"][i] == after["draws"][i]
                    assert 0 < after["confidences"][i] <= 1


def test_precompile_builds_the_block_programs(model, ref_forward):
    """The ladder's block prefills and both block-step programs ahead of
    the first request; the request then compiles nothing and reads as the
    reference does."""
    eng = ServingEngine(model, **ENGINE)
    done = eng.precompile(force=True)
    assert done["precompiled"] == len(eng.ladder) + 2
    c0 = (monitor.stat("serving.prefill_compiles").get(),
          monitor.stat("serving.decode_compiles").get())
    req = eng.submit(prompt_of(9, 70), max_new_tokens=7)
    eng.run()
    assert req.tokens == reference_run(model, ref_forward, prompt_of(9, 70),
                                       7)[0]
    assert (monitor.stat("serving.prefill_compiles").get(),
            monitor.stat("serving.decode_compiles").get()) == c0
    st = eng.stats()
    assert (st["prefill_executables"], st["decode_executables"]) == (2, 2)


def test_refusals_by_name(model):
    paddle.seed(0)
    gpt = GPTForPretraining(gpt_tiny())
    with pytest.raises(ValueError, match="diffusion over blocks"):
        ServingEngine(model, draft_model=gpt, **ENGINE)
    with pytest.raises(ValueError, match="diffusion over blocks"):
        ServingEngine(model, kv_layout="paged", **ENGINE)
    with pytest.raises(ValueError, match="multiples of the model's block"):
        ServingEngine(model, **dict(ENGINE, ladder=(6, 16)))
    eng = ServingEngine(model, **ENGINE)
    with pytest.raises(ValueError, match="mask token"):
        eng.submit([1, 2, MASK, 4])
    with pytest.raises(ValueError, match="remasking"):
        eng.submit([1, 2, 3], remasking="random")
    with pytest.raises(ValueError, match="denoising_steps"):
        eng.submit([1, 2, 3], denoising_steps=0)
    with pytest.raises(ValueError, match="speculate_k"):
        eng.submit([1, 2, 3], speculate_k=2)
    with pytest.raises(ValueError, match="diffusion over blocks"):
        model.generate(paddle.to_tensor(np.zeros((1, 4), np.int64)))
    plain = ServingEngine(gpt, slot_count=2, ladder=(8,), max_seq_len=32,
                          max_new_cap=8)
    for kw in (dict(denoising_steps=2), dict(remasking="sequential"),
               dict(confidence_threshold=0.5), dict(record_blocks=True)):
        with pytest.raises(ValueError, match=next(iter(kw))):
            plain.submit([1, 2, 3], **kw)
    assert model.generation == BlockDiffusion(4, MASK)


def test_the_block_kernel_forced_gives_the_plain_engines_blocks(
        model, monkeypatch):
    """Every block step's attention core through the kernel
    (ops/pallas/block_decode.py, interpreted, in blocks of 16 of the 48
    rows): greedy and sampled requests at different depths, the fourth
    seated again in a slot the second left, hold the plain engine's tokens
    and its block after every forward, with the same draws and confidences;
    the slots hold the same rows; each block-step program traced counts one
    kernel a layer."""
    plans = [(9, 16, {}), (14, 12, dict(temperature=0.8, top_k=20,
                                        top_p=0.9, seed=3)),
             (5, 16, {}), (11, 8, {})]

    def serve():
        eng = ServingEngine(model, **ENGINE)
        reqs = [eng.submit(prompt_of(n, 80 + n), max_new_tokens=new,
                           record_blocks=True, **kw) for n, new, kw in plans]
        eng.run()
        assert all(r.done and r.finish_reason == "length" for r in reqs)
        return eng, reqs

    def count(form):
        return metrics.default_registry().counter("attn.calls." + form).value

    plain_eng, plain = serve()
    monkeypatch.setattr(block_decode, "_target", lambda: "interpret")
    monkeypatch.setattr(block_decode, "BLOCK_ROWS", 16)
    before = count("block"), count("block_kernel")
    eng, forced = serve()
    traced = count("block") - before[0], count("block_kernel") - before[1]
    layers = model.config.num_hidden_layers
    assert traced[0] == traced[1] > 0 and traced[1] % layers == 0
    for theirs, mine in zip(plain, forced):
        assert mine.tokens == theirs.tokens
        assert [(s["offset"], s["block"], s["committed"], s["draws"])
                for s in mine.block_states] == [
            (s["offset"], s["block"], s["committed"], s["draws"])
            for s in theirs.block_states]
        for a, b in zip(mine.block_states, theirs.block_states):
            assert np.abs(np.asarray(a["confidences"])
                          - np.asarray(b["confidences"])).max() <= 1e-5
    for mine, theirs in zip(eng.slot_cache.k_stored + eng.slot_cache.v_stored,
                            plain_eng.slot_cache.k_stored
                            + plain_eng.slot_cache.v_stored):
        assert float(jnp.abs(mine - theirs).max()) <= 1e-5


# ------------------------------------------------------------- the programs
@pytest.mark.parametrize("family", ["sdar", "deepseek"])
def test_the_serving_programs_are_the_recorded_ones(family):
    """SDAR's block prefills and block-step decode programs, by the digest
    of their text beside the other families' (a PR that changes one on
    purpose takes them again: tools/serving_program_digests.py --write);
    and DeepSeek-V2's, taken on PR 39's parent and unchanged by it, beside
    the three that tests/test_deepseek_v2.py holds."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import serving_program_digests as tool
    finally:
        sys.path.pop(0)
    with open(tool.FILE) as f:
        want = json.load(f)["digests"]
    mine = {k: v for k, v in want.items() if k.startswith(family + ".")}
    assert len(mine) == 4 and tool.digests(family) == mine


def test_the_benchmarks_reference_is_this_one():
    with open(os.path.join(REPO, "tests", "reference_sdar.py")) as a, \
            open(os.path.join(REPO, "benchmarks", "lib",
                              "reference_sdar.py")) as b:
        assert a.read() == b.read()
