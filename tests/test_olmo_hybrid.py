"""The `olmo_hybrid` decoder (models/olmo_hybrid.py), the gated delta rule
(ops/gated_delta.py) and the slot cache's `state` kind (nn/kv_cache.py,
serving/kv_state.py), against the plain reference
(tests/reference_olmo_hybrid.py) at a small size on the CPU: hidden 64, 4
heads, d_k 8, d_v 16, the pattern L,L,L,F twice, vocabulary 256, float32,
seeded random weights.
"""
import functools
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import reference_olmo_hybrid as ref
from test_tracing import _NullScope
from paddle_tpu.core import monitor
from paddle_tpu.models import (GPTConfig, GPTForPretraining,
                               OlmoHybridConfig, OlmoHybridForCausalLM,
                               gpt_tiny, olmo_hybrid_tiny)
from paddle_tpu.models import olmo_hybrid as hybrid
from paddle_tpu.nn.kv_cache import (KVLayerSpec, SlotState, StateLayerSpec,
                                    conv_tail, stored_dims)
from paddle_tpu.observability import device_trace, metrics
from paddle_tpu.ops.gated_delta import gated_delta_chunked, gated_delta_step
from paddle_tpu.serving import ServingEngine, kv_state

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 1e-4          # f32 against f32: the model's logits and the reference's
STATE_BYTES = 4 * 8 * 16 * 4 + 3 * (4 * (2 * 8 + 16)) * 4   # tiny, f32 tail


def ref_config(cfg) -> dict:
    """The reference reads the published keys; the model's config has them
    as attributes."""
    return {"hidden_size": cfg.hidden_size,
            "num_attention_heads": cfg.num_attention_heads,
            "num_key_value_heads": cfg.num_key_value_heads,
            "rms_norm_eps": cfg.rms_norm_eps,
            "num_hidden_layers": cfg.num_hidden_layers,
            "layer_types": cfg.layer_types,
            "linear_num_key_heads": cfg.linear_num_heads,
            "linear_key_head_dim": cfg.linear_key_head_dim,
            "linear_value_head_dim": cfg.linear_value_head_dim,
            "linear_conv_kernel_dim": cfg.linear_conv_kernel_dim,
            "linear_allow_neg_eigval": cfg.linear_allow_neg_eigval}


def state_of(model) -> dict:
    return {k: v._data for k, v in model.state_dict(
        include_non_persistable_buffer=True).items()}


@pytest.fixture(scope="module")
def tiny():
    paddle.seed(5)
    model = OlmoHybridForCausalLM(olmo_hybrid_tiny())
    model.eval()
    return model, state_of(model), ref_config(model.config)


def _ids(n, seed=0, vocab=256):
    return np.random.default_rng(seed).integers(0, vocab, (n,), dtype=np.int64)


def _engine(model, slots=3, **kw):
    kw.setdefault("ladder", (8, 16, 32))
    kw.setdefault("max_seq_len", 48)
    kw.setdefault("max_new_cap", 16)
    kw.setdefault("steps_per_dispatch", 4)
    return ServingEngine(model, slot_count=slots, **kw)


WIDTH = 48          # every sequence here fits; the reference compiles once


@pytest.fixture(scope="module")
def reference(tiny):
    """The reference over ids right-padded to WIDTH (the pad is after every
    position that is read), compiled once: (ids, length) -> (logits
    [WIDTH, vocab], [info a layer] after `length` positions)."""
    _, state, rcfg = tiny

    @jax.jit
    def run(ids, length):
        h, infos = ref.hidden_states(state, ids, rcfg, length=length)
        return ref.head(state, h, rcfg), infos

    def padded(ids, length=None):
        buf = np.zeros((WIDTH,), np.int64)
        buf[:len(ids)] = ids
        return run(jnp.asarray(buf),
                   jnp.int32(len(ids) if length is None else length))

    return padded


@pytest.fixture(scope="module")
def served(tiny):
    """One engine for the tests that only need programs that exist: three
    slots, rungs 8 / 16 / 32, used and reused by one test after another."""
    return _engine(tiny[0])


def _worst_gap(reference, reqs):
    """Every served token against the reference's logits over the same
    prefix: how far the reference's logit of the token lies under its
    maximum, as a share of the position's (max - mean) spread."""
    worst = 0.0
    for r in reqs:
        logits = np.asarray(reference(r.output_ids())[0])
        for i, tok in enumerate(r.tokens):
            row = logits[len(r.prompt_ids) - 1 + i]
            worst = max(worst, float((row.max() - row[tok])
                                     / (row.max() - row.mean())))
    return worst


def _held_errors(eng, req, reference):
    """What the request's slot holds against the reference's after the
    positions the slot absorbed (all of the output but its last token):
    the worst absolute error of a state, a tail and a row."""
    out = req.output_ids()
    held = len(out) - 1
    _, infos = reference(out, held)
    kv, worst = eng.slot_cache, 0.0
    mine = iter(zip(kv.state, kv.tail))
    rows = iter(zip(kv.k, kv.v))
    for info in infos:
        if "state" in info:
            s, t = next(mine)
            worst = max(worst, float(jnp.abs(s[req.slot] - info["state"]).max()),
                        float(jnp.abs(t[req.slot] - info["tail"]).max()))
        else:
            k, v = next(rows)
            worst = max(worst,
                        float(jnp.abs(k[req.slot, :held] - info["k"][:held]).max()),
                        float(jnp.abs(v[req.slot, :held] - info["v"][:held]).max()))
    return worst


# ------------------------------------------------- 1. model vs reference
def _forward(model, ids):
    """The model's plain forward (no cache), compiled."""
    from paddle_tpu.core.tensor import Tensor

    return jax.jit(lambda i: model(Tensor(i))._data)(ids[None])[0]


@pytest.mark.parametrize("length", [1, 3, 21, 40])
def test_logits_match_reference(tiny, reference, length):
    """Shorter than the convolution, shorter and longer than a chunk of the
    delta rule (the tiny model's chunk is the published 64, so 40 is one
    chunk with a pad; `test_chunked_form_is_the_recurrence` has the rest)."""
    model, _, _ = tiny
    ids = _ids(length, seed=length)
    got = _forward(model, ids)
    want = reference(ids)[0][:length]
    assert float(jnp.abs(got - want).max()) <= TOL


def test_attention_in_query_blocks_and_delta_rule_in_small_chunks(
        tiny, reference, monkeypatch):
    model, _, _ = tiny
    monkeypatch.setattr(hybrid, "_QUERY_BLOCK", 5)
    monkeypatch.setattr(hybrid, "gated_delta_chunked",
                        functools.partial(gated_delta_chunked, chunk=4))
    ids = _ids(24, seed=4)
    got = _forward(model, ids)
    assert float(jnp.abs(got - reference(ids)[0][:24]).max()) <= TOL


def test_published_configuration_builds_its_cache_spec():
    """The benchmark's configuration file through `OlmoHybridConfig.from_dict`:
    the published widths, 2.28 MB a state, and at 16 slots x 4,096 3.02 GB
    of rows beside 0.33 GB of states."""
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "olmo-hybrid-7b.json")) as f:
        cfg = OlmoHybridConfig.from_dict(json.load(f))
    assert (cfg.hidden_size, cfg.intermediate_size, cfg.num_attention_heads,
            cfg.num_key_value_heads, cfg.head_dim, cfg.vocab_size) == (
        3840, 11008, 30, 30, 128, 100352)
    assert (cfg.linear_num_heads, cfg.linear_key_head_dim,
            cfg.linear_value_head_dim, cfg.linear_conv_kernel_dim,
            cfg.linear_allow_neg_eigval) == (30, 96, 192, 4, True)
    assert (cfg.num_layers, cfg.dtype, cfg.max_seq_len) == (
        12, "bfloat16", 65536)
    assert cfg.layer_types == (["linear_attention"] * 3
                               + ["full_attention"]) * 3
    spec = hybrid.kv_cache_spec(cfg, 4096)
    assert spec[0] == StateLayerSpec("state", 30, 96, 192, 3, 11520)
    assert spec[3] == KVLayerSpec("full", 4096, 30, 128)
    one = 30 * 96 * 192 * 4 + 3 * 11520 * 2
    assert one == 2280960
    rows = sum(16 * s.rows * s.kv_heads * s.head_dim * 2 * 2
               for s in spec if s.kind == "full")
    assert rows == 16 * 4096 * 46080 == 3019898880
    assert 9 * 16 * one == 328458240


def test_a_rotary_theta_is_refused_not_guessed():
    with pytest.raises(ValueError, match="rope_theta is null"):
        olmo_hybrid_tiny(rope_parameters={"rope_theta": 500000.0})
    assert olmo_hybrid_tiny(rope_parameters={"rope_theta": None})


def test_weights_are_held_once_in_the_serving_dtype():
    paddle.seed(1)
    model = OlmoHybridForCausalLM(olmo_hybrid_tiny(dtype="bfloat16"))
    for name, t in model.state_dict().items():
        f32 = name.endswith(("A_log", "dt_bias"))
        assert t._data.dtype == (jnp.float32 if f32 else jnp.bfloat16), name
    eng = _engine(model)
    assert eng._cache_dtype == jnp.bfloat16
    for name, t in model.state_dict().items():
        assert eng._params[name] is t._data, name
    kv = eng.slot_cache
    assert {a.dtype for a in kv.state} == {jnp.dtype("float32")}
    assert {a.dtype for a in kv.tail} == {jnp.dtype("bfloat16")}
    req = eng.submit(_ids(5), max_new_tokens=6)
    eng.run()
    assert req.done and len(req.tokens) == 6


# --------------------------------------------- 2. chunked vs one position
def _delta_inputs(s, seed, b=2, h=3, dk=8, dv=16):
    rng = np.random.default_rng(seed)

    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    f = np.float32
    return tuple(jnp.asarray(x) for x in (
        unit(rng.normal(size=(b, s, h, dk))).astype(f) * dk ** -0.5,
        unit(rng.normal(size=(b, s, h, dk))).astype(f),
        rng.normal(size=(b, s, h, dv)).astype(f),
        -rng.uniform(0, 2, size=(b, s, h)).astype(f),
        rng.uniform(0, 2, size=(b, s, h)).astype(f),
        rng.normal(size=(b, h, dk, dv)).astype(f)))


def _by_steps(q, k, v, g, beta, state):
    """The recurrence in numpy float64, one position at a time."""
    q, k, v, g, beta, S = (np.asarray(x, np.float64)
                           for x in (q, k, v, g, beta, state))
    outs = []
    for t in range(q.shape[1]):
        S = S * np.exp(g[:, t])[..., None, None]
        u = beta[:, t][..., None] * (
            v[:, t] - np.einsum("bhkv,bhk->bhv", S, k[:, t]))
        S = S + k[:, t][..., :, None] * u[..., None, :]
        outs.append(np.einsum("bhkv,bhk->bhv", S, q[:, t]))
    return np.stack(outs, 1), S


_chunked = jax.jit(gated_delta_chunked, static_argnames=("chunk",))


@pytest.mark.parametrize("chunk", [4, 16, 64])
@pytest.mark.parametrize("length", [1, 2, 3, 5, 17, 64, 100])
def test_chunked_form_is_the_recurrence(length, chunk):
    """From a state that is not zero, at lengths that are not multiples of
    the chunk and shorter than the convolution."""
    args = _delta_inputs(length, seed=length)
    want_o, want_s = _by_steps(*args)
    got_o, got_s = _chunked(*args, chunk=chunk)
    assert got_o.shape == want_o.shape
    assert float(np.abs(got_o - want_o).max()) <= 2e-5
    assert float(np.abs(got_s - want_s).max()) <= 2e-5


def test_step_form_is_the_recurrence():
    q, k, v, g, beta, state = _delta_inputs(6, seed=3)
    want_o, want_s = _by_steps(q, k, v, g, beta, state)
    for t in range(6):
        o, state = gated_delta_step(q[:, t], k[:, t], v[:, t], g[:, t],
                                    beta[:, t], state)
        assert float(np.abs(o - want_o[:, t]).max()) <= 1e-5
    assert float(np.abs(state - want_s).max()) <= 1e-5


@pytest.mark.parametrize("chunk", [4, 64])
def test_positions_with_zero_gates_leave_the_state_alone(chunk):
    """beta = 0 and g = 0 on the last 7 of 20 positions: the state is the one
    after 13, whatever q, k and v hold there."""
    q, k, v, g, beta, state = _delta_inputs(20, seed=9)
    real = (jnp.arange(20) < 13)[None, :, None]
    _, want = _by_steps(q[:, :13], k[:, :13], v[:, :13], g[:, :13],
                        beta[:, :13], state)
    _, got = _chunked(q, k, v, jnp.where(real, g, 0.0),
                      jnp.where(real, beta, 0.0), state, chunk=chunk)
    assert float(np.abs(got - want).max()) <= 2e-5
    o, same = gated_delta_step(q[:, 0], k[:, 0], v[:, 0], 0 * g[:, 0],
                               0 * beta[:, 0], state)
    assert (same == state).all()


def test_delta_calls_are_counted_by_form():
    reg = metrics.default_registry()

    def count(path):
        return reg.counter("delta.calls." + path).value

    c0, s0 = count("chunked"), count("step")
    q, k, v, g, beta, state = _delta_inputs(5, seed=1)
    jax.eval_shape(lambda *a: gated_delta_chunked(*a, chunk=4),
                   q, k, v, g, beta, state)
    jax.eval_shape(gated_delta_step, q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                   beta[:, 0], state)
    assert count("chunked") - c0 == 1 and count("step") - s0 == 1


@pytest.mark.parametrize("real", [0, 1, 2, 5])
def test_conv_tail_keeps_the_last_real_inputs(real):
    tail = jnp.arange(6, dtype=jnp.float32).reshape(1, 3, 2)
    z = 10 + jnp.arange(10, dtype=jnp.float32).reshape(1, 5, 2)
    valid = (jnp.arange(5) < real)[None]
    seen = np.concatenate([np.asarray(tail), np.asarray(z)], 1)
    got = conv_tail(tail, z, valid)
    assert (np.asarray(got) == seen[:, real:real + 3]).all()


# ------------------------------------------- 3. through the serving engine
@pytest.mark.parametrize("length", [7, 8, 9, 15, 16, 17, 31, 32])
def test_padded_prefill_then_decode_through_the_engine(served, reference,
                                                       length):
    """A prompt just under, on and just over a rung, right-padded to its
    rung, then 12 tokens through the cache: every token is the reference's
    choice over the same prefix, and the slot holds the reference's state,
    tail and rows."""
    req = served.submit(_ids(length, seed=length), max_new_tokens=12)
    served.run()
    assert req.done and req.outcome == "length" and len(req.tokens) == 12
    assert req.bucket == next(r for r in (8, 16, 32) if r >= length)
    assert _worst_gap(reference, [req]) <= 1e-3
    assert _held_errors(served, req, reference) <= TOL


# ------------------------------------------------------ 4. the pad is inert
@pytest.mark.parametrize("length", [3, 13, 20])
def test_state_after_a_padded_prefill_is_the_exact_rungs(tiny, length):
    model, _, _ = tiny
    padded, exact = _engine(model, ladder=(32,)), _engine(
        model, ladder=(length, 32))
    prompt = _ids(length, seed=2)
    a = padded.submit(prompt, max_new_tokens=1)
    b = exact.submit(prompt, max_new_tokens=1)
    padded.run(), exact.run()
    assert (a.bucket, b.bucket) == (32, length) and a.tokens == b.tokens
    for mine, theirs in zip(
            padded.slot_cache.state + padded.slot_cache.tail,
            exact.slot_cache.state + exact.slot_cache.tail):
        assert float(jnp.abs(mine[0] - theirs[0]).max()) <= 1e-5
        assert float(jnp.abs(mine[0]).max()) > 0


# --------------------------------- 5. slots at depths, reseated, and idle
def test_slots_at_different_depths_and_a_slot_reused(tiny, reference):
    model, _, _ = tiny
    eng = _engine(model, slots=2)
    prompts = [_ids(n, seed=n) for n in (3, 13, 30, 7, 16, 2)]
    budgets = (16, 5, 16, 9, 16, 12)
    reqs = [eng.submit(p, max_new_tokens=new)
            for p, new in zip(prompts, budgets)]
    eng.run()
    assert [len(r.tokens) for r in reqs] == list(budgets)
    assert all(r.outcome == "length" for r in reqs)
    assert _worst_gap(reference, reqs) <= 1e-3
    # the last request of each slot is what the slot still holds
    last = {r.slot: r for r in reqs}
    assert sorted(last) == [0, 1]
    for r in last.values():
        assert _held_errors(eng, r, reference) <= TOL
    # a fresh engine gives a request that sat in a reused slot the same tokens
    fresh = _engine(model, slots=2)
    alone = fresh.submit(prompts[4], max_new_tokens=budgets[4])
    fresh.run()
    assert alone.tokens == reqs[4].tokens


def test_an_idle_slots_state_does_not_change(tiny):
    model, _, _ = tiny
    eng = _engine(model, slots=2)
    short = eng.submit(_ids(6, seed=1), max_new_tokens=3)
    long = eng.submit(_ids(9, seed=2), max_new_tokens=16)
    while not short.done:
        eng.step()
    assert not long.done and short.slot == 0
    kv = eng.slot_cache
    before = [np.asarray(a[0]) for a in kv.state + kv.tail]
    other = [np.asarray(a[1]) for a in kv.state]
    eng.run()
    assert long.done
    after = [np.asarray(a[0]) for a in kv.state + kv.tail]
    for a, b in zip(before, after):
        assert (a == b).all() and np.abs(a).max() > 0
    assert any((np.asarray(a[1]) != o).any() for a, o in zip(kv.state, other))


# ----------------------------------------------------------- 6. run-ahead
@pytest.mark.parametrize("sampling", [dict(temperature=0.0),
                                      dict(temperature=0.8, top_k=20,
                                           top_p=0.9)])
def test_run_ahead_gives_the_same_tokens_with_state_in_the_carry(
        tiny, monkeypatch, sampling):
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


# ------------------------------------------------------- 7. the refusals
def test_what_state_layers_cannot_do_is_refused_by_name(tiny):
    model, _, _ = tiny
    # the paged layout and its prefix cache (only PagedSlotCache builds one)
    with pytest.raises(ValueError, match=r"paged.*\[0, 1, 2, 4, 5, 6\].*"
                                         r"recurrent state.*position.*"
                                         r"prefix cache.*cannot be cut"):
        _engine(model, kv_layout="paged")
    paddle.seed(0)
    draft = GPTForPretraining(GPTConfig(
        vocab_size=256, hidden_size=32, num_layers=1, num_heads=2,
        max_seq_len=64))
    with pytest.raises(ValueError, match="speculative.*recurrent state.*"
                                         "rewinds"):
        _engine(model, draft_model=draft)
    paddle.seed(0)
    target = GPTForPretraining(GPTConfig(
        vocab_size=256, hidden_size=32, num_layers=1, num_heads=2,
        max_seq_len=64))
    with pytest.raises(ValueError, match="draft model's cache.*recurrent"):
        ServingEngine(target, slot_count=2, ladder=(8,), max_seq_len=32,
                      max_new_cap=8, draft_model=model)
    with pytest.raises(ValueError, match="unknown cache kind"):
        kv_state.spec_of(type("M", (), {"kv_cache_spec": staticmethod(
            lambda n: [("compressed", n, 1, 8)])})(), 16)


# ------------------------------------------------------------ 8. the bytes
def test_cache_bytes_are_the_arithmetic(tiny):
    model, _, _ = tiny
    eng = _engine(model)
    spec = kv_state.spec_of(model, 48)
    assert [s.kind for s in spec] == (["state"] * 3 + ["full"]) * 2
    assert spec[0] == StateLayerSpec("state", 4, 8, 16, 3, 128)
    assert spec[3] == KVLayerSpec("full", 48, 4, 16)
    kv = eng.slot_cache
    assert kv.n_args == 4 and len(kv.args()) == 4
    assert [a.shape for a in kv.k] == [(3, 48, 4, 16)] * 2
    assert [a.shape for a in kv.state] == [(3, 4, 8, 16)] * 6
    assert [a.shape for a in kv.tail] == [(3, 3, 128)] * 6
    assert [a.shape for a in kv.k_stored] == [(3, 48, 4, 128)] * 2
    rows = 2 * 3 * 48 * 4 * 128 * 4 * 2         # k and v as stored, f32
    assert kv.state_bytes() == 6 * 3 * STATE_BYTES
    assert eng.kv_cache_bytes() == rows + 6 * 3 * STATE_BYTES
    assert eng.stats()["kv_cache_bytes"] == eng.kv_cache_bytes()
    assert kv.gauges() == {"state_bytes": 6 * 3 * STATE_BYTES}
    assert eng._donate(1, kv) == (1, 2, 3, 4)


def test_serve_step_record_carries_the_state(tiny):
    """`state_absmax` leaves the decode program with its tokens and reaches
    the `serve_step` record and `serving.state_absmax`; `state_bytes` is the
    cache's gauge."""
    model, _, _ = tiny

    class Sink:
        records = []

        def write(self, rec):
            self.records.append(rec)

        def close(self):
            pass

    eng = _engine(model, sink=Sink())
    eng.submit(_ids(9), max_new_tokens=8)
    eng.run()
    steps = [r for r in Sink.records if r["event"] == "serve_step"]
    assert steps and all(r["state_absmax"] > 0 for r in steps)
    assert all(r["state_bytes"] == 6 * 3 * STATE_BYTES for r in steps)
    kv = eng.slot_cache
    held = max(float(jnp.abs(a[0]).max()) for a in kv.state)
    # the largest over the steps a dispatch fuses, so not under the last's
    assert held * (1 - 1e-6) <= steps[-1]["state_absmax"] < 10 * held
    assert monitor.stat("serving.state_absmax").get() == pytest.approx(
        steps[-1]["state_absmax"])
    assert monitor.stat("serving.state_bytes").get() == 6 * 3 * STATE_BYTES


# ------------------------------------------------------------ 9. the scopes
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
    eng = _engine(model, slots=2, ladder=(8, 16), max_seq_len=32,
                  max_new_cap=8, steps_per_dispatch=2)
    scoped = _lower(eng, which)
    with monkeypatch.context() as m:
        m.setattr(jax, "named_scope", _NullScope)
        bare = _lower(eng, which)
    assert scoped.as_text() == bare.as_text()
    names = set(re.findall(r'op_name="([^"]+)"', scoped.compile().as_text()))
    scopes = {device_trace.scope_of(n)[0] for n in names}
    linear = {"proj", "conv", "gates", "delta_rule", "out_gate", "out",
              "state_write"}
    # (a prefill's fresh cache is the chunk's own rows: no write survives)
    attn = {"qkv", "qk_norm", "core", "out"} | (
        {"cache_write"} if which == "decode" else set())
    assert scopes >= ({f"{which}/linear_attn/{s}" for s in linear}
                      | {f"{which}/attn/{s}" for s in attn}
                      | {f"{which}/{s}" for s in (
                          "embed", "mlp", "final_norm", "lm_head", "sample")})
    if which == "prefill":
        assert f"{which}/state_write" in scopes      # the slot's row copied


# ----------------- a spec with no state layer is the parent's cache, as was
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_slot_cache_without_state_layers_is_as_it_was(dtype):
    """What keeps the three other cells' executables and their compile-cache
    entries: two arguments `k`, `v`, one array a layer each, both donated,
    no gauge, and the same two results taken back."""
    paddle.seed(0)
    model = GPTForPretraining(gpt_tiny())
    spec = kv_state.spec_of(model, 32)
    assert all(isinstance(s, KVLayerSpec) and s.kind == "full" for s in spec)
    kv = kv_state.SlotCache(spec, 3, 32, jnp.dtype(dtype))
    assert kv.n_args == 2
    k, v = kv.args()
    assert k is kv.k_stored and v is kv.v_stored
    assert kv.state == kv.tail == []
    assert [a.shape for a in kv.k] == [a.shape for a in kv.v] == [
        (3, 32, s.kv_heads, s.head_dim) for s in spec]
    assert [a.shape for a in k] == [a.shape for a in v] == [
        (3, 32) + stored_dims(s.kv_heads, s.head_dim) for s in spec]
    assert {a.dtype for a in k + v} == {jnp.dtype(dtype)}
    assert ServingEngine._donate(1, kv) == (1, 2)
    assert kv.gauges() == {} and kv.state_bytes() == 0
    assert kv.nbytes() == 2 * sum(int(a.size) * a.dtype.itemsize for a in k)
    handles = kv.views(kv.args(), jnp.zeros((3,), jnp.int32),
                       jnp.ones((3,), bool))
    assert not any(isinstance(h, SlotState) for h in handles)
    out = kv.absorb(kv.args(), handles, None)
    assert len(out) == 2 and all(a is b for a, b in zip(out[0], k))
    kv.take(out)
    assert kv.k_stored is out[0] and kv.v_stored is out[1]
    eng = ServingEngine(model, slot_count=2, ladder=(8,), max_seq_len=32,
                        max_new_cap=8)
    assert eng.slot_cache.n_args == 2 and eng._donate(1, eng.slot_cache) == (1, 2)
