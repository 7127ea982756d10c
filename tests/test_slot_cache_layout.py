"""What a slot holds and what the engine emits do not depend on how the slot
cache is stored (nn/kv_cache.py `stored_dims`: heads and head size padded to
the shape whose default device layout the decode loop keeps; every tiny model
here is padded, 16 or 32 wide heads to 128).

Over tiny GPT-2, `afmoe` (a ring of 8 rows, shorter than two of the rungs) and
`olmo_hybrid` (states beside rows):

- the tokens of a fixed set of requests equal the ones recorded from the
  commit before the cache was padded (`RECORDED`; greedy and sampled; to
  record again: `python tests/test_slot_cache_layout.py`);
- after a prefill at each rung, and after 16 decode steps,
  `slot_cache.k[l][slot]` read on the host as [rows, kv_heads, head_dim]
  holds, at row `p % rows`, the row `ChunkKV` holds for position p of the
  same tokens run alone: what benchmarks/runners/serve_afmoe.py
  (`row_errors`) and serve_hybrid.py (`held_errors`) index; the pad of the
  stored arrays stays zero;
- a retired slot's tip write, and a slot that filled up at
  `max_seq_len - 1`, leave every row another reader sees as it was.
"""
import json
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import GPTForPretraining, gpt_tiny
from paddle_tpu.models.afmoe import AfmoeForCausalLM, afmoe_tiny
from paddle_tpu.models.olmo_hybrid import (OlmoHybridForCausalLM,
                                           olmo_hybrid_tiny)
from paddle_tpu.nn.kv_cache import logical_rows, stored_dims
from paddle_tpu.serving import ServingEngine
from paddle_tpu.utils import hlo_inspect

MODELS = {
    "gpt2": lambda: GPTForPretraining(gpt_tiny()),
    "afmoe": lambda: AfmoeForCausalLM(afmoe_tiny()),
    "olmo_hybrid": lambda: OlmoHybridForCausalLM(olmo_hybrid_tiny()),
}
LADDER, T, NEW = (4, 16, 32), 48, 16
# (prompt length, new tokens): one prompt under each rung, one longer than
# afmoe's ring, more requests than slots so that slots are seated twice
REQUESTS = ((3, 9), (13, 16), (29, 12), (7, 5), (20, 16))
SAMPLING = {"greedy": dict(temperature=0.0),
            "sampled": dict(temperature=0.9, top_k=40, top_p=0.95)}

# recorded at commit 109846e (the parent of the pinned layout), on the CPU
RECORDED = {
    "afmoe": {
        "greedy": [
            [53, 53, 53, 53, 53, 53, 53, 49, 224],
            [64, 111, 183, 111, 79, 183, 183, 172, 183, 45, 231,
             231, 62, 21, 31, 32],
            [119, 57, 43, 200, 212, 212, 212, 220, 21, 43, 119,
             226],
            [185, 195, 248, 165, 195],
            [20, 20, 132, 132, 73, 100, 114, 188, 226, 20, 20,
             20, 62, 231, 28, 111],
        ],
        "sampled": [
            [138, 134, 119, 53, 58, 255, 228, 57, 130],
            [66, 64, 154, 25, 120, 240, 84, 227, 133, 104, 129,
             84, 42, 137, 6, 253],
            [85, 44, 195, 57, 120, 212, 44, 106, 245, 175, 92,
             57],
            [222, 219, 245, 4, 169],
            [21, 186, 132, 168, 194, 12, 74, 123, 40, 122, 40,
             129, 197, 78, 198, 85],
        ],
    },
    "gpt2": {
        "greedy": [
            [72, 72, 72, 860, 860, 27, 912, 416, 912],
            [315, 872, 872, 872, 872, 773, 773, 872, 872, 872,
             872, 872, 872, 315, 315, 315],
            [654, 722, 352, 773, 42, 24, 844, 844, 953, 722,
             844, 844],
            [884, 884, 884, 884, 884],
            [773, 773, 654, 872, 24, 872, 654, 654, 864, 773,
             872, 872, 773, 654, 872, 844],
        ],
        "sampled": [
            [853, 94, 852, 27, 966, 860, 156, 690, 992],
            [987, 814, 208, 182, 373, 844, 990, 992, 349, 814,
             623, 880, 732, 417, 697, 684],
            [612, 24, 654, 626, 864, 524, 773, 912, 208, 839,
             24, 654],
            [789, 722, 884, 907, 912],
            [77, 669, 575, 460, 992, 410, 654, 654, 135, 54,
             872, 872, 197, 182, 24, 152],
        ],
    },
    "olmo_hybrid": {
        "greedy": [
            [92, 77, 241, 180, 46, 116, 73, 96, 163],
            [53, 5, 27, 180, 120, 230, 78, 70, 191, 246, 170,
             211, 65, 72, 114, 53],
            [252, 114, 255, 246, 120, 209, 26, 114, 174, 209,
             184, 237],
            [80, 105, 116, 220, 68],
            [73, 6, 205, 53, 234, 152, 235, 106, 140, 241, 96,
             166, 98, 0, 200, 206],
        ],
        "sampled": [
            [70, 1, 206, 134, 58, 172, 228, 1, 224],
            [66, 85, 61, 250, 120, 52, 241, 62, 60, 157, 225,
             255, 202, 137, 106, 129],
            [98, 98, 91, 134, 96, 249, 44, 106, 189, 33, 32, 80],
            [107, 117, 44, 229, 213],
            [243, 186, 216, 168, 201, 133, 74, 29, 14, 202, 232,
             29, 197, 78, 38, 46],
        ],
    },
}


def build(name):
    paddle.seed(0)
    model = MODELS[name]()
    model.eval()
    return model


def engine(model, slots=3, **kw):
    args = dict(slot_count=slots, ladder=LADDER, max_seq_len=T,
                max_new_cap=NEW, steps_per_dispatch=4)
    args.update(kw)
    return ServingEngine(model, **args)


def prompt(n, seed):
    return np.random.default_rng(seed).integers(0, 256, (n,), dtype=np.int64)


def served_tokens(name, family):
    eng = engine(build(name))
    reqs = [eng.submit(prompt(n, i), max_new_tokens=new, seed=100 + i,
                       **SAMPLING[family])
            for i, (n, new) in enumerate(REQUESTS)]
    eng.run()
    return [[int(t) for t in r.tokens] for r in reqs]


@pytest.fixture(scope="module", params=sorted(MODELS))
def served(request):
    """(name, model, engine): one model a module run, its engine shared by
    the cases that read rows (each seats its own requests)."""
    model = build(request.param)
    return request.param, model, engine(model)


def alone(eng, model, ids):
    """What a `ChunkKV` (and a `SlotState`) a layer holds after `ids` run
    alone through the model, as the engine's prefill runs a request: one
    pair of arrays a layer of the spec, rows [len(ids), kv_heads, head_dim]
    or (state, tail)."""
    kv, n = eng.slot_cache, len(ids)
    width = -(-n // 16) * 16
    padded = np.zeros((1, width), np.int64)
    padded[0, :n] = ids

    @jax.jit
    def run(params, padded):
        caches = kv.prefill_views(None, width, jnp.int32(n), jnp.int32(0))
        return ServingEngine._backbone(model, params, padded, caches)[1]

    out = []
    for spec, h in zip(kv.spec, run(eng._params, jnp.asarray(padded))):
        out.append((np.asarray(h.state[0]), np.asarray(h.tail[0]))
                   if spec.kind == "state"
                   else (np.asarray(h.k[0, :n]), np.asarray(h.v[0, :n])))
    return out


def assert_slot_holds(eng, model, slot, ids, tol=2e-4):
    """Slot `slot` holds the `len(ids)` positions of `ids`: every row a
    reader may still see (row `p % rows` holds position p; the row position
    `held` will take is left out as `row_errors` leaves it out, a retired
    slot has written its tip there), every state."""
    kv, held = eng.slot_cache, len(ids)
    rows, states = iter(zip(kv.k, kv.v)), iter(zip(kv.state, kv.tail))
    padded = iter(zip(kv.k_stored, kv.v_stored))
    for spec, ref in zip(kv.spec, alone(eng, model, ids)):
        if spec.kind == "state":
            for mine, want in zip(next(states), ref):
                np.testing.assert_allclose(np.asarray(mine[slot]), want,
                                           rtol=tol, atol=tol)
            continue
        for mine, want, stored in zip(next(rows), ref, next(padded)):
            size = mine.shape[1]
            assert stored.shape == (kv.k_stored[0].shape[0], spec.rows,
                                    *stored_dims(spec.kv_heads,
                                                 spec.head_dim))
            whole = np.asarray(stored[slot])
            assert not whole[:, spec.kv_heads:].any()
            assert not whole[:, :, spec.head_dim:].any()
            assert mine.shape[1:] == (spec.rows, spec.kv_heads,
                                      spec.head_dim)
            at = np.arange(max(0, held + 1 - size), held)
            got = np.asarray(mine[slot], np.float32)[at % size]
            np.testing.assert_allclose(got, want[at], rtol=tol, atol=tol)
            if spec.kind == "full":     # the form serve_hybrid.py reads
                np.testing.assert_allclose(
                    np.asarray(mine[slot], np.float32)[:held], want,
                    rtol=tol, atol=tol)


@pytest.mark.parametrize("dims, stored", [
    ((20, 64), (24, 128)),      # GPT-2 large: what the loop's tiles pad to
    ((30, 128), (32, 128)),     # Olmo-Hybrid: heads to the 8 sublanes
    ((4, 128), (4, 128)),       # Trinity: a tile of its own, nothing to pad
    ((8, 128), (8, 128)), ((1, 64), (1, 128)), ((2, 16), (2, 128)),
    ((3, 128), (8, 128)), ((25, 64), (32, 128)), ((16, 256), (16, 256)),
])
def test_stored_dims(dims, stored):
    assert stored_dims(*dims) == stored
    a = jnp.zeros((2, 3) + stored)
    view = logical_rows(a, *dims)
    assert view.shape == (2, 3) + dims
    assert (view is a) == (dims == stored)


@pytest.mark.parametrize("family", sorted(SAMPLING))
@pytest.mark.parametrize("name", sorted(MODELS))
def test_tokens_equal_the_recorded_ones(name, family):
    assert served_tokens(name, family) == RECORDED[name][family]


@pytest.mark.parametrize("rung", LADDER)
def test_slot_holds_chunk_rows_after_a_prefill(served, rung):
    _, model, eng = served
    ids = prompt(rung - 1, 10 + rung)
    req = eng.submit(ids, max_new_tokens=1, temperature=0.0)
    eng.run()
    assert req.done and len(req.tokens) == 1
    assert_slot_holds(eng, model, req.slot, ids)


def test_slot_holds_chunk_rows_after_16_decode_steps(served):
    """The positions the decode steps wrote, the ring wrapped twice for
    afmoe: prompt and generated tokens run alone give the same rows."""
    _, model, _ = served
    eng = engine(model, ladder=LADDER[:2], max_new_cap=NEW + 1)
    ids = prompt(13, 7)
    req = eng.submit(ids, max_new_tokens=NEW + 1, temperature=0.0)
    eng.run()
    assert len(req.tokens) == NEW + 1
    ids = np.concatenate([ids, np.asarray(req.tokens[:-1], np.int64)])
    assert_slot_holds(eng, model, req.slot, ids)


def test_retired_slot_tip_write_is_inert(served):
    """A slot retired early keeps being stepped as an idle row while its
    neighbour decodes on: what it held when it retired is still there."""
    _, model, eng = served
    short, long = prompt(6, 1), prompt(9, 2)
    a = eng.submit(short, max_new_tokens=3, temperature=0.0)
    b = eng.submit(long, max_new_tokens=NEW, temperature=0.0)
    eng.run()
    assert len(a.tokens) == 3 and len(b.tokens) == NEW and a.slot != b.slot
    for req, ids in ((a, short), (b, long)):
        ids = np.concatenate([ids, np.asarray(req.tokens[:-1], np.int64)])
        assert_slot_holds(eng, model, req.slot, ids)


def test_full_slot_stays_inside_the_cache(served):
    """The longest request the engine admits (a whole top rung, the whole
    budget) ends with its offset at `max_seq_len - 1`; stepped on as an idle
    row it writes its tip there and nowhere else: its neighbour's tokens
    and rows are those of the neighbour served alone."""
    _, model, eng = served
    filler, other = prompt(LADDER[-1], 3), prompt(11, 4)
    b = eng.submit(other, max_new_tokens=NEW, temperature=0.0)
    a = eng.submit(filler, max_new_tokens=NEW, temperature=0.0)
    eng.run()
    assert len(a.tokens) == NEW and len(filler) + NEW == T
    c = eng.submit(other, max_new_tokens=NEW, temperature=0.0)
    eng.run()               # decodes beside the full slot, now an idle row
    assert c.slot != a.slot
    assert b.tokens == c.tokens and len(b.tokens) == NEW
    single = engine(model, slots=1)
    d = single.submit(other, max_new_tokens=NEW, temperature=0.0)
    single.run()
    assert b.tokens == d.tokens
    ids = np.concatenate([other, np.asarray(c.tokens[:-1], np.int64)])
    assert_slot_holds(eng, model, c.slot, ids)
    ids = np.concatenate([filler, np.asarray(a.tokens[:-1], np.int64)])
    assert len(ids) == T - 1
    assert_slot_holds(eng, model, a.slot, ids)


HLO = """HloModule jit_step_chunk, is_scheduled=true

%fused_computation.1 (param_0.1: bf16[4,8,20,64]) -> bf16[4,8,20,64] {
  %param_0.1 = bf16[4,8,20,64]{3,2,1,0:T(8,128)(2,1)} parameter(0)
  ROOT %copy.9 = bf16[4,8,20,64]{3,1,2,0:T(8,128)(2,1)} copy(%param_0.1)
}

%fused_computation.2 (param_0.2: bf16[4,8,20,64], param_1.2: bf16[4,20,64]) -> f32[4,20,8] {
  %param_0.2 = bf16[4,8,20,64]{3,2,1,0:T(8,128)(2,1)} parameter(0)
  %fusion.7 = bf16[4,8,20,64]{3,1,2,0:T(8,128)(2,1)} fusion(%param_0.2), kind=kLoop, calls=%fused_computation.1
  %param_1.2 = bf16[4,20,64]{2,1,0:T(8,128)(2,1)} parameter(1)
  ROOT %convolution.1 = f32[4,20,8]{2,1,0:T(8,128)} convolution(%fusion.7, %param_1.2), dim_labels=0b1f_01oi->01bf
}

%body.1 (arg.1: (bf16[4,8,20,64], bf16[4,20,64])) -> (bf16[4,8,20,64], bf16[4,20,64]) {
  %arg.1 = (bf16[4,8,20,64]{3,2,1,0:T(8,128)(2,1)}, bf16[4,20,64]{2,1,0:T(8,128)(2,1)}) parameter(0)
  %get-tuple-element.1 = bf16[4,8,20,64]{3,2,1,0:T(8,128)(2,1)} get-tuple-element(%arg.1), index=0
  %get-tuple-element.2 = bf16[4,20,64]{2,1,0:T(8,128)(2,1)} get-tuple-element(%arg.1), index=1
  %copy-start.1 = (bf16[4,8,20,64]{3,2,1,0:T(8,128)(2,1)}, bf16[4,8,20,64]{3,2,1,0:T(8,128)(2,1)S(1)}, u32[]{:S(2)}) copy-start(%get-tuple-element.1)
  %fusion.8 = f32[4,20,8]{2,1,0:T(8,128)} fusion(%get-tuple-element.1, %get-tuple-element.2), kind=kOutput, calls=%fused_computation.2
  ROOT %tuple.1 = (bf16[4,8,20,64]{3,2,1,0:T(8,128)(2,1)}, bf16[4,20,64]{2,1,0:T(8,128)(2,1)}) tuple(%get-tuple-element.1, %get-tuple-element.2)
}

%cond.1 (arg.2: (bf16[4,8,20,64], bf16[4,20,64])) -> pred[] {
  %arg.2 = (bf16[4,8,20,64]{3,2,1,0:T(8,128)(2,1)}, bf16[4,20,64]{2,1,0:T(8,128)(2,1)}) parameter(0)
  ROOT %constant.1 = pred[] constant(false)
}

ENTRY %main.1 (k.1: bf16[4,8,20,64], q.1: bf16[4,20,64]) -> bf16[4,8,20,64] {
  %k.1 = bf16[4,8,20,64]{1,3,2,0:T(8,128)(2,1)} parameter(0)
  %q.1 = bf16[4,20,64]{2,1,0:T(8,128)(2,1)} parameter(1)
  %copy.1 = bf16[4,8,20,64]{3,2,1,0:T(8,128)(2,1)} copy(%k.1)
  %tuple.2 = (bf16[4,8,20,64]{3,2,1,0:T(8,128)(2,1)}, bf16[4,20,64]{2,1,0:T(8,128)(2,1)}) tuple(%copy.1, %q.1)
  %while.1 = (bf16[4,8,20,64]{3,2,1,0:T(8,128)(2,1)}, bf16[4,20,64]{2,1,0:T(8,128)(2,1)}) while(%tuple.2), condition=%cond.1, body=%body.1
  %get-tuple-element.3 = bf16[4,8,20,64]{3,2,1,0:T(8,128)(2,1)} get-tuple-element(%while.1), index=0
  ROOT %copy.2 = bf16[4,8,20,64]{1,3,2,0:T(8,128)(2,1)} copy(%get-tuple-element.3)
}
"""


def test_boundary_report_reads_the_cache_round_a_loop():
    """tools/decode_hlo_probe.py's reading of a compiled decode program, on
    a text of the parent's shape: the entry layout and the loop's differ,
    one copy in and one out, a prefetch inside that changes no layout, and
    an operand read in another order INSIDE a fusion, which is no pass
    over memory and is not counted."""
    r = hlo_inspect.boundary_report(HLO, {("bf16", (4, 8, 20, 64))})
    assert r["entry_layouts"] == {"bf16[4,8,20,64]": {"1,3,2,0": 1}}
    assert r["in_loop_layouts"] == {"bf16[4,8,20,64]": {"3,2,1,0": 1}}
    size = round(2 * 4 * 8 * 20 * 64 * 2 / 2 ** 30, 3)
    assert r["cache_sized_copies"] == {
        "outside": {"count": 2, "GiB": size},
        "inside": {"count": 0, "GiB": 0.0},
        "inside_prefetch": {"count": 1, "GiB": round(size / 2, 3)}}
    # the same program with the entry in the loop's layout: nothing left
    pinned = HLO.replace("{1,3,2,0:", "{3,2,1,0:").replace(
        "%copy.1 = bf16[4,8,20,64]{3,2,1,0:T(8,128)(2,1)} copy(%k.1)",
        "%copy.1 = bf16[4,8,20,64]{3,2,1,0:T(8,128)(2,1)} bitcast(%k.1)"
    ).replace("ROOT %copy.2 = bf16[4,8,20,64]{3,2,1,0:T(8,128)(2,1)} copy(",
              "ROOT %copy.2 = bf16[4,8,20,64]{3,2,1,0:T(8,128)(2,1)} bitcast(")
    r = hlo_inspect.boundary_report(pinned, {("bf16", (4, 8, 20, 64))})
    assert r["cache_sized_copies"]["outside"]["count"] == 0
    assert r["entry_layouts"] == r["in_loop_layouts"]


if __name__ == "__main__":      # record: run on the commit to compare with
    json.dump({name: {family: served_tokens(name, family)
                      for family in sorted(SAMPLING)}
               for name in sorted(MODELS)}, sys.stdout)
