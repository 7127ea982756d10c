"""The Pallas kernel of a decode step's attention core over a `full` layer of
the slot cache (ops/pallas/slot_decode.py) through its door
(ops/slot_attention.py) against the models' plain core on the same inputs,
on the CPU in interpret mode at small shapes; which calls take it
(`supported`); its two counters; and a tiny GPT-2 and Olmo-Hybrid
`ServingEngine` with the kernel forced beside the plain engine.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import (GPTConfig, GPTForPretraining,
                               OlmoHybridForCausalLM, olmo_hybrid_tiny)
from paddle_tpu.models.afmoe import _attend
from paddle_tpu.nn.kv_cache import (ChunkKV, RingKV, SlotKV, logical_rows,
                                    stored_dims)
from paddle_tpu.observability import metrics
from paddle_tpu.ops import slot_attention
from paddle_tpu.ops.pallas import _common, slot_decode
from paddle_tpu.serving import ServingEngine

T = 16              # the block here; the chip's is slot_decode.BLOCK_ROWS
ROWS = 4 * T
# (kv heads, head size) in small, as `stored_dims` pads them to (8, 128):
# GPT-2 large's 20 heads of 64 in 24 x 128, Olmo-Hybrid's 30 of 128 in 32,
# and rows with no pad at all
SHAPES = {"head 64 in 128, 5 heads in 8": (5, 64),
          "6 heads in 8": (6, 128),
          "dense": (8, 128)}
LENGTHS = [1, T - 1, T, T + 1, ROWS]


@pytest.fixture
def kernel(monkeypatch):
    """The kernel in interpret mode, in blocks of T rows."""
    monkeypatch.setattr(slot_decode, "_target", lambda: "interpret")
    monkeypatch.setattr(slot_decode, "BLOCK_ROWS", T)


def _count(form):
    return metrics.default_registry().counter("attn.calls." + form).value


def _inputs(lengths, kv_heads, d, dtype):
    """q, and the handle `update` would return: the arrays as stored, the
    offset counting the step's own row."""
    b = len(lengths)
    heads, width = stored_dims(kv_heads, d)
    keys = jax.random.split(jax.random.key(0), 3)
    pad = [(0, 0), (0, 0), (0, heads - kv_heads), (0, width - d)]
    q = jax.random.normal(keys[0], (b, 1, kv_heads, 1, d), dtype)
    k, v = (jnp.pad(jax.random.normal(key, (b, ROWS, kv_heads, d), dtype),
                    pad) for key in keys[1:])
    return q, SlotKV(k, v, jnp.asarray(lengths, jnp.int32))


def _plain(q, cache):
    """The models' plain core over the rows `update` hands them."""
    kv_heads, d = q.shape[2], q.shape[4]
    held = jnp.arange(cache.k.shape[1])[None, None, :]
    return _attend(q, logical_rows(cache.k, kv_heads, d),
                   logical_rows(cache.v, kv_heads, d),
                   held < cache.offset[:, None, None])


def _gap(a, b):
    return float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max())


# ------------------------------------------ 1. the kernel vs the plain core
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 4e-2)])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_kernel_is_the_plain_core(kernel, shape, dtype, tol):
    """Lengths 1, T - 1, T, T + 1 and every row, mixed in one batch, on the
    three stored shapes. float32 to rounding; bf16 within a rounding of
    results of size 4 (`p` is rounded before the division by the sum here
    and after it there)."""
    q, cache = _inputs(LENGTHS, *SHAPES[shape], dtype)
    before = _count("slot"), _count("slot_kernel")
    got = slot_attention.decode_core(q, cache)
    assert (_count("slot"), _count("slot_kernel")) == (before[0] + 1,
                                                       before[1] + 1)
    want = _plain(q, cache)
    assert got.shape == want.shape == q.shape
    assert got.dtype == want.dtype == dtype
    assert _gap(got, want) <= tol


@pytest.mark.parametrize("fill", [float("nan"), 1e30])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_rows_past_a_length_do_not_reach_the_result(kernel, shape, fill):
    q, cache = _inputs([1, T - 1, T, T + 1, 3 * T + 5], *SHAPES[shape],
                       jnp.float32)
    clean = slot_attention.decode_core(q, cache)
    past = (jnp.arange(ROWS)[None, :, None, None]
            >= cache.offset[:, None, None, None])
    dirty = slot_attention.decode_core(q, SlotKV(
        jnp.where(past, fill, cache.k), jnp.where(past, fill, cache.v),
        cache.offset))
    assert bool(jnp.isfinite(dirty).all())
    assert _gap(dirty, clean) == 0.0


def test_an_idle_slot_reads_its_first_row(kernel):
    """A slot nothing is seated in steps at offset 0 (1 with its own row):
    it costs one block and returns row 0's values, whatever lies behind."""
    q, cache = _inputs([1, 1, 2 * T], 6, 128, jnp.float32)
    got = slot_attention.decode_core(q, cache)
    want = logical_rows(cache.v, 6, 128)[:2, 0][:, None, :, None]
    assert _gap(got[:2], want) <= 1e-6


def test_lengths_are_clipped_as_the_write_is(kernel):
    """`SlotKV.update` clips its write to the last row; an offset past the
    rows sees them all, one under 1 sees row 0."""
    q, cache = _inputs([ROWS, 1], 8, 128, jnp.float32)
    inside = slot_attention.decode_core(q, cache)
    outside = slot_attention.decode_core(q, SlotKV(
        cache.k, cache.v, jnp.asarray([ROWS + 7, 0], jnp.int32)))
    assert _gap(inside, outside) == 0.0


# ------------------------------------------------- 2. who takes which path
def _handle(kind=SlotKV, rows=ROWS, heads=8, width=128, offset=None, b=3):
    k = jnp.zeros((b, rows, heads, width))
    offset = jnp.zeros((b,), jnp.int32) if offset is None else offset
    return kind(k, k, offset)


REFUSED = {
    "a ring": dict(cache=lambda: _handle(RingKV)),
    "a chunk cache": dict(cache=lambda: ChunkKV(
        jnp.zeros((3, ROWS, 8, 128)), jnp.zeros((3, ROWS, 8, 128)),
        jnp.int32(5))),
    "a scalar offset": dict(cache=lambda: _handle(offset=jnp.int32(5))),
    "one offset for the batch": dict(
        cache=lambda: _handle(offset=jnp.zeros((1,), jnp.int32))),
    "a chunk of two": dict(s=2),
    "four query heads a key head": dict(groups=4),
    "rows 64 wide": dict(cache=lambda: _handle(width=64)),
    "four stored heads": dict(cache=lambda: _handle(heads=4), kv_heads=4),
    "rows the block does not divide": dict(
        cache=lambda: _handle(rows=ROWS + 8)),
    "a backend with no kernels": dict(target=None),
}


@pytest.mark.parametrize("name", sorted(REFUSED))
def test_supported_refuses(kernel, monkeypatch, name):
    case = dict(s=1, groups=1, kv_heads=8, cache=_handle, target="interpret")
    assert slot_decode.supported((3, 1, 8, 1, 128), _handle())
    case.update(REFUSED[name])
    monkeypatch.setattr(slot_decode, "_target", lambda: case["target"])
    q_shape = (3, case["s"], case["kv_heads"], case["groups"], 128)
    cache = case["cache"]()
    assert not slot_decode.supported(q_shape, cache)
    # and the door hands the call back to the model's plain core
    before = _count("slot_kernel")
    assert slot_attention.decode_core(jnp.zeros(q_shape), cache) is None
    assert _count("slot_kernel") == before


def test_supported_refuses_a_program_over_a_mesh(kernel):
    case = (2, 1, 8, 1, 128), _handle(b=2)
    assert slot_decode.supported(*case)
    devices = np.asarray(jax.devices()[:2])
    with _common.mesh_scope(jax.sharding.Mesh(devices, ("mp",))):
        assert not slot_decode.supported(*case)
    with _common.mesh_scope(jax.sharding.Mesh(devices[:1], ("mp",))):
        assert slot_decode.supported(*case)
    with jax.set_mesh(jax.sharding.Mesh(devices, ("mp",))):
        assert not slot_decode.supported(*case)


def test_the_cpu_takes_the_plain_core_unasked():
    assert slot_decode._target() is None
    q, cache = _inputs([3, T], 8, 128, jnp.float32)
    before = _count("slot"), _count("slot_kernel")
    assert slot_attention.decode_core(q, cache) is None
    assert (_count("slot"), _count("slot_kernel")) == (before[0] + 1,
                                                       before[1])


@pytest.mark.parametrize("case", ["a ring", "a chunk of two",
                                  "a chunk cache"])
def test_only_a_decode_step_over_slot_rows_counts(kernel, case):
    """`attn.calls.slot` counts what the bound could serve: one query a
    slot over a `SlotKV`. Everything else is the models' own business."""
    q_shape, cache = {
        "a ring": ((3, 1, 8, 1, 128), _handle(RingKV)),
        "a chunk of two": ((3, 2, 8, 1, 128), _handle()),
        "a chunk cache": ((3, 1, 8, 1, 128), ChunkKV(
            jnp.zeros((3, ROWS, 8, 128)), jnp.zeros((3, ROWS, 8, 128)),
            jnp.int32(5))),
    }[case]
    before = _count("slot"), _count("slot_kernel")
    assert slot_attention.decode_core(jnp.zeros(q_shape), cache) is None
    assert (_count("slot"), _count("slot_kernel")) == before


# --------------------------------- 3. the engines, the kernel forced
def _gpt():
    # 5 heads of 32 stored in 8 x 128: both pads of GPT-2 large's rows
    return GPTForPretraining(GPTConfig(
        vocab_size=1024, hidden_size=160, num_layers=2, num_heads=5,
        max_seq_len=128))


def _olmo():
    # 8 heads of 8 stored in 8 x 128; full attention in layers 3 and 7
    return OlmoHybridForCausalLM(olmo_hybrid_tiny(
        num_attention_heads=8, num_key_value_heads=8))


FAMILIES = {"gpt": (0, _gpt, 1024, 2), "olmo": (5, _olmo, 256, 2)}


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_the_kernel_forced_gives_the_plain_engines_tokens(monkeypatch,
                                                          family):
    """Slots at different depths and one seated again, every decode step's
    full layers through the kernel (interpreted, in blocks of 16 of the 48
    rows): greedy tokens are the plain engine's and the slots hold the same
    rows; each decode program traced counts one kernel a full layer."""
    seed, make, vocab, full_layers = FAMILIES[family]
    paddle.seed(seed)
    model = make()
    model.eval()
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, vocab, (n,), dtype=np.int64)
               for n in (3, 13, 30, 7)]
    budgets = (8, 5, 8, 8)      # the fourth request takes the second's slot

    def serve():
        eng = ServingEngine(model, slot_count=3, ladder=(8, 16, 32),
                            max_seq_len=48, max_new_cap=8,
                            steps_per_dispatch=4)
        reqs = [eng.submit(p, max_new_tokens=new, temperature=0.0)
                for p, new in zip(prompts, budgets)]
        eng.run()
        assert all(r.done and r.outcome == "length" for r in reqs)
        return eng, [r.tokens for r in reqs]

    plain_eng, plain = serve()
    monkeypatch.setattr(slot_decode, "_target", lambda: "interpret")
    monkeypatch.setattr(slot_decode, "BLOCK_ROWS", 16)
    before = _count("slot"), _count("slot_kernel")
    eng, forced = serve()
    traced = _count("slot") - before[0], _count("slot_kernel") - before[1]
    assert traced[0] == traced[1] > 0 and traced[1] % full_layers == 0
    assert forced == plain
    for mine, theirs in zip(eng.slot_cache.k_stored + eng.slot_cache.v_stored,
                            plain_eng.slot_cache.k_stored
                            + plain_eng.slot_cache.v_stored):
        assert float(jnp.abs(mine - theirs).max()) <= 1e-5
