"""The Pallas kernels of a decode step's attention core over a `full` layer
of the slot cache (ops/pallas/slot_decode.py) and of a block step's
(ops/pallas/block_decode.py: a block of 4 queries a slot, grouped query
heads) through their door (ops/slot_attention.py) against the models' plain
cores on the same inputs, on the CPU in interpret mode at small shapes;
which calls take them (`supported`); their counters; and a tiny GPT-2 and
Olmo-Hybrid `ServingEngine` with the kernel forced beside the plain engine
(SDAR's is in tests/test_sdar.py).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import (GPTConfig, GPTForPretraining,
                               OlmoHybridForCausalLM, olmo_hybrid_tiny)
from paddle_tpu.models.afmoe import _attend
from paddle_tpu.nn.kv_cache import (ChunkKV, RingKV, SlotKV, block_causal_mask,
                                    logical_rows, stored_dims)
from paddle_tpu.observability import metrics
from paddle_tpu.ops import slot_attention
from paddle_tpu.ops.pallas import _common, block_decode, slot_decode
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving.kv_pages import PagedLayerCache

T = 16              # the block here; the chips' are the BLOCK_ROWS
ROWS = 4 * T
B = 4               # a block step's queries a slot (SDAR's block length)
# (kv heads, head size, queries a slot, query heads a key head). A decode
# step's in small, as `stored_dims` pads them to (8, 128): GPT-2 large's 20
# heads of 64 in 24 x 128, Olmo-Hybrid's 30 of 128 in 32, and rows with no
# pad at all. A block step's: SDAR's 4 heads of 128 (stored as they are)
# with 8 query heads each, 5 heads of 64 stored in 8 x 128 with 2, and one
# head of 128 with 4
SHAPES = {"head 64 in 128, 5 heads in 8": (5, 64, 1, 1),
          "6 heads in 8": (6, 128, 1, 1),
          "dense": (8, 128, 1, 1),
          "block step, 4 heads, 8 groups": (4, 128, B, 8),
          "block step, head 64 in 128, 5 heads in 8, 2 groups": (5, 64, B, 2),
          "block step, one head, 4 groups": (1, 128, B, 4)}


def _lengths(s, *extra):
    """Rows a slot sees: a decode step's 1, T - 1, T, T + 1, a block
    step's to the end of a block (offsets on a block's edge), and all."""
    step = 1 if s == 1 else B
    return [step, T - step, T, T + step, *extra, ROWS]


@pytest.fixture
def kernel(monkeypatch):
    """The kernels in interpret mode, in blocks of T rows."""
    for module in (slot_decode, block_decode):
        monkeypatch.setattr(module, "_target", lambda: "interpret")
        monkeypatch.setattr(module, "BLOCK_ROWS", T)


def _count(form):
    return metrics.default_registry().counter("attn.calls." + form).value


def _inputs(lengths, kv_heads, d, dtype, s=1, groups=1):
    """q, and the handle `update` would return: the arrays as stored, the
    offset counting the step's own rows."""
    b = len(lengths)
    heads, width = stored_dims(kv_heads, d)
    keys = jax.random.split(jax.random.key(0), 3)
    pad = [(0, 0), (0, 0), (0, heads - kv_heads), (0, width - d)]
    q = jax.random.normal(keys[0], (b, s, kv_heads, groups, d), dtype)
    k, v = (jnp.pad(jax.random.normal(key, (b, ROWS, kv_heads, d), dtype),
                    pad) for key in keys[1:])
    return q, SlotKV(k, v, jnp.asarray(lengths, jnp.int32))


def _core(q, cache):
    """The door as the models call it: a block step states its block."""
    s = q.shape[1]
    return slot_attention.decode_core(q, cache, block=B if s > 1 else None)


def _plain(q, cache):
    """The models' plain core over the rows `update` hands them: a decode
    step's causal mask, a block step's block-causal one (its queries are
    the block that ends at the offset)."""
    kv_heads, s, d = q.shape[2], q.shape[1], q.shape[4]
    held = jnp.arange(cache.k.shape[1])[None, None, :]
    if s == 1:
        mask = held < cache.offset[:, None, None]
    else:
        pos = cache.offset[:, None] - s + jnp.arange(s)[None, :]
        mask = block_causal_mask(held, pos, B)
    return _attend(q, logical_rows(cache.k, kv_heads, d),
                   logical_rows(cache.v, kv_heads, d), mask)


def _forms(s):
    """The counters of the door's form for queries of s a slot."""
    form = "slot" if s == 1 else "block"
    return form, form + "_kernel"


def _gap(a, b):
    return float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max())


# ------------------------------------------ 1. the kernel vs the plain core
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 4e-2)])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_kernel_is_the_plain_core(kernel, shape, dtype, tol):
    """Lengths 1, T - 1, T, T + 1 (a block step's 4, T - 4, T, T + 4) and
    every row, mixed in one batch, on the stored shapes. float32 to
    rounding; bf16 within a rounding of results of size 4 (`p` is rounded
    before the division by the sum here and after it there)."""
    kv_heads, d, s, groups = SHAPES[shape]
    q, cache = _inputs(_lengths(s), kv_heads, d, dtype, s, groups)
    forms = _forms(s)
    before = [_count(f) for f in forms]
    got = _core(q, cache)
    assert [_count(f) for f in forms] == [n + 1 for n in before]
    want = _plain(q, cache)
    assert got.shape == want.shape == q.shape
    assert got.dtype == want.dtype == dtype
    assert _gap(got, want) <= tol


@pytest.mark.parametrize("fill", [float("nan"), 1e30])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_rows_past_a_length_do_not_reach_the_result(kernel, shape, fill):
    kv_heads, d, s, groups = SHAPES[shape]
    lengths = _lengths(s, 3 * T + (5 if s == 1 else B))[:-1]
    q, cache = _inputs(lengths, kv_heads, d, jnp.float32, s, groups)
    clean = _core(q, cache)
    past = (jnp.arange(ROWS)[None, :, None, None]
            >= cache.offset[:, None, None, None])
    dirty = _core(q, SlotKV(
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


def test_an_idle_slot_in_a_block_step_reads_its_own_block(kernel):
    """A slot nothing is seated in steps its block at offset 0 (4 with the
    block's own rows): it costs one block and sees those four rows alone,
    whatever lies behind them."""
    q, cache = _inputs([B, B, 2 * T], 4, 128, jnp.float32, B, 8)
    got = _core(q, cache)
    past = jnp.arange(ROWS)[None, :, None, None] >= B
    dirty = _core(q, SlotKV(jnp.where(past, jnp.nan, cache.k),
                            jnp.where(past, jnp.nan, cache.v), cache.offset))
    assert _gap(got[:2], _plain(q, cache)[:2]) <= 1e-5
    assert _gap(dirty[:2], got[:2]) == 0.0


def test_a_block_steps_lengths_are_clipped_as_the_write_is(kernel):
    """A slot whose offset has reached the last row writes its block there
    (`SlotKV.update` clips) and sees every row."""
    q, cache = _inputs([ROWS, B], 4, 128, jnp.float32, B, 8)
    inside = _core(q, cache)
    outside = _core(q, SlotKV(cache.k, cache.v,
                              jnp.asarray([ROWS + B - 1, 0], jnp.int32)))
    assert _gap(inside[:1], outside[:1]) == 0.0


# ------------------------------------------------- 2. who takes which path
def _handle(kind=SlotKV, rows=ROWS, heads=8, width=128, offset=None, b=3):
    k = jnp.zeros((b, rows, heads, width))
    offset = jnp.zeros((b,), jnp.int32) if offset is None else offset
    return kind(k, k, offset)


def _paged(heads=4):
    pool = jnp.zeros((8, 16, heads, 128))
    return PagedLayerCache(pool, pool, jnp.zeros((3, 4), jnp.int32),
                           jnp.zeros((3,), jnp.int32),
                           jnp.ones((3,), bool), 16, jnp.float32)


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
    # a block step's (SDAR's shape: a block of 4, 4 heads of 8 groups)
    "block step: a ring": dict(block=B, cache=lambda: _handle(RingKV,
                                                               heads=4)),
    "block step: the paged handle": dict(block=B, cache=_paged),
    "block step: a chunk of two where four were stated": dict(block=B, s=2),
    "block step: a causal chunk of four, no block stated": dict(
        block=None, s=B, groups=8, kv_heads=4,
        cache=lambda: _handle(heads=4)),
    "block step: three stored heads": dict(
        block=B, kv_heads=3, cache=lambda: _handle(heads=3)),
    "block step: rows 64 wide": dict(block=B, cache=lambda: _handle(
        heads=4, width=64)),
    "block step: rows the block does not divide": dict(
        block=B, cache=lambda: _handle(rows=ROWS + 8, heads=4)),
    "block step: a backend with no kernels": dict(block=B, target=None),
}
# what a block step's case changes, the rest SDAR's shape
BLOCK_CASE = dict(s=B, groups=8, kv_heads=4, cache=lambda: _handle(heads=4))


@pytest.mark.parametrize("name", sorted(REFUSED))
def test_supported_refuses(kernel, monkeypatch, name):
    case = dict(s=1, groups=1, kv_heads=8, cache=_handle, target="interpret",
                block=None)
    assert slot_decode.supported((3, 1, 8, 1, 128), _handle())
    assert block_decode.supported((3, B, 4, 8, 128), _handle(heads=4), B)
    if name.startswith("block step"):
        case.update(BLOCK_CASE)
    case.update(REFUSED[name])
    for module in (slot_decode, block_decode):
        monkeypatch.setattr(module, "_target", lambda: case["target"])
    q_shape = (3, case["s"], case["kv_heads"], case["groups"], 128)
    cache = case["cache"]()
    if case["block"] is None:
        assert not slot_decode.supported(q_shape, cache)
    else:
        assert not block_decode.supported(q_shape, cache, case["block"])
    # and the door hands the call back to the model's plain core
    before = _count("slot_kernel"), _count("block_kernel")
    assert slot_attention.decode_core(jnp.zeros(q_shape), cache,
                                      block=case["block"]) is None
    assert (_count("slot_kernel"), _count("block_kernel")) == before


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


def test_a_block_step_over_a_mesh_is_refused(kernel):
    case = (2, B, 4, 8, 128), _handle(b=2, heads=4), B
    assert block_decode.supported(*case)
    devices = np.asarray(jax.devices()[:2])
    with _common.mesh_scope(jax.sharding.Mesh(devices, ("mp",))):
        assert not block_decode.supported(*case)
    with jax.set_mesh(jax.sharding.Mesh(devices, ("mp",))):
        assert not block_decode.supported(*case)


def test_the_cpu_takes_the_plain_core_unasked():
    assert slot_decode._target() is None
    q, cache = _inputs([3, T], 8, 128, jnp.float32)
    before = _count("slot"), _count("slot_kernel")
    assert slot_attention.decode_core(q, cache) is None
    assert (_count("slot"), _count("slot_kernel")) == (before[0] + 1,
                                                       before[1])


def test_the_cpu_takes_a_block_steps_plain_core_unasked():
    assert block_decode._target() is None
    q, cache = _inputs([B, T], 4, 128, jnp.float32, B, 8)
    before = _count("block"), _count("block_kernel")
    assert _core(q, cache) is None
    assert (_count("block"), _count("block_kernel")) == (before[0] + 1,
                                                         before[1])


@pytest.mark.parametrize("case", ["a ring", "a chunk of two",
                                  "a chunk cache", "a block step's ring",
                                  "a block of two where four were stated",
                                  "a block step's chunk cache"])
def test_only_a_decode_step_over_slot_rows_counts(kernel, case):
    """`attn.calls.slot` counts what the bound could serve: one query a
    slot over a `SlotKV`; `attn.calls.block` a block step's `block` queries
    a slot over one. Everything else is the models' own business."""
    chunk = ChunkKV(jnp.zeros((3, ROWS, 4, 128)),
                    jnp.zeros((3, ROWS, 4, 128)), jnp.int32(5))
    q_shape, cache, block = {
        "a ring": ((3, 1, 8, 1, 128), _handle(RingKV), None),
        "a chunk of two": ((3, 2, 8, 1, 128), _handle(), None),
        "a chunk cache": ((3, 1, 8, 1, 128), ChunkKV(
            jnp.zeros((3, ROWS, 8, 128)), jnp.zeros((3, ROWS, 8, 128)),
            jnp.int32(5)), None),
        "a block step's ring": ((3, B, 4, 8, 128), _handle(RingKV, heads=4),
                                B),
        "a block of two where four were stated": (
            (3, 2, 4, 8, 128), _handle(heads=4), B),
        "a block step's chunk cache": ((3, B, 4, 8, 128), chunk, B),
    }[case]
    forms = ("slot", "slot_kernel", "block", "block_kernel")
    before = [_count(f) for f in forms]
    assert slot_attention.decode_core(jnp.zeros(q_shape), cache,
                                      block=block) is None
    assert [_count(f) for f in forms] == before


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
