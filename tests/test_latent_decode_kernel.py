"""The Pallas kernel of the absorbed decode core (ops/pallas/latent_decode.py)
against the plain `latent_attention.absorbed` on the same inputs, on the CPU
in interpret mode at small shapes, and which inputs take it (`supported`).
The tiny model through `ServingEngine` with the kernel forced is in
tests/test_deepseek_v2.py, beside the same traffic through the plain form.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import DeepseekV2ForCausalLM, deepseek_v2_tiny
from paddle_tpu.nn.kv_cache import ChunkLatent, SlotLatent
from paddle_tpu.observability import metrics
from paddle_tpu.ops import latent_attention
from paddle_tpu.ops.pallas import _common, latent_decode

T = 16              # the block here; the chip's is latent_decode.BLOCK_ROWS
ROWS = 4 * T
HEADS, RANK, ROPE = 4, 96, 24


@pytest.fixture
def kernel(monkeypatch):
    """The kernel in interpret mode, in blocks of T rows."""
    monkeypatch.setattr(latent_decode, "_target", lambda: "interpret")
    monkeypatch.setattr(latent_decode, "BLOCK_ROWS", T)


def _count(form):
    return metrics.default_registry().counter("mla.calls." + form).value


def _inputs(lengths, width, dtype):
    b = len(lengths)
    keys = jax.random.split(jax.random.key(0), 3)
    q_l = jax.random.normal(keys[0], (b, 1, HEADS, RANK), dtype)
    q_r = jax.random.normal(keys[1], (b, 1, HEADS, ROPE), dtype)
    rows = jnp.pad(jax.random.normal(keys[2], (b, ROWS, RANK + ROPE), dtype),
                   [(0, 0), (0, 0), (0, width - RANK - ROPE)])
    lengths = jnp.asarray(lengths, jnp.int32)
    mask = jnp.arange(ROWS)[None, None, :] < lengths[:, None, None]
    return q_l, q_r, rows, mask, lengths


def _gap(a, b):
    return float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max())


# ------------------------------------------ 1. the kernel vs the plain form
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 4e-2)])
@pytest.mark.parametrize("width", [128, 640])
def test_kernel_is_the_plain_form(kernel, width, dtype, tol):
    """Lengths 1, T - 1, T, T + 1 and every row, mixed in one batch; rows
    stored wider than they are used, zeros behind. float32 within the
    tolerance `test_absorbed_form_is_the_expanded_form` uses; bf16 within a
    rounding of results of size 4 (`p` is rounded before the division by
    the sum here and after it there)."""
    q_l, q_r, rows, mask, lengths = _inputs([1, T - 1, T, T + 1, ROWS], width,
                                            dtype)
    want = latent_attention.absorbed(q_l, q_r, rows, mask, 0.3)
    before = _count("absorbed"), _count("absorbed_kernel")
    got = latent_attention.absorbed(q_l, q_r, rows, mask, 0.3,
                                    lengths=lengths)
    assert (_count("absorbed"), _count("absorbed_kernel")) == (
        before[0] + 1, before[1] + 1)
    assert got.shape == want.shape == (5, 1, HEADS, RANK)
    assert got.dtype == want.dtype == dtype
    assert _gap(got, want) <= tol


@pytest.mark.parametrize("fill", [float("nan"), 1e30])
def test_rows_past_a_length_do_not_reach_the_result(kernel, fill):
    q_l, q_r, rows, mask, lengths = _inputs([1, T - 1, T, T + 1, 3 * T + 5],
                                            128, jnp.float32)
    clean = latent_attention.absorbed(q_l, q_r, rows, mask, 0.3,
                                      lengths=lengths)
    past = jnp.arange(ROWS)[None, :, None] >= lengths[:, None, None]
    dirty = latent_attention.absorbed(q_l, q_r, jnp.where(past, fill, rows),
                                      mask, 0.3, lengths=lengths)
    assert bool(jnp.isfinite(dirty).all())
    assert _gap(dirty, clean) == 0.0


def test_a_slot_of_length_one_returns_its_rows_latent(kernel):
    q_l, q_r, rows, mask, _ = _inputs([1, 1], 128, jnp.float32)
    got = latent_attention.absorbed(q_l, q_r, rows, mask, 0.3,
                                    lengths=jnp.asarray([1, 1], jnp.int32))
    want = jnp.broadcast_to(rows[:, None, None, 0, :RANK], got.shape)
    assert _gap(got, want) <= 1e-6


def test_lengths_are_clipped_as_the_write_is(kernel):
    """`SlotLatent.update` clips its write to the last row; a length past
    the rows sees them all, one under 1 sees row 0."""
    q_l, q_r, rows, _, _ = _inputs([ROWS, 1], 128, jnp.float32)
    q = jnp.pad(jnp.concatenate([q_l, q_r], -1)[:, 0], [(0, 0), (0, 0), (0, 8)])
    inside = latent_decode.latent_decode(
        q, rows, jnp.asarray([ROWS, 1], jnp.int32), 0.3, 128)
    outside = latent_decode.latent_decode(
        q, rows, jnp.asarray([ROWS + 7, 0], jnp.int32), 0.3, 128)
    assert _gap(inside, outside) == 0.0


# ------------------------------------------------- 2. who takes which path
REFUSED = {
    "a chunk of two": dict(s=2),
    "a scalar offset": dict(lengths=jnp.int32(5)),
    "no lengths": dict(lengths=None),
    "one length for the batch": dict(lengths=jnp.asarray([5], jnp.int32)),
    "a width of 576": dict(width=576),
    "rows the block does not divide": dict(rows=ROWS + 8),
    "a backend with no kernels": dict(target=None),
}


@pytest.mark.parametrize("name", sorted(REFUSED))
def test_supported_refuses(kernel, monkeypatch, name):
    case = dict(s=1, lengths=jnp.zeros((3,), jnp.int32), width=640, rows=ROWS,
                target="interpret")
    assert latent_decode.supported((3, 1, HEADS, 640), (3, ROWS, 640),
                                   case["lengths"])
    case.update(REFUSED[name])
    monkeypatch.setattr(latent_decode, "_target", lambda: case["target"])
    assert not latent_decode.supported(
        (3, case["s"], HEADS, case["width"]),
        (3, case["rows"], case["width"]), case["lengths"])


def test_supported_refuses_a_program_over_a_mesh(kernel):
    shapes = (2, 1, HEADS, 128), (2, ROWS, 128), jnp.zeros((2,), jnp.int32)
    assert latent_decode.supported(*shapes)
    devices = np.asarray(jax.devices()[:2])
    with _common.mesh_scope(jax.sharding.Mesh(devices, ("mp",))):
        assert not latent_decode.supported(*shapes)
    with _common.mesh_scope(jax.sharding.Mesh(devices[:1], ("mp",))):
        assert latent_decode.supported(*shapes)
    with jax.set_mesh(jax.sharding.Mesh(devices, ("mp",))):
        assert not latent_decode.supported(*shapes)


def test_the_cpu_takes_the_plain_form_unasked():
    assert latent_decode._target() is None
    q_l, q_r, rows, mask, lengths = _inputs([3, T], 128, jnp.float32)
    before = _count("absorbed"), _count("absorbed_kernel")
    latent_attention.absorbed(q_l, q_r, rows, mask, 0.3, lengths=lengths)
    assert (_count("absorbed"), _count("absorbed_kernel")) == (
        before[0] + 1, before[1])


@pytest.fixture(scope="module")
def tiny():
    paddle.seed(3)
    model = DeepseekV2ForCausalLM(deepseek_v2_tiny())
    model.eval()
    return model


@pytest.mark.parametrize("case", ["a chunk behind held rows",
                                  "a scalar offset", "a slot cache"])
def test_the_model_takes_the_form_its_cache_allows(kernel, tiny, case):
    """What `supported()` refuses goes through the plain form and counts as
    absorbed; one position a slot of a `SlotLatent` takes the kernel and
    counts as both."""
    attn = tiny.model.layers[0].self_attn
    rows = jnp.zeros((2, ROWS, 128))
    s, cache = {
        "a chunk behind held rows": (3, SlotLatent(
            rows, jnp.asarray([4, 9], jnp.int32))),
        "a scalar offset": (1, ChunkLatent(rows, jnp.int32(4))),
        "a slot cache": (1, SlotLatent(rows, jnp.asarray([4, 9], jnp.int32))),
    }[case]
    before = _count("absorbed"), _count("absorbed_kernel")
    attn(jnp.ones((2, s, 64)) * 0.1, cache=cache)
    assert (_count("absorbed"), _count("absorbed_kernel")) == (
        before[0] + 1, before[1] + (case == "a slot cache"))
