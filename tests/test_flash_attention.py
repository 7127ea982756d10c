"""Pallas flash-attention kernels vs the dense XLA reference (interpret mode on
CPU; the same kernels Mosaic-compile on a real chip — chip_smoke.py, and
tests/test_hlo_perf_gates.py lowers them for a described TPU).

Every path of `flash_attention._path` is a case here: `packed` (d=64, an
even head count: two heads a 128-lane block), `head128` (d % 128 == 0) and
`legacy` (everything else `supported()` admits, an odd head count among it).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.observability import metrics
from paddle_tpu.ops.pallas.flash_attention import (
    _path, flash_attention, flash_attention_with_lse, supported)


def dense_ref(q, k, v, causal, with_lse=False):
    qt, kt, vt = [jnp.swapaxes(x, 1, 2).astype(jnp.float32)
                  for x in (q, k, v)]
    s = jnp.einsum("bhqd,bhkd->bhqk", qt, kt) / np.sqrt(q.shape[-1])
    if causal:
        m = jnp.tril(jnp.ones(s.shape[-2:], bool))
        s = jnp.where(m, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.swapaxes(jnp.einsum("bhqk,bhkd->bhqd", p, vt), 1, 2)
    return (o, jax.nn.logsumexp(s, axis=-1)) if with_lse else o


def _operands(seed, b, sq, sk, h, d, dtype):
    rng = np.random.RandomState(seed)
    return [jnp.asarray(rng.randn(b, s, h, d).astype(np.float32), dtype)
            for s in (sq, sk, sk)]


# (b, sq, sk, h, d): the shapes the tests of the [b*h, s, d] kernels pinned
# before the packed paths existed, kept as cases (d = 32 / 16 -> legacy) ...
_OLD_FWD = (2, 128, 128, 2, 32)
_OLD_GRADS = (1, 64, 64, 2, 16)
_OLD_CROSS = (1, 32, 128, 2, 16)
# ... and one shape a path, two q blocks and two kv blocks each
_PACKED = (2, 256, 256, 4, 64)
_HEAD128 = (1, 256, 256, 2, 128)
_ODD_HEADS = (1, 256, 256, 3, 64)      # -> legacy, by the head count
_SHAPES = {
    "old_fwd": _OLD_FWD, "old_grads": _OLD_GRADS, "packed": _PACKED,
    "head128": _HEAD128, "odd_heads": _ODD_HEADS,
    # s with no 128-row divisor: one whole-sequence block
    "packed_s136": (1, 136, 136, 2, 64), "packed_s152": (1, 152, 152, 2, 64),
    "head128_s136": (1, 136, 136, 1, 128),
    # sq != sk (cross attention, a ring step)
    "packed_cross": (1, 128, 256, 2, 64), "head128_cross": (1, 256, 128, 1, 128),
    # two q blocks over one kv sub-block: the second lies wholly past the keys
    "packed_long_q": (1, 1024, 128, 2, 64),
}


def test_paths_by_shape():
    f32 = jnp.float32
    assert _path(16, 64, 1024, 1024, jnp.bfloat16) == ("packed", 2)
    assert _path(8, 128, 1024, 1024, jnp.bfloat16) == ("head128", 1)
    assert _path(4, 256, 512, 512, f32) == ("head128", 1)
    assert _path(25, 64, 1024, 1024, jnp.bfloat16)[0] == "legacy"   # GPT-2 XL
    assert _path(5, 64, 1024, 1024, jnp.bfloat16)[0] == "legacy"    # 20 / mp4
    for d in (32, 80, 96):
        assert _path(4, d, 1024, 1024, f32)[0] == "legacy"
    # no 128-row divisor and too long for one block
    assert _path(4, 64, 2056, 2056, f32)[0] == "legacy"
    # the whole-sequence blocks outgrow the VMEM budget: two-kernel scheme
    assert _path(4, 64, 1 << 16, 1 << 16, jnp.bfloat16)[0] == "legacy"
    assert _path(4, 64, 4096, 4096, jnp.bfloat16)[0] == "packed"
    for name, (_, sq, sk, h, d) in _SHAPES.items():
        want = name.split("_")[0]
        if want in ("packed", "head128"):
            assert _path(h, d, sq, sk, f32)[0] == want, name
        else:
            assert _path(h, d, sq, sk, f32)[0] == "legacy", name


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("shape", [
    "old_fwd", "packed", "head128", "odd_heads", "packed_s136", "packed_s152",
    "head128_s136", "packed_cross", "head128_cross", "packed_long_q"])
def test_flash_forward(shape, causal):
    q, k, v = _operands(0, *_SHAPES[shape], jnp.float32)
    out = flash_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(out, dense_ref(q, k, v, causal), atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("shape", [
    "old_grads", "packed", "head128", "odd_heads", "packed_s136",
    "head128_s136", "packed_cross", "head128_cross", "packed_long_q"])
def test_flash_grads(shape, causal):
    """dq, dk and dv of a loss that weights every output element."""
    q, k, v = _operands(1, *_SHAPES[shape], jnp.float32)
    w = jnp.asarray(np.random.RandomState(7).randn(*q.shape), jnp.float32)

    gf = jax.grad(lambda q, k, v: jnp.sum(
        flash_attention(q, k, v, causal=causal) * w), argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(lambda q, k, v: jnp.sum(dense_ref(q, k, v, causal) * w),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(a, b, atol=5e-5)


def test_flash_cross_attention_lengths():
    # sq != sk (cross attention / unequal blocks), the legacy kernels
    q, k, v = _operands(2, *_OLD_CROSS, jnp.float32)
    out = flash_attention(q, k, v, causal=False)
    np.testing.assert_allclose(out, dense_ref(q, k, v, False), atol=2e-5)


def test_supported_predicate():
    assert supported(512, 512, 64)
    assert not supported(7, 512, 64)     # too short
    assert not supported(512, 512, 63)   # head_dim not 8-aligned


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("shape", ["old_fwd", "packed", "head128",
                                   "odd_heads", "packed_s136"])
def test_flash_bf16_storage_dtype(shape, causal):
    """bf16 inputs exercise the storage-dtype matmul path (bf16 operands,
    f32 accumulation) that real-chip amp runs; CPU f32 tests can't see it.
    Forward and the three gradients against dense attention in f32 on the
    same bf16-rounded inputs: each is within 2e-2 of the reference's largest
    magnitude (P and dS round to bf16 before their second matmul)."""
    q, k, v = _operands(3, *_SHAPES[shape], jnp.bfloat16)
    qf, kf, vf = [x.astype(jnp.float32) for x in (q, k, v)]

    out = flash_attention(q, k, v, causal=causal)
    assert out.dtype == jnp.bfloat16
    ref = dense_ref(qf, kf, vf, causal)
    np.testing.assert_allclose(out.astype(np.float32), ref, atol=2e-2)

    g = jax.grad(lambda q, k, v: jnp.sum(
        flash_attention(q, k, v, causal=causal).astype(jnp.float32)),
        argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(lambda q, k, v: jnp.sum(dense_ref(q, k, v, causal)),
                  argnums=(0, 1, 2))(qf, kf, vf)
    for a, b in zip(g, gd):
        assert a.dtype == jnp.bfloat16
        a = np.asarray(a.astype(jnp.float32))
        assert np.isfinite(a).all()
        assert np.abs(a - np.asarray(b)).max() <= 2e-2 * max(
            1.0, float(np.abs(b).max()))


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("shape", ["packed", "head128", "odd_heads",
                                   "packed_cross"])
def test_flash_with_lse_and_its_cotangent(shape, causal):
    """flash_attention_with_lse returns lse [b, h, sq] equal to the dense
    logsumexp, and a loss through BOTH outputs (a non-zero g_lse, as ring
    attention's merge sends back) gives the dense gradients."""
    q, k, v = _operands(4, *_SHAPES[shape], jnp.float32)
    b, sq, h, _ = q.shape
    rng = np.random.RandomState(8)
    w = jnp.asarray(rng.randn(*q.shape), jnp.float32)
    wl = jnp.asarray(rng.randn(b, h, sq), jnp.float32)

    o, lse = flash_attention_with_lse(q, k, v, causal=causal)
    o_ref, lse_ref = dense_ref(q, k, v, causal, with_lse=True)
    assert lse.shape == (b, h, sq) and lse.dtype == jnp.float32
    np.testing.assert_allclose(o, o_ref, atol=2e-5)
    np.testing.assert_allclose(lse, lse_ref, atol=2e-5)

    def loss(fn):
        def f(q, k, v):
            o, lse = fn(q, k, v)
            return jnp.sum(o * w) + jnp.sum(lse * wl)
        return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    gf = loss(lambda q, k, v: flash_attention_with_lse(q, k, v, causal=causal))
    gd = loss(lambda q, k, v: dense_ref(q, k, v, causal, with_lse=True))
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(a, b, atol=5e-5)


# ------------------------------------------------- the flash.calls counter ---

def _calls():
    counters = metrics.default_registry().snapshot(
        include_monitor=False)["counters"]
    return {name.rsplit(".", 1)[1]: int(n) for name, n in counters.items()
            if name.startswith("flash.calls.")}


@pytest.fixture
def fresh_metrics():
    metrics.reset()
    yield
    metrics.reset()


@pytest.mark.parametrize("heads,d,path", [
    (16, 64, "packed"),       # GPT-2 medium: 16 heads of 64
    (8, 128, "head128"),
    (25, 64, "legacy"),       # GPT-2 XL's odd head count
])
def test_flash_calls_counter_names_the_path(fresh_metrics, heads, d, path):
    """The counter is bumped where the entry chooses, at trace time: an
    abstract evaluation at the real shape is enough to read it."""
    x = jax.ShapeDtypeStruct((8, 1024, heads, d), jnp.bfloat16)
    jax.eval_shape(lambda q, k, v: flash_attention(q, k, v, causal=True),
                   x, x, x)
    assert _calls() == {path: 1}


def _kernels_in(jaxpr, found):
    """Names of the pallas_calls of a jaxpr, every occurrence (a sub-jaxpr
    shared by two call sites counts twice)."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn.params["name"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _kernels_in(sub, found)
    return found


def test_traced_train_step_takes_the_packed_path_in_every_layer(fresh_metrics):
    """A 2-layer model of 4 heads of 64, loss + gradients traced: the program
    holds one packed forward and one packed backward kernel a layer and no
    legacy kernel. The counter reads ONE: it counts choices traced, and
    core/dispatch's rule cache traces the attention rule once for all the
    layers of one shape (the backward is the custom vjp's and does not come
    through the entry again)."""
    from paddle_tpu.jit import functional_call
    from paddle_tpu.models import GPTConfig, GPTForPretraining

    paddle.set_flags({"use_flash_attention": True,
                      "pallas_interpret_ok": True})
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=256, hidden_size=256, num_layers=2,
                    num_heads=4, max_seq_len=128)
    model = GPTForPretraining(cfg)
    model.train()
    state = model.state_dict(include_non_persistable_buffer=True)
    arrays = {k: v._data for k, v in state.items()}
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 256, (2, 128)))
    labels = jnp.roll(ids, -1, 1)

    def loss(params):
        out = functional_call(model, params, paddle.Tensor(ids),
                              paddle.Tensor(labels))
        return out._data if isinstance(out, paddle.Tensor) else out

    traced = jax.make_jaxpr(jax.value_and_grad(loss))(arrays)
    kernels = _kernels_in(traced.jaxpr, [])
    assert sorted(kernels) == (["flash_bwd"] * cfg.num_layers
                               + ["flash_fwd"] * cfg.num_layers)
    assert _calls() == {"packed": 1}
