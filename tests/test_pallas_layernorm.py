"""Fused Pallas LayerNorm vs the XLA lowering (values + grads), interpret
mode on CPU. Reference parity: phi layer_norm_kernel fused path.

Round 5: the kernel is RETIRED from the nn.functional.layer_norm route —
these tests call it DIRECTLY
(ops/pallas/layer_norm.py), keeping its math pinned as a library kernel.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle  # noqa: F401  (x64 mode + platform init)
from paddle_tpu.ops.pallas.layer_norm import layer_norm as pln
from paddle_tpu.ops.pallas.layer_norm import supported


def _data(shape, hidden, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.randn(*shape, hidden).astype(np.float32)
    g = rng.rand(hidden).astype(np.float32) + 0.5
    b = rng.randn(hidden).astype(np.float32)
    return x, g, b


def _ref(x, g, b, eps=1e-5):
    xf = x.astype(np.float32)
    m = xf.mean(-1, keepdims=True)
    v = xf.var(-1, keepdims=True)
    return (xf - m) / np.sqrt(v + eps) * g + b


@pytest.mark.parametrize("shape,hidden", [((16,), 128), ((4, 8), 256),
                                          ((2, 3, 8), 128)])
def test_values_match_reference(shape, hidden):
    x, g, b = _data(shape, hidden)
    got = np.asarray(pln(jnp.asarray(x), jnp.asarray(g), jnp.asarray(b)))
    np.testing.assert_allclose(got, _ref(x, g, b), rtol=2e-5, atol=2e-5)


def test_grads_match_xla_lowering():
    x, g, b = _data((8,), 128, seed=3)
    w = np.random.RandomState(4).randn(8, 128).astype(np.float32)
    xj, gj, bj, wj = (jnp.asarray(a) for a in (x, g, b, w))

    def loss_pallas(xx, gg, bb):
        return (pln(xx, gg, bb) * wj).sum()

    def loss_xla(xx, gg, bb):
        xf = xx.astype(jnp.float32)
        m = xf.mean(-1, keepdims=True)
        v = ((xf - m) ** 2).mean(-1, keepdims=True)
        return (((xf - m) * jax.lax.rsqrt(v + 1e-5) * gg + bb) * wj).sum()

    gp = jax.grad(loss_pallas, argnums=(0, 1, 2))(xj, gj, bj)
    gr = jax.grad(loss_xla, argnums=(0, 1, 2))(xj, gj, bj)
    for a, r in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=2e-4, atol=2e-4)


def test_bf16_io_f32_stats():
    """bf16 in/out with f32 statistics inside the kernel: output dtype
    follows the input, values match the f32 reference at bf16 tolerance
    (pins the .astype chains in _fwd_kernel and the o_ref.dtype cast for
    the retained library kernel)."""
    x, g, b = _data((4, 8), 256, seed=7)
    out = pln(jnp.asarray(x, jnp.bfloat16), jnp.asarray(g), jnp.asarray(b))
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               _ref(x, g, b), rtol=2e-2, atol=2e-2)


def test_supported_predicate():
    assert supported(16384, 768)      # bench shape
    assert not supported(16, 100)     # hidden not lane-aligned
