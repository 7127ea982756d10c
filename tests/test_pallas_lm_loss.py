"""Online Pallas LM-head cross-entropy (ops/pallas/lm_loss.py) vs dense math
(interpret mode on CPU). Round 5: RETIRED from the fused_linear_cross_entropy
route — called DIRECTLY here, keeping the math
pinned as a library kernel."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.ops.pallas.lm_loss import lm_head_cross_entropy, supported


def _dense(h, w, lab):
    logits = h @ w.T
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, lab[:, None], axis=1)[:, 0]
    return lse - picked


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 1e-4), (jnp.bfloat16, 8e-2)])
def test_kernel_matches_dense(dtype, atol):
    rng = np.random.RandomState(0)
    N, V, H = 1024, 512, 128
    h = jnp.asarray(rng.randn(N, H), dtype)
    w = jnp.asarray(rng.randn(V, H) * 0.05, dtype)
    lab = jnp.asarray(rng.randint(0, V, (N,)).astype(np.int32))

    loss = lm_head_cross_entropy(h, w, lab)
    assert loss.dtype == jnp.float32
    ref = _dense(h.astype(jnp.float32), w.astype(jnp.float32), lab)
    np.testing.assert_allclose(loss, ref, atol=atol, rtol=1e-2)


def test_kernel_grads_match_dense():
    rng = np.random.RandomState(1)
    N, V, H = 1024, 256, 128
    h = jnp.asarray(rng.randn(N, H).astype(np.float32))
    w = jnp.asarray((rng.randn(V, H) * 0.05).astype(np.float32))
    lab = jnp.asarray(rng.randint(0, V, (N,)).astype(np.int32))

    gp = jax.grad(lambda a, b: lm_head_cross_entropy(a, b, lab).mean(),
                  argnums=(0, 1))(h, w)
    gr = jax.grad(lambda a, b: _dense(a, b, lab).mean(), argnums=(0, 1))(h, w)
    np.testing.assert_allclose(gp[0], gr[0], atol=1e-6)
    np.testing.assert_allclose(gp[1], gr[1], atol=1e-6)


def test_supported_predicate():
    assert supported(8192, 50304, 768)    # bench shapes (vocab padded to 50688)
    assert supported(16384, 50304, 768)
    # rows tile the 1D labels/loss/lse operands whose XLA layout is 1024-wide:
    # anything below/off the 1024 grid fails Mosaic layout verification on TPU
    assert not supported(512, 50304, 768)
    assert not supported(100, 512, 128)   # rows not tileable
    assert supported(1024, 500, 128)      # unaligned vocab: padded internally
    assert not supported(1024, 512, 100)  # hidden not lane-aligned


def test_unaligned_vocab_padded():
    """Vocab not divisible by 512: W is padded and masked; results must match
    the dense reference exactly on the true vocab, grads flow only to W[:V]."""
    rng = np.random.RandomState(5)
    N, V, H = 1024, 500, 128
    h = jnp.asarray(rng.randn(N, H).astype(np.float32))
    w = jnp.asarray((rng.randn(V, H) * 0.05).astype(np.float32))
    lab = jnp.asarray(rng.randint(0, V, (N,)).astype(np.int32))

    loss = lm_head_cross_entropy(h, w, lab)
    ref = _dense(h, w, lab)
    np.testing.assert_allclose(loss, ref, atol=1e-4, rtol=1e-4)

    gp = jax.grad(lambda a, b: lm_head_cross_entropy(a, b, lab).mean(),
                  argnums=(0, 1))(h, w)
    gr = jax.grad(lambda a, b: _dense(a, b, lab).mean(), argnums=(0, 1))(h, w)
    assert gp[1].shape == (V, H)  # pad sliced off by autodiff of the concat
    np.testing.assert_allclose(gp[0], gr[0], atol=1e-5)
    np.testing.assert_allclose(gp[1], gr[1], atol=1e-5)


def test_mixed_dtype_bf16_h_f32_w():
    """The on-chip amp config: bf16 activations against the f32 master
    embedding weight — the kernel must unify dtypes, dW back in f32."""
    rng = np.random.RandomState(4)
    N, V, H = 1024, 256, 128
    h = jnp.asarray(rng.randn(N, H), jnp.bfloat16)
    w = jnp.asarray(rng.randn(V, H) * 0.05, jnp.float32)
    lab = jnp.asarray(rng.randint(0, V, (N,)).astype(np.int32))

    loss = lm_head_cross_entropy(h, w, lab)
    ref = _dense(h.astype(jnp.float32), w, lab)
    np.testing.assert_allclose(loss, ref, atol=8e-2, rtol=1e-2)

    gh, gw = jax.grad(lambda a, b: lm_head_cross_entropy(a, b, lab).mean(),
                      argnums=(0, 1))(h, w)
    assert gh.dtype == jnp.bfloat16 and gw.dtype == jnp.float32
    gr = jax.grad(lambda a, b: _dense(a.astype(jnp.float32), b, lab).mean(),
                  argnums=(0, 1))(h, w)
    np.testing.assert_allclose(gw, gr[1], atol=5e-3, rtol=5e-2)


@pytest.mark.parametrize("block_n", [256, 512])
def test_small_compute_blocks_match_dense(block_n):
    """block_n shrinks the 2D compute tiles while the 1D operands stay on
    their 1024-element XLA-tile blocks (revisit sub-slices) — value and both
    grads must match the dense reference at every supported block size.
    (The knob exists because Mosaic compile time grows superlinearly in
    per-block vector ops.)"""
    rng = np.random.RandomState(7)
    N, V, H = 2048, 640, 128  # N spans 2 revisit groups at block 256
    h = jnp.asarray(rng.randn(N, H).astype(np.float32))
    w = jnp.asarray((rng.randn(V, H) * 0.05).astype(np.float32))
    lab = jnp.asarray(rng.randint(0, V, (N,)).astype(np.int32))
    loss = lm_head_cross_entropy(h, w, lab, block_n=block_n)
    ref = _dense(h, w, lab)
    np.testing.assert_allclose(loss, ref, atol=1e-4, rtol=1e-4)
    gp = jax.grad(lambda a, b: lm_head_cross_entropy(
        a, b, lab, block_n=block_n).mean(), argnums=(0, 1))(h, w)
    gr = jax.grad(lambda a, b: _dense(a, b, lab).mean(),
                  argnums=(0, 1))(h, w)
    np.testing.assert_allclose(gp[0], gr[0], atol=1e-5)
    np.testing.assert_allclose(gp[1], gr[1], atol=1e-5)
