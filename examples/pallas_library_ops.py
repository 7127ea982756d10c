"""Direct-call Pallas library ops: the retired-but-retained kernels.

Round 5 retired the online LM-head cross-entropy and fused LayerNorm Pallas
kernels from the TRAINING path (compile pathology / no measured
headroom against the 91 TFLOP/s chunked fused-CE) — but both remain in the
library as direct-call ops with pinned math. This example is their living
caller (VERDICT r5 next #6): it invokes each against a dense reference, in
Pallas interpret mode on CPU (automatic — `ops/pallas/_common.interpret()`)
and as real Mosaic kernels on a TPU.

    JAX_PLATFORMS=cpu python examples/pallas_library_ops.py
"""
import numpy as np

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas.layer_norm import layer_norm
from paddle_tpu.ops.pallas.lm_loss import lm_head_cross_entropy, supported


def main():
    rng = np.random.RandomState(0)

    # ---- online LM-head cross-entropy (block-n tiled over vocab) ----
    # shapes must satisfy supported(); block_n=256 is the documented safe
    # default (1024 is the recorded Mosaic compile hazard at bench vocab —
    # see the lm_head_cross_entropy docstring before raising it)
    N, V, H = 1024, 1024, 128   # N must tile the 1024-wide 1D row blocks
    assert supported(N, V, H)
    h = jnp.asarray(rng.randn(N, H).astype(np.float32))
    w = jnp.asarray((rng.randn(V, H) * 0.05).astype(np.float32))
    labels = jnp.asarray(rng.randint(0, V, (N,)).astype(np.int32))

    loss = lm_head_cross_entropy(h, w, labels, block_n=256)
    logits = h @ w.T
    ref = (jax.nn.logsumexp(logits, axis=-1)
           - logits[jnp.arange(N), labels])
    err = float(jnp.abs(loss - ref).max())
    assert err < 1e-3, err
    # the kernel differentiates through its custom vjp like any op
    g_h = jax.grad(lambda a: lm_head_cross_entropy(
        a, w, labels, block_n=256).mean())(h)
    assert g_h.shape == h.shape
    print(f"lm_head_cross_entropy ok: mean loss {float(loss.mean()):.4f}, "
          f"max |kernel - dense| {err:.2e}")

    # ---- fused LayerNorm ----
    B, S, Hd = 4, 64, 256
    x = jnp.asarray(rng.randn(B, S, Hd).astype(np.float32))
    weight = jnp.asarray(1.0 + 0.1 * rng.randn(Hd).astype(np.float32))
    bias = jnp.asarray(0.1 * rng.randn(Hd).astype(np.float32))

    out = layer_norm(x, weight, bias)
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    ref_ln = (x - mu) / jnp.sqrt(var + 1e-5) * weight + bias
    err_ln = float(jnp.abs(out - ref_ln).max())
    assert err_ln < 1e-4, err_ln
    g_x = jax.grad(lambda a: layer_norm(a, weight, bias).sum())(x)
    assert g_x.shape == x.shape
    print(f"pallas layer_norm ok: max |kernel - dense| {err_ln:.2e} "
          f"(backend={jax.default_backend()}, interpret on cpu)")


if __name__ == "__main__":
    main()
