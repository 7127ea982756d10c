"""Fused LM-head + softmax-cross-entropy (chunked, recompute-in-backward).

The reference fuses the vocab-parallel loss on GPU as a custom CUDA op
(`paddle/fluid/operators/collective/c_softmax_with_cross_entropy_op.cu`); the
single-chip hot path there still materializes the [tokens, vocab] logits.
On TPU the logits tensor is the single largest activation of a GPT step
(batch 8 x seq 1024 x vocab 50304 in f32 = 1.6 GB, plus autodiff residuals of
the same size), so this op computes

    loss[i] = logsumexp(h[i] @ W) - (h[i] @ W)[label[i]]

in row chunks under `lax.scan`: each chunk's logits live only for the duration
of one scan step, and the backward pass recomputes them chunk-by-chunk instead
of saving softmax residuals (FlashAttention-style recompute applied to the
classifier). Matmul inputs stay in the activation dtype (bf16 under amp) with
f32 accumulation on the MXU; the dW accumulator is carried in f32.

Saved residuals: per-row logsumexp only ([tokens] f32).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..core.dispatch import apply
from ..core.tensor import Tensor
from ._helpers import t_

_CHUNK = 2048  # rows per scan step: chunk x vocab f32 logits = ~400 MB transient @ 50k vocab


def _logits_chunk(hc, w, transpose_y):
    """[C, H] x W -> [C, V] f32 (W cast to the activation dtype for MXU rate)."""
    wc = w.astype(hc.dtype) if hc.dtype != w.dtype else w
    dims = (((1,), (1,)), ((), ())) if transpose_y else (((1,), (0,)), ((), ()))
    return jax.lax.dot_general(hc, wc, dims, preferred_element_type=jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _fused_lce(h2, w, labels, transpose_y, chunk, ignore_index):
    loss, _ = _lce_fwd_impl(h2, w, labels, transpose_y, chunk, ignore_index)
    return loss


def _lce_fwd_impl(h2, w, labels, transpose_y, chunk, ignore_index):
    n, _ = h2.shape
    nc = n // chunk
    h3 = h2.reshape(nc, chunk, h2.shape[1])
    l3 = labels.reshape(nc, chunk)

    def one(_, hl):
        hc, lc = hl
        logits = _logits_chunk(hc, w, transpose_y)
        m = jnp.max(logits, axis=-1)
        lse = m + jnp.log(jnp.sum(jnp.exp(logits - m[:, None]), axis=-1))
        safe = jnp.where(lc == ignore_index, 0, lc)
        picked = jnp.take_along_axis(logits, safe[:, None], axis=-1)[:, 0]
        loss = jnp.where(lc == ignore_index, 0.0, lse - picked)
        return None, (loss, lse)

    _, (loss, lse) = jax.lax.scan(one, None, (h3, l3))
    return loss.reshape(n), lse.reshape(n)


def _lce_fwd_rule(h2, w, labels, transpose_y, chunk, ignore_index):
    loss, lse = _lce_fwd_impl(h2, w, labels, transpose_y, chunk, ignore_index)
    return loss, (h2, w, labels, lse)


def _lce_bwd_rule(transpose_y, chunk, ignore_index, res, g):
    h2, w, labels, lse = res
    n, hdim = h2.shape
    v = w.shape[0] if transpose_y else w.shape[1]
    nc = n // chunk
    h3 = h2.reshape(nc, chunk, hdim)
    l3 = labels.reshape(nc, chunk)
    lse3 = lse.reshape(nc, chunk)
    g3 = g.reshape(nc, chunk)

    def one(dw_acc, inp):
        hc, lc, lsec, gc = inp
        logits = _logits_chunk(hc, w, transpose_y)          # recompute, [C, V] f32
        p = jnp.exp(logits - lsec[:, None])
        safe = jnp.where(lc == ignore_index, 0, lc)
        onehot = jax.lax.broadcasted_iota(jnp.int32, p.shape, 1) == safe[:, None].astype(jnp.int32)
        gc = jnp.where(lc == ignore_index, 0.0, gc)
        dl = ((p - onehot) * gc[:, None]).astype(hc.dtype)  # [C, V]
        wc = w.astype(hc.dtype) if hc.dtype != w.dtype else w
        if transpose_y:  # W [V, H]
            dh = jax.lax.dot_general(dl, wc, (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            dw = jax.lax.dot_general(dl, hc, (((0,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
        else:  # W [H, V]
            dh = jax.lax.dot_general(dl, wc, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            dw = jax.lax.dot_general(hc, dl, (((0,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
        return dw_acc + dw, dh.astype(hc.dtype)

    dw_shape = (v, hdim) if transpose_y else (hdim, v)
    dw, dh3 = jax.lax.scan(one, jnp.zeros(dw_shape, jnp.float32),
                           (h3, l3, lse3, g3))
    dlabels = np.zeros(labels.shape, dtype=jax.dtypes.float0)
    return dh3.reshape(n, hdim), dw.astype(w.dtype), dlabels


_fused_lce.defvjp(_lce_fwd_rule, _lce_bwd_rule)


def fused_linear_cross_entropy(hidden, weight, label, transpose_y=True,
                               ignore_index=-100, name=None):
    """Per-position LM loss without materializing full logits.

    hidden: [..., H]; weight: [V, H] if transpose_y (tied-embedding layout) else
    [H, V]; label: int [...]. Returns f32 loss of shape [...] (0 where
    label == ignore_index). Chunked over rows; rows are padded with
    ignore_index up to a chunk multiple, so any token count works.
    """
    hidden, weight, label = t_(hidden), t_(weight), t_(label)
    lead_shape = hidden.shape[:-1]
    hdim = hidden.shape[-1]

    def kernel(h, w, lb):
        # the custom backward inherits the scope open round the forward
        with jax.named_scope("lm_head_loss"):
            return _chunked(h, w, lb)

    def _chunked(h, w, lb):
        n = int(np.prod(lead_shape)) if lead_shape else 1
        h2 = h.reshape(n, hdim)
        lb1 = lb.reshape(n).astype(jnp.int32)

        # The online Pallas lm_loss kernel is RETIRED from this path
        # (round 5: its bench-vocab Mosaic compile exceeded 9.5 min, and
        # the chunked scan below measured 91 TFLOP/s on the 2026-08-01
        # chip rows of BENCH_HISTORY.jsonl). It is not compiled on the
        # machine this tree now runs on. It remains a
        # direct-call library kernel (ops/pallas/lm_loss.py) with its math
        # pinned by tests/test_pallas_lm_loss.py.
        from ..core.flags import flag as _flag

        cfg_chunk = int(_flag("fused_ce_chunk") or _CHUNK)
        if cfg_chunk < 1:
            raise ValueError(
                f"FLAGS_fused_ce_chunk must be >= 1, got {cfg_chunk}")
        chunk = min(cfg_chunk, n)
        pad = (-n) % chunk
        if pad:
            h2 = jnp.concatenate([h2, jnp.zeros((pad, hdim), h2.dtype)], axis=0)
            lb1 = jnp.concatenate(
                [lb1, jnp.full((pad,), ignore_index, jnp.int32)], axis=0)
        loss = _fused_lce(h2, w, lb1, transpose_y, chunk, ignore_index)
        if pad:
            loss = loss[:n]
        return loss.reshape(lead_shape)

    return apply("fused_linear_cross_entropy", kernel, [hidden, weight, label],
                 nondiff_mask=[False, False, True])
