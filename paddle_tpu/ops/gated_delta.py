"""The gated delta rule (Gated DeltaNet), the recurrence of a
`linear_attention` layer, in two forms that a later kernel replaces one at a
time.

A head keeps a matrix S [d_k, d_v] in float32. At position t, with q_t and
k_t already normalised, a decay `g_t <= 0` and a write strength `beta_t`:

    S' = exp(g_t) S_{t-1}
    u_t = beta_t (v_t - S'^T k_t)
    S_t = S' + k_t u_t^T
    o_t = S_t^T q_t

`gated_delta_step` is that, one position for a batch of slots: elementwise
float32 products and sums, no matrix unit, so that nothing of S is rounded.

`gated_delta_chunked` runs a whole chunk of positions (a prefill) as matrix
products over sub-chunks of C positions and a scan over the sub-chunks. In
one sub-chunk, with gamma_i = sum_{j<=i} g_j and S_0 the state at its start:

    A_ij = beta_i exp(gamma_i - gamma_j) (k_i . k_j)   for j < i, else 0
    [TV | W] = (I + A)^-1 [beta * V | beta * exp(gamma) * K]
    U   = TV - W S_0
    O   = (exp(gamma) * Q) S_0 + tril(Q K^T * exp(gamma_i - gamma_j)) U
    S_C = exp(gamma_C) S_0 + (exp(gamma_C - gamma) * K)^T U

(I + A) is unit lower triangular and does not depend on S_0, so it is inverted
for all sub-chunks at once before the scan (`unit_lower_inverse`: forward
substitution inside blocks of 16, elementwise, then the blocks joined by
matrix products). Every exponent is <= 0.
A position with `beta = 0` and `g = 0` leaves the state as it was (u = 0, gamma
does not move): that is how a pad is made inert, and how a length that is
not a multiple of C is filled.

`delta.calls.<path>` (`chunked` / `step`) counts the calls traced.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..observability import metrics

# float32 products on the matrix unit: three bf16 passes (`HIGH`) for the
# chunked form's products, which read one layer's 7.7 ms at 3,584 positions
# as 5.0 against six passes' 5.7 with the same distance from the recurrence
# (8e-5; chip probe, PERF.md §6, PR 33); six where the inverse's blocks join
_HIGH = jax.lax.Precision.HIGH
_HIGHEST = jax.lax.Precision.HIGHEST


def _count(path: str) -> None:
    metrics.default_registry().counter(
        "delta.calls." + path,
        "gated-delta-rule calls traced, by the form they took").inc()


_BLOCK = 16      # rows inverted by substitution before blocks are joined


def unit_lower_inverse(a):
    """(I + a)^-1 for a strictly lower triangular `a` [..., c, c], float32;
    c is at most 16 or 16 times a power of two. Row i of the inverse of a
    block of 16 is e_i - a[i, :i] X[:i] (elementwise products, so nothing is
    rounded to the matrix unit's inputs); two inverted diagonal blocks X11,
    X22 of a block twice their size join as [[X11, 0], [-X22 a21 X11, X22]]."""
    c = a.shape[-1]
    n = min(c, _BLOCK)
    if c % n or (c // n) & (c // n - 1):
        raise ValueError(f"chunk {c}: at most {_BLOCK}, or {_BLOCK} times a "
                         f"power of two")
    lead = a.shape[:-2]
    # the diagonal blocks [..., c // n, n, n]
    blocks = a.reshape(lead + (c // n, n, c // n, n))
    diag = jnp.stack([blocks[..., i, :, i, :] for i in range(c // n)], -3)
    x = jnp.broadcast_to(jnp.eye(n, dtype=a.dtype), diag.shape)
    for i in range(1, n):
        row = (diag[..., i, :i, None] * x[..., :i, :]).sum(-2)
        x = x.at[..., i, :].add(-row)
    while n < c:
        # pairs of inverted diagonal blocks of n -> blocks of 2n
        pairs = c // (2 * n)
        x = x.reshape(lead + (pairs, 2, n, n))
        x11, x22 = x[..., 0, :, :], x[..., 1, :, :]
        a21 = jnp.stack([a[..., (2 * j + 1) * n:(2 * j + 2) * n,
                           2 * j * n:(2 * j + 1) * n]
                         for j in range(pairs)], -3)
        x21 = -jnp.einsum("...ij,...jk,...kl->...il", x22, a21, x11,
                          precision=_HIGHEST)
        top = jnp.concatenate([x11, jnp.zeros_like(x11)], -1)
        x = jnp.concatenate([top, jnp.concatenate([x21, x22], -1)], -2)
        n *= 2
    return x.reshape(a.shape)


def gated_delta_step(q, k, v, g, beta, state):
    """One position a row. q, k [b, h, d_k], v [b, h, d_v], g, beta [b, h],
    state [b, h, d_k, d_v]; everything float32. -> (o [b, h, d_v], state)."""
    _count("step")
    s = state * jnp.exp(g)[..., None, None]
    u = beta[..., None] * (v - (s * k[..., :, None]).sum(-2))
    s = s + k[..., :, None] * u[..., None, :]
    return (s * q[..., :, None]).sum(-2), s


def gated_delta_chunked(q, k, v, g, beta, state, chunk: int = 64):
    """A chunk of s positions. q, k [b, s, h, d_k], v [b, s, h, d_v], g, beta
    [b, s, h], state [b, h, d_k, d_v]; everything float32; s need not be a
    multiple of `chunk`. -> (o [b, s, h, d_v], the state after position
    s - 1)."""
    _count("chunked")
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    c = int(chunk)
    n = -(-s // c)
    pad = n * c - s

    def split(x):
        """[b, s, h, ...] -> [n, b, h, c, ...], zeros past s."""
        if pad:
            x = jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
        x = x.reshape((b, n, c) + x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 1), 2, 0)

    q, k, v, g, beta = (split(x) for x in (q, k, v, g, beta))
    gamma = jnp.cumsum(g, axis=-1)                            # [n, b, h, c]
    lower = jnp.tril(jnp.ones((c, c), bool))
    decay = jnp.exp(jnp.where(
        lower, gamma[..., :, None] - gamma[..., None, :], -jnp.inf))
    kk = jnp.einsum("...id,...jd->...ij", k, k, precision=_HIGH)
    a = jnp.where(jnp.tril(lower, -1), beta[..., :, None] * decay * kk, 0.0)
    rhs = jnp.concatenate(
        [beta[..., None] * v, (beta * jnp.exp(gamma))[..., None] * k], -1)
    solved = jnp.einsum("...ij,...jv->...iv", unit_lower_inverse(a), rhs,
                        precision=_HIGH)
    tv, w = solved[..., :dv], solved[..., dv:]
    qg = jnp.exp(gamma)[..., None] * q
    m = decay * jnp.einsum("...id,...jd->...ij", q, k, precision=_HIGH)
    last = gamma[..., -1]                                     # [n, b, h]
    kd = jnp.exp(last[..., None] - gamma)[..., None] * k

    def one(s0, xs):
        tv, w, qg, m, kd, last = xs
        u = tv - jnp.einsum("...ck,...kv->...cv", w, s0, precision=_HIGH)
        o = (jnp.einsum("...ck,...kv->...cv", qg, s0, precision=_HIGH)
             + jnp.einsum("...ij,...jv->...iv", m, u, precision=_HIGH))
        s1 = (jnp.exp(last)[..., None, None] * s0
              + jnp.einsum("...ck,...cv->...kv", kd, u, precision=_HIGH))
        return s1, o

    state, o = jax.lax.scan(one, state, (tv, w, qg, m, kd, last))
    # [n, b, h, c, d_v] -> [b, s, h, d_v]
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 2), 1, 3).reshape(b, n * c, h, dv)
    return o[:, :s], state
