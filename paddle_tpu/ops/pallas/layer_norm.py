"""Fused LayerNorm Pallas kernel (forward + backward).

Reference parity: paddle/phi/kernels/gpu/layer_norm_kernel.cu (the fused
welford + affine CUDA kernel). TPU-native: rows tile over the grid, each
program normalizes a [block_rows, hidden] tile in VMEM with f32 statistics —
one HBM read per tensor in each pass instead of XLA's separate
mean/var/normalize ops. Backward recomputes xhat from saved (mu, rstd) and
produces dx in one pass; dgamma/dbeta accumulate across the sequential TPU
grid into one revisited [1, hidden] output block (the Mosaic reduction idiom —
no atomics, no partials array).

RETIRED from the nn.functional.layer_norm route in round 5 (never completed
a functional on-chip run, and XLA fuses the plain lowering into the
surrounding elementwise chain, leaving little headroom). Available as a
direct-call library kernel; math pinned by tests/test_pallas_layernorm.py
(interpret mode). Not compiled on the machine this tree now runs on.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._common import interpret as _interpret, pick_block as _pick_block

_LANES = 128


def supported(n_rows: int, hidden: int) -> bool:
    return hidden % _LANES == 0 and n_rows >= 1


def _pick_rows(n_rows: int, hidden: int) -> int:
    # target ~1-2 MB f32 tiles; at least 8 rows for sublane alignment
    target = max(8, min(256, (1 << 19) // max(hidden, 1)))
    b = _pick_block(n_rows, preferred=target)
    return b if b <= target else 1  # pick_block falls back to n_rows itself


def _fwd_kernel(x_ref, g_ref, b_ref, o_ref, mu_ref, rstd_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)                 # [rows, hidden]
    mu = jnp.mean(x, axis=1, keepdims=True)
    xc = x - mu
    var = jnp.mean(xc * xc, axis=1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = xc * rstd
    o_ref[...] = (xhat * g_ref[...].astype(jnp.float32)
                  + b_ref[...].astype(jnp.float32)).astype(o_ref.dtype)
    if mu_ref is not None:  # inference variant skips the residual writes
        # row stats broadcast across the lane dim (TPU per-row scalar layout)
        mu_ref[...] = jnp.broadcast_to(mu, mu_ref.shape)
        rstd_ref[...] = jnp.broadcast_to(rstd, rstd_ref.shape)


def _infer_kernel(x_ref, g_ref, b_ref, o_ref, *, eps):
    _fwd_kernel(x_ref, g_ref, b_ref, o_ref, None, None, eps=eps)


def _bwd_kernel(x_ref, g_ref, dy_ref, mu_ref, rstd_ref,
                dx_ref, dg_ref, db_ref):
    i = pl.program_id(0)
    x = x_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    dy = dy_ref[...].astype(jnp.float32)
    mu = mu_ref[...][:, :1]
    rstd = rstd_ref[...][:, :1]
    xhat = (x - mu) * rstd
    wdy = dy * g
    c1 = jnp.mean(wdy, axis=1, keepdims=True)
    c2 = jnp.mean(wdy * xhat, axis=1, keepdims=True)
    dx_ref[...] = ((wdy - c1 - xhat * c2) * rstd).astype(dx_ref.dtype)
    # dgamma/dbeta: accumulate into one revisited [1, h] output block — TPU
    # grid steps run sequentially, so += across iterations is the Mosaic
    # reduction idiom (a [tiles, h] partials array with [1, h] blocks violates
    # the (8, 128) block-tiling rule — caught by the TPU-export gate)
    @pl.when(i == 0)
    def _init():
        dg_ref[...] = jnp.zeros_like(dg_ref)
        db_ref[...] = jnp.zeros_like(db_ref)

    dg_ref[...] += jnp.sum(dy * xhat, axis=0, keepdims=True)
    db_ref[...] += jnp.sum(dy, axis=0, keepdims=True)


def _fwd(x2d, g, b, eps):
    n, h = x2d.shape
    rows = _pick_rows(n, h)
    grid = (n // rows,)
    o, mu, rstd = pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((rows, h), lambda i: (i, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((rows, h), lambda i: (i, 0)),
            pl.BlockSpec((rows, _LANES), lambda i: (i, 0)),
            pl.BlockSpec((rows, _LANES), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, h), x2d.dtype),
            jax.ShapeDtypeStruct((n, _LANES), jnp.float32),
            jax.ShapeDtypeStruct((n, _LANES), jnp.float32),
        ],
        interpret=_interpret(),
    )(x2d, g[None, :], b[None, :])
    return o, mu, rstd


def _infer(x2d, g, b, eps):
    """Forward-only variant: no mu/rstd residual writes to HBM."""
    n, h = x2d.shape
    rows = _pick_rows(n, h)
    return pl.pallas_call(
        functools.partial(_infer_kernel, eps=eps),
        grid=(n // rows,),
        in_specs=[
            pl.BlockSpec((rows, h), lambda i: (i, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((rows, h), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, h), x2d.dtype),
        interpret=_interpret(),
    )(x2d, g[None, :], b[None, :])


def _bwd(x2d, g, dy, mu, rstd):
    n, h = x2d.shape
    rows = _pick_rows(n, h)
    tiles = n // rows
    dx, dg_part, db_part = pl.pallas_call(
        _bwd_kernel,
        grid=(tiles,),
        in_specs=[
            pl.BlockSpec((rows, h), lambda i: (i, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
            pl.BlockSpec((rows, h), lambda i: (i, 0)),
            pl.BlockSpec((rows, _LANES), lambda i: (i, 0)),
            pl.BlockSpec((rows, _LANES), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((rows, h), lambda i: (i, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, h), x2d.dtype),
            jax.ShapeDtypeStruct((1, h), jnp.float32),
            jax.ShapeDtypeStruct((1, h), jnp.float32),
        ],
        interpret=_interpret(),
    )(x2d, g[None, :], dy, mu, rstd)
    return dx, dg_part[0], db_part[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _ln(x2d, g, b, eps):
    # primal (no-grad) path: stats-free kernel, half the HBM writes
    return _infer(x2d, g, b, eps)


def _ln_fwd(x2d, g, b, eps):
    o, mu, rstd = _fwd(x2d, g, b, eps)
    return o, (x2d, g, mu, rstd)


def _ln_bwd(eps, res, dy):
    x2d, g, mu, rstd = res
    dx, dg, db = _bwd(x2d, g, dy, mu, rstd)
    return dx, dg.astype(g.dtype), db.astype(g.dtype)


_ln.defvjp(_ln_fwd, _ln_bwd)


def layer_norm(x, weight, bias, eps=1e-5):
    """x: [..., hidden]; weight/bias: [hidden]. Returns x's shape/dtype."""
    shape = x.shape
    h = shape[-1]
    n = math.prod(shape[:-1]) if len(shape) > 1 else 1
    out = _ln(x.reshape(n, h), weight, bias, float(eps))
    return out.reshape(shape)
