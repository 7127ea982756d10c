"""The parts the zoo's decoders share (models/afmoe.py, olmo_hybrid.py,
sdar.py, deepseek_v2.py): a bias-free projection, an RMSNorm computed in
float32, rotary positions (rotate-half) and the SwiGLU MLP. Each layer holds
its weights in the dtype it is given, drawn on the device straight into it;
norms and RoPE compute in float32 and hand back the input's dtype.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import initializer as I
from ..layer import Layer
from .routed_experts import NormalInto


class Projection(Layer):
    """A bias-free projection, weight [in, out]: y = x @ W."""

    def __init__(self, fan_in, fan_out, dtype, std):
        super().__init__(dtype=dtype)
        self.weight = self.create_parameter(
            (fan_in, fan_out), default_initializer=NormalInto(std))

    def forward(self, x):
        w = self.weight._data
        return jnp.dot(x.astype(w.dtype), w)


class RMSNormF32(Layer):
    """RMSNorm in float32, the result in the input's dtype."""

    def __init__(self, width, eps, dtype):
        super().__init__(dtype=dtype)
        self.eps = eps
        self.weight = self.create_parameter(
            (width,), default_initializer=I.Constant(1.0))

    def forward(self, x):
        y = x.astype(jnp.float32)
        y = y * jax.lax.rsqrt((y * y).mean(-1, keepdims=True) + self.eps)
        return (y * self.weight._data.astype(jnp.float32)).astype(x.dtype)


def rope(x, pos, theta):
    """x [b, s, heads, d], pos [b or 1, s] absolute positions."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[..., None] * inv               # [b, s, d/2]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, :, None, :]
    y = x.astype(jnp.float32)
    rot = jnp.concatenate([-y[..., d // 2:], y[..., :d // 2]], -1)
    return (y * cos + rot * sin).astype(x.dtype)


class SwiGLU(Layer):
    """SwiGLU: (silu(m Wg) * (m Wu)) Wd."""

    def __init__(self, hidden, width, dtype, std):
        super().__init__(dtype=dtype)
        self.gate_proj = Projection(hidden, width, dtype, std)
        self.up_proj = Projection(hidden, width, dtype, std)
        self.down_proj = Projection(width, hidden, dtype, std)

    def forward(self, m):
        return self.down_proj(jax.nn.silu(self.gate_proj(m)) * self.up_proj(m))
