"""The `olmo_hybrid` decoder family (Olmo-Hybrid): gated-delta-rule layers,
whose state is a matrix a head and not rows of keys, three to every
full-attention layer, for the serving path.

What a block is (config keys from the published `config.json`; what is not a
key is listed under `assumed` in benchmarks/configs/olmo-hybrid-7b.json):

- the OLMo block, both kinds: no pre-norm, the norm sits on the branch's
  output: h = x + RMSNorm(mixer(x)); h = h + RMSNorm(W_down(silu(h W_gate) *
  (h W_up))); no bias anywhere; logits = RMSNorm(h) W_head, untied.
- `full_attention`: q and k RMS-normalised over the whole projection with a
  learned weight, causal softmax attention; `rope_parameters.rope_theta` is
  null in the published file and is read as no rotary encoding.
- `linear_attention`, the gated delta rule (ops/gated_delta.py):
  z = x [W_q | W_k | W_v]; a depthwise causal convolution of width
  `linear_conv_kernel_dim` over time, no bias, then SiLU; q, k L2-normalised
  a head, q scaled by d_k^-1/2; beta = 2 sigmoid(x W_b) (the 2 is
  `linear_allow_neg_eigval`), g = -exp(A_log) softplus(x W_a + dt_bias), one
  each a head, float32; the recurrence; y = (RMSNorm_dv(o) * silu(x W_g)) W_o.

The model computes in the dtype its weights have (`OlmoHybridConfig.dtype`),
drawn on the device straight into it; norms, softmax, the convolution, the
gates and the whole delta rule run in float32. `forward(ids)` gives logits;
training is not written.

Serving: `kv_cache_spec` declares a `full` cache for an attention layer and a
`state` for a linear layer (nn/kv_cache.py): the matrix [heads, d_k, d_v]
float32 and the convolution's last inputs. A linear layer reads what its
handle holds, masks the gates of the positions that are not real (`valid`:
beta = 0, g = 0 leave the matrix alone) and hands back what the slot holds
next. A whole chunk runs the chunked form, one position the step form.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .. import nn
from ..core import dtype as dtypes
from ..core import random as random_mod
from ..core.tensor import Tensor
from ..nn.kv_cache import (KVLayerSpec, SlotState, StateLayerSpec,
                           conv_tail)
from ..nn.layers.decoder_parts import Projection, RMSNormF32, SwiGLU
from ..ops import slot_attention
from ..ops.gated_delta import gated_delta_chunked, gated_delta_step

LINEAR, FULL = "linear_attention", "full_attention"
_L2_EPS = 1e-6


class OlmoHybridConfig:
    def __init__(self, vocab_size=100352, hidden_size=3840,
                 intermediate_size=11008, num_hidden_layers=32,
                 num_attention_heads=30, num_key_value_heads=30,
                 layer_types=None, linear_num_key_heads=30,
                 linear_num_value_heads=30, linear_key_head_dim=96,
                 linear_value_head_dim=192, linear_conv_kernel_dim=4,
                 linear_allow_neg_eigval=True, rope_parameters=None,
                 hidden_act="silu", attention_bias=False, rms_norm_eps=1e-6,
                 max_position_embeddings=65536, tie_word_embeddings=False,
                 dtype="float32", initializer_range=0.02):
        if hidden_act != "silu" or attention_bias or tie_word_embeddings:
            raise ValueError("olmo_hybrid is written with SiLU, without "
                             "biases and with an untied head")
        if (rope_parameters or {}).get("rope_theta") is not None:
            raise ValueError(
                "rope_parameters.rope_theta is null in the published file and "
                "the full layers are written without a rotary encoding")
        if linear_num_key_heads != linear_num_value_heads:
            raise ValueError("the delta rule is written for as many key "
                             "heads as value heads")
        if hidden_size % num_attention_heads \
                or num_attention_heads % num_key_value_heads:
            raise ValueError("heads must divide the hidden size, and key "
                             "heads the query heads")
        if layer_types is None:
            layer_types = [FULL if (i + 1) % 4 == 0 else LINEAR
                           for i in range(num_hidden_layers)]
        if len(layer_types) != num_hidden_layers \
                or set(layer_types) - {LINEAR, FULL}:
            raise ValueError(f"{len(layer_types)} layer_types of "
                             f"{sorted(set(layer_types))} for "
                             f"{num_hidden_layers} layers")
        self.vocab_size = int(vocab_size)
        self.hidden_size = int(hidden_size)
        self.intermediate_size = int(intermediate_size)
        self.num_hidden_layers = int(num_hidden_layers)
        self.num_attention_heads = int(num_attention_heads)
        self.num_key_value_heads = int(num_key_value_heads)
        self.head_dim = self.hidden_size // self.num_attention_heads
        self.layer_types = list(layer_types)
        self.linear_num_heads = int(linear_num_key_heads)
        self.linear_key_head_dim = int(linear_key_head_dim)
        self.linear_value_head_dim = int(linear_value_head_dim)
        self.linear_conv_kernel_dim = int(linear_conv_kernel_dim)
        self.linear_allow_neg_eigval = bool(linear_allow_neg_eigval)
        self.rms_norm_eps = float(rms_norm_eps)
        self.max_position_embeddings = int(max_position_embeddings)
        self.dtype = dtype
        self.initializer_range = float(initializer_range)

    # the names the serving engine and the other models' configs use
    @property
    def num_layers(self):
        return self.num_hidden_layers

    @property
    def max_seq_len(self):
        return self.max_position_embeddings

    @classmethod
    def from_dict(cls, config: dict, **overrides):
        """From a huggingface `config.json` (keys this model does not read,
        such as `model_type`, are left aside)."""
        import inspect

        known = set(inspect.signature(cls.__init__).parameters) - {"self"}
        kw = {k: v for k, v in config.items() if k in known}
        kw.update(overrides)
        return cls(**kw)


def olmo_hybrid_tiny(**kw):
    """The CPU tests' size: the pattern L,L,L,F twice."""
    base = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                num_hidden_layers=8, num_attention_heads=4,
                num_key_value_heads=4, linear_num_key_heads=4,
                linear_num_value_heads=4, linear_key_head_dim=8,
                linear_value_head_dim=16, max_position_embeddings=64)
    base.update(kw)
    return OlmoHybridConfig(**base)


class _Drawn(nn.initializer.Initializer):
    """`draw(key, shape)` in float32, rounded to the parameter's dtype."""

    def __init__(self, draw):
        self.draw = draw

    def __call__(self, shape, dtype):
        key = random_mod.named_generator("init").next_key()
        return self.draw(key, tuple(shape)).astype(dtypes.convert_dtype(dtype))


def _a_log(key, shape):
    """`fla`'s default: A uniform in (0, 16)."""
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 0.0, 16.0))


def _dt_bias(key, shape, lo=1e-3, hi=0.1, floor=1e-4):
    """`fla`'s default: dt log-uniform in (lo, hi), through the inverse of
    the softplus."""
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32)
                 * (math.log(hi) - math.log(lo)) + math.log(lo))
    dt = jnp.maximum(dt, floor)
    return dt + jnp.log(-jnp.expm1(-dt))


def _conv_weight(key, shape):
    """A depthwise Conv1d's default: uniform in +-1/sqrt(width)."""
    bound = 1.0 / math.sqrt(shape[0])
    return jax.random.uniform(key, shape, jnp.float32, -bound, bound)


def _state_spec(heads, dk, dv, width):
    return StateLayerSpec("state", heads, dk, dv, width - 1,
                          heads * (2 * dk + dv))


def kv_cache_spec(config: OlmoHybridConfig, max_seq_len: int):
    """What each layer keeps a slot: rows of keys and values for a
    `full_attention` layer, a state for a `linear_attention` layer."""
    c = config
    return [_state_spec(c.linear_num_heads, c.linear_key_head_dim,
                        c.linear_value_head_dim, c.linear_conv_kernel_dim)
            if t == LINEAR else
            KVLayerSpec("full", max_seq_len, c.num_key_value_heads,
                        c.head_dim)
            for t in c.layer_types]


def _dot_f32(x, weight):
    w = weight._data
    return jnp.dot(x.astype(w.dtype), w, preferred_element_type=jnp.float32)


class GatedDeltaNet(nn.Layer):
    """The `linear_attention` mixer. forward(x [b, s, hidden], cache) ->
    y [b, s, hidden], and with a cache (a `SlotState`) the new cache and the
    largest |S| over the rows that hold a real position."""

    def __init__(self, config: OlmoHybridConfig):
        super().__init__(dtype=config.dtype)
        c = config
        self.heads = c.linear_num_heads
        self.dk, self.dv = c.linear_key_head_dim, c.linear_value_head_dim
        self.width = c.linear_conv_kernel_dim
        self.beta_scale = 2.0 if c.linear_allow_neg_eigval else 1.0
        self.eps = c.rms_norm_eps
        std = c.initializer_range
        keys, values = self.heads * self.dk, self.heads * self.dv
        self.channels = 2 * keys + values
        self.qkv_proj = Projection(c.hidden_size, self.channels, c.dtype, std)
        self.conv_weight = self.create_parameter(
            (self.width, self.channels),
            default_initializer=_Drawn(_conv_weight))
        self.a_proj = Projection(c.hidden_size, self.heads, c.dtype, std)
        self.b_proj = Projection(c.hidden_size, self.heads, c.dtype, std)
        self.A_log = self.create_parameter(
            (self.heads,), dtype="float32", default_initializer=_Drawn(_a_log))
        self.dt_bias = self.create_parameter(
            (self.heads,), dtype="float32",
            default_initializer=_Drawn(_dt_bias))
        self.g_proj = Projection(c.hidden_size, values, c.dtype, std)
        self.o_norm = RMSNormF32(self.dv, c.rms_norm_eps, c.dtype)
        self.o_proj = Projection(values, c.hidden_size, c.dtype, std)

    def state_spec(self):
        return _state_spec(self.heads, self.dk, self.dv, self.width)

    def _conv(self, tail, z):
        """silu of the causal depthwise convolution of [tail | z] -> q, k
        normalised and v, float32, [b, s, heads, d]."""
        b, s = z.shape[0], z.shape[1]
        seen = jnp.concatenate([tail.astype(z.dtype), z], axis=1)
        w = self.conv_weight._data.astype(jnp.float32)
        c = sum(w[j] * seen[:, j:j + s] for j in range(self.width))
        c = jax.nn.silu(c)
        keys = self.heads * self.dk
        q = c[..., :keys].reshape(b, s, self.heads, self.dk)
        k = c[..., keys:2 * keys].reshape(b, s, self.heads, self.dk)
        v = c[..., 2 * keys:].reshape(b, s, self.heads, self.dv)

        def unit(x):
            return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + _L2_EPS)

        return unit(q) * self.dk ** -0.5, unit(k), v

    def forward(self, x, cache=None):
        b, s = x.shape[0], x.shape[1]
        served = cache is not None
        if not served:          # a whole sequence from nothing, every position real
            cache = SlotState.zeros(b, self.state_spec(), x.dtype,
                                    jnp.ones((b, s), bool))
        state, tail, valid = cache.read()
        with jax.named_scope("proj"):
            # float32 out of the matrix unit: what the convolution and the
            # delta rule read is not rounded to the weights' dtype first
            z = _dot_f32(x, self.qkv_proj.weight)
        with jax.named_scope("conv"):
            q, k, v = self._conv(tail, z)
        with jax.named_scope("gates"):
            beta = self.beta_scale * jax.nn.sigmoid(_dot_f32(x, self.b_proj.weight))
            g = -jnp.exp(self.A_log._data) * jax.nn.softplus(
                _dot_f32(x, self.a_proj.weight) + self.dt_bias._data)
            # a position that is not real leaves the state as it was
            beta = jnp.where(valid[..., None], beta, 0.0)
            g = jnp.where(valid[..., None], g, 0.0)
        with jax.named_scope("delta_rule"):
            if s == 1:
                o, state = gated_delta_step(q[:, 0], k[:, 0], v[:, 0],
                                            g[:, 0], beta[:, 0], state)
                o = o[:, None]
            else:
                o, state = gated_delta_chunked(q, k, v, g, beta, state)
        with jax.named_scope("out_gate"):
            gate = jax.nn.silu(_dot_f32(x, self.g_proj.weight))
            o = self.o_norm(o).reshape(b, s, -1) * gate
            o = o.astype(x.dtype)
        with jax.named_scope("out"):
            y = self.o_proj(o)
        if not served:
            return y
        with jax.named_scope("state_write"):
            cache = cache.replace(state, conv_tail(tail, z, valid))
        live = valid.any(1)[:, None, None, None]
        return y, cache, jnp.where(live, jnp.abs(state), 0.0).max()


class OlmoHybridAttention(nn.Layer):
    """The `full_attention` mixer. forward(x [b, s, hidden], cache) ->
    y, and the new cache when one was given (a handle of nn/kv_cache.py)."""

    def __init__(self, config: OlmoHybridConfig):
        super().__init__(dtype=config.dtype)
        c = config
        self.num_heads, self.kv_heads = (c.num_attention_heads,
                                         c.num_key_value_heads)
        self.head_dim = c.head_dim
        std = c.initializer_range
        kv = self.kv_heads * c.head_dim
        self.q_proj = Projection(c.hidden_size, c.hidden_size, c.dtype, std)
        self.k_proj = Projection(c.hidden_size, kv, c.dtype, std)
        self.v_proj = Projection(c.hidden_size, kv, c.dtype, std)
        self.o_proj = Projection(c.hidden_size, c.hidden_size, c.dtype, std)
        self.q_norm = RMSNormF32(c.hidden_size, c.rms_norm_eps, c.dtype)
        self.k_norm = RMSNormF32(kv, c.rms_norm_eps, c.dtype)

    def forward(self, x, cache=None):
        b, s = x.shape[0], x.shape[1]
        groups = self.num_heads // self.kv_heads
        alone = cache is None or cache.fresh
        with jax.named_scope("qkv"):
            q, k, v = self.q_proj(x), self.k_proj(x), self.v_proj(x)
        with jax.named_scope("qk_norm"):
            q, k = self.q_norm(q), self.k_norm(k)
        q = q.reshape(b, s, self.kv_heads, groups, self.head_dim)
        k = k.reshape(b, s, self.kv_heads, self.head_dim)
        v = v.reshape(b, s, self.kv_heads, self.head_dim)
        pos, seen = None, None
        if cache is not None:
            pos = cache.positions(s)
            with jax.named_scope("cache_write"):
                *rows, cache = cache.update(k, v)
            seen = None if alone else rows
        with jax.named_scope("core"):
            o = slot_attention.core(q, k, v, pos, seen, cache)
        with jax.named_scope("out"):
            out = self.o_proj(o.reshape(b, s, -1))
        return out if cache is None else (out, cache)


class OlmoHybridBlock(nn.Layer):
    def __init__(self, config: OlmoHybridConfig, index: int):
        super().__init__(dtype=config.dtype)
        c = config
        self.linear = c.layer_types[index] == LINEAR
        self.mixer = GatedDeltaNet(c) if self.linear else OlmoHybridAttention(c)
        self.post_attention_layernorm = RMSNormF32(c.hidden_size,
                                                   c.rms_norm_eps, c.dtype)
        self.mlp = SwiGLU(c.hidden_size, c.intermediate_size, c.dtype,
                          c.initializer_range)
        self.post_feedforward_layernorm = RMSNormF32(c.hidden_size,
                                                     c.rms_norm_eps, c.dtype)

    def forward(self, h, cache=None):
        """-> (h, new cache or None, the linear layer's largest |S| or
        None). Each scope takes its branch, the norm on it and the add."""
        absmax = None
        with jax.named_scope("linear_attn" if self.linear else "attn"):
            a = self.mixer(h, cache=cache)
            if cache is not None:
                a, cache, *rest = a
                absmax = rest[0] if rest else None
            h = h + self.post_attention_layernorm(a)
        with jax.named_scope("mlp"):
            h = h + self.post_feedforward_layernorm(self.mlp(h))
        return h, cache, absmax


class OlmoHybridModel(nn.Layer):
    """ids [b, s] -> hidden states after the final norm. With `caches` it
    returns (h, new caches, stats): `state_absmax` is the largest |S| any
    linear layer holds for a row with a real position (beta reaches 2, so a
    state that diverges should be seen)."""

    def __init__(self, config: OlmoHybridConfig):
        super().__init__(dtype=config.dtype)
        self.config = config
        self.embed_tokens = Projection(config.vocab_size, config.hidden_size,
                                       config.dtype, config.initializer_range)
        self.layers = nn.LayerList([OlmoHybridBlock(config, i)
                                    for i in range(config.num_hidden_layers)])
        self.norm = RMSNormF32(config.hidden_size, config.rms_norm_eps,
                               config.dtype)

    def forward(self, input_ids, caches=None):
        ids = input_ids._data if isinstance(input_ids, Tensor) else input_ids
        with jax.named_scope("embed"):
            h = jnp.take(self.embed_tokens.weight._data, ids, axis=0)
        new_caches, absmax = [], []
        for i, blk in enumerate(self.layers):
            h, c, m = blk(h, None if caches is None else caches[i])
            new_caches.append(c)
            if m is not None:
                absmax.append(m)
        with jax.named_scope("final_norm"):
            h = self.norm(h)
        if caches is None:
            return Tensor(h)
        stats = {"state_absmax": jnp.stack(absmax).max()} if absmax else {}
        return Tensor(h), new_caches, stats


class OlmoHybridForCausalLM(nn.Layer):
    """forward(ids [b, s]) -> logits [b, s, vocab]."""

    # what a decode dispatch reports beside its tokens, and how the engine
    # folds the values of the steps it fused
    serving_step_stats = {"state_absmax": "max"}

    def __init__(self, config: OlmoHybridConfig):
        super().__init__(dtype=config.dtype)
        self.config = config
        self.model = OlmoHybridModel(config)
        self.lm_head = Projection(config.hidden_size, config.vocab_size,
                                  config.dtype, config.initializer_range)

    def forward(self, input_ids):
        return self._head_logits(self.model(input_ids))

    def _head_logits(self, h):
        """Hidden states -> vocab logits (shared by forward and decode)."""
        data = h._data if isinstance(h, Tensor) else h
        with jax.named_scope("lm_head"):
            return Tensor(self.lm_head(data))

    # ---- what ServingEngine asks of a model -----------------------------
    def serving_backbone(self):
        """(the layer called with (ids, caches=...), its prefix in
        state_dict)."""
        return self.model, "model."

    def kv_cache_spec(self, max_seq_len: int):
        return kv_cache_spec(self.config, max_seq_len)
