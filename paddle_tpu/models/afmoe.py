"""The `afmoe` decoder family (Arcee Trinity): a routed-expert decoder with
window and full attention layers, for the serving path.

What a block is (config keys from the published `config.json`; what is not a
key from the published `afmoe` modelling code, listed under `assumed` in
benchmarks/configs/trinity-mini.json):

- h0 = E[ids] * sqrt(hidden) when `mup_enabled`;
- attention: 32 query heads over 4 key/value heads of 128, q and k
  RMS-normalised per head, RoPE (rotate-half, absolute position) on
  `sliding_attention` layers and no positional encoding on `full_attention`
  layers, a sliding layer sees the last `sliding_window` positions, the output
  is gated by `sigmoid(a Wg)` before the output projection; no bias anywhere;
- four norms a block: h += norm(attn(norm(h))), h += norm(f(norm(h)));
- f is a SwiGLU MLP on the first `num_dense_layers` layers, and on the rest
  `nn.RoutedExperts` (sigmoid router, top-k of all experts, dropless) beside
  `num_shared_experts` shared SwiGLU experts;
- logits = norm(h) W_head, untied.

The model computes in the dtype its weights have (`AfmoeConfig.dtype`):
parameters are drawn on the device straight into that dtype, one at a time,
so a bf16 model never has a float32 twin. Norms, softmax, RoPE and the router
run in float32. `forward(ids)` gives logits; training is not written.

Serving: `kv_cache_spec` declares a `window` cache of `sliding_window` rows
for a sliding layer and a `full` one for the rest, and each attention layer
hands its new keys and values to the cache handle it is given
(nn/kv_cache.py) and what comes back to ops/slot_attention.core, which masks
it by the position each row holds: causal, and the window on a sliding
layer. A prefill over a fresh cache attends the chunk's own keys blockwise
instead.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .. import nn
from ..core.tensor import Tensor
from ..nn.kv_cache import KVLayerSpec
from ..nn.layers.decoder_parts import Projection, RMSNormF32, SwiGLU, rope
from ..ops import slot_attention


class AfmoeConfig:
    def __init__(self, vocab_size=200192, hidden_size=2048,
                 intermediate_size=6144, moe_intermediate_size=1024,
                 num_hidden_layers=32, num_dense_layers=2,
                 num_attention_heads=32, num_key_value_heads=4, head_dim=128,
                 layer_types=None, sliding_window=2048,
                 global_attn_every_n_layers=4, num_experts=128,
                 num_experts_per_tok=8, num_shared_experts=1,
                 score_func="sigmoid", route_norm=True, route_scale=2.826,
                 rms_norm_eps=1e-5, rope_theta=10000.0,
                 max_position_embeddings=131072, mup_enabled=True,
                 tie_word_embeddings=False, dtype="float32",
                 initializer_range=0.02, expert_bias_std=0.0,
                 experts_held=None):
        if score_func != "sigmoid":
            raise ValueError(f"score_func {score_func!r}: the afmoe router "
                             f"scores with a sigmoid")
        if tie_word_embeddings:
            raise ValueError("afmoe's output head is untied")
        if num_attention_heads % num_key_value_heads:
            raise ValueError("query heads must be a multiple of key heads")
        if layer_types is None:
            n = int(global_attn_every_n_layers)
            layer_types = ["full_attention" if (i + 1) % n == 0
                           else "sliding_attention"
                           for i in range(num_hidden_layers)]
        if len(layer_types) != num_hidden_layers:
            raise ValueError(f"{len(layer_types)} layer_types for "
                             f"{num_hidden_layers} layers")
        self.vocab_size = int(vocab_size)
        self.hidden_size = int(hidden_size)
        self.intermediate_size = int(intermediate_size)
        self.moe_intermediate_size = int(moe_intermediate_size)
        self.num_hidden_layers = int(num_hidden_layers)
        self.num_dense_layers = int(num_dense_layers)
        self.num_attention_heads = int(num_attention_heads)
        self.num_key_value_heads = int(num_key_value_heads)
        self.head_dim = int(head_dim)
        self.layer_types = list(layer_types)
        self.sliding_window = int(sliding_window)
        self.num_experts = int(num_experts)
        self.num_experts_per_tok = int(num_experts_per_tok)
        self.num_shared_experts = int(num_shared_experts)
        self.score_func = score_func
        self.route_norm = bool(route_norm)
        self.route_scale = float(route_scale)
        self.rms_norm_eps = float(rms_norm_eps)
        self.rope_theta = float(rope_theta)
        self.max_position_embeddings = int(max_position_embeddings)
        self.mup_enabled = bool(mup_enabled)
        self.tie_word_embeddings = False
        self.dtype = dtype
        self.initializer_range = float(initializer_range)
        self.expert_bias_std = float(expert_bias_std)
        # (first, count) of the published experts this process holds
        self.experts_held = (tuple(experts_held) if experts_held is not None
                             else (0, self.num_experts))

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
        such as `n_group`, are left aside)."""
        import inspect

        known = set(inspect.signature(cls.__init__).parameters) - {"self"}
        kw = {k: v for k, v in config.items() if k in known}
        kw.update(overrides)
        return cls(**kw)


def afmoe_tiny(**kw):
    """The CPU tests' size: 1 dense + 4 expert layers in the pattern
    S,S,S,S,F, window 8, 8 experts top-2 + 1 shared."""
    base = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                moe_intermediate_size=32, num_hidden_layers=5,
                num_dense_layers=1, num_attention_heads=4,
                num_key_value_heads=2, head_dim=16,
                layer_types=["sliding_attention"] * 4 + ["full_attention"],
                sliding_window=8, num_experts=8, num_experts_per_tok=2,
                num_shared_experts=1, max_position_embeddings=64,
                expert_bias_std=0.01)
    base.update(kw)
    return AfmoeConfig(**base)


class AfmoeAttention(nn.Layer):
    def __init__(self, config: AfmoeConfig, layer_type: str):
        super().__init__(dtype=config.dtype)
        c = config
        self.num_heads, self.kv_heads = c.num_attention_heads, c.num_key_value_heads
        self.head_dim = c.head_dim
        self.window = c.sliding_window if layer_type == "sliding_attention" \
            else None
        self.rope_theta = c.rope_theta
        width, std = c.num_attention_heads * c.head_dim, c.initializer_range
        self.q_proj = Projection(c.hidden_size, width, c.dtype, std)
        self.k_proj = Projection(c.hidden_size, self.kv_heads * c.head_dim,
                                 c.dtype, std)
        self.v_proj = Projection(c.hidden_size, self.kv_heads * c.head_dim,
                                 c.dtype, std)
        self.gate_proj = Projection(c.hidden_size, width, c.dtype, std)
        self.o_proj = Projection(width, c.hidden_size, c.dtype, std)
        self.q_norm = RMSNormF32(c.head_dim, c.rms_norm_eps, c.dtype)
        self.k_norm = RMSNormF32(c.head_dim, c.rms_norm_eps, c.dtype)

    def forward(self, a, cache=None):
        """a [b, s, hidden], already normalised -> [b, s, hidden], and the
        new cache when one was given (a handle of nn/kv_cache.py)."""
        b, s = a.shape[0], a.shape[1]
        groups = self.num_heads // self.kv_heads
        # nothing held before this chunk (no cache, or a fresh one that
        # starts at position 0): its own keys are all there is to see
        alone = cache is None or cache.fresh
        pos = (jnp.arange(s, dtype=jnp.int32)[None, :] if cache is None
               else cache.positions(s))                           # [b|1, s]
        with jax.named_scope("qkv"):
            q = self.q_proj(a).reshape(b, s, self.num_heads, self.head_dim)
            k = self.k_proj(a).reshape(b, s, self.kv_heads, self.head_dim)
            v = self.v_proj(a).reshape(b, s, self.kv_heads, self.head_dim)
        with jax.named_scope("qk_norm"):
            q, k = self.q_norm(q), self.k_norm(k)
        if self.window is not None:
            with jax.named_scope("rope"):
                q = rope(q, pos, self.rope_theta)
                k = rope(k, pos, self.rope_theta)
        q = q.reshape(b, s, self.kv_heads, groups, self.head_dim)
        seen = None
        if cache is not None:
            with jax.named_scope("cache_write"):
                *rows, cache = cache.update(k, v)
            seen = None if alone else rows
        with jax.named_scope("core"):
            o = slot_attention.core(q, k, v, pos, seen, cache,
                                    window=self.window)
        with jax.named_scope("gate"):
            o = o.reshape(b, s, self.num_heads * self.head_dim)
            o = o * jax.nn.sigmoid(
                self.gate_proj(a).astype(jnp.float32)).astype(o.dtype)
        with jax.named_scope("out"):
            out = self.o_proj(o)
        return out if cache is None else (out, cache)


class AfmoeMoE(nn.RoutedExperts):
    """The routed experts held here beside the shared experts. forward(m
    [b, s, hidden]) -> (f, touched, max_load)."""

    def __init__(self, config: AfmoeConfig):
        c = config
        first, count = c.experts_held
        super().__init__(c.hidden_size, c.moe_intermediate_size,
                         c.num_experts, c.num_experts_per_tok, first=first,
                         count=count, route_norm=c.route_norm,
                         route_scale=c.route_scale,
                         bias_std=c.expert_bias_std, dtype=c.dtype,
                         init_std=c.initializer_range)
        self.shared_experts = (SwiGLU(
            c.hidden_size, c.moe_intermediate_size * c.num_shared_experts,
            c.dtype, c.initializer_range) if c.num_shared_experts else None)

    def forward(self, m):
        out, touched, max_load = self.routed(m.reshape(-1, m.shape[-1]))
        out = out.reshape(m.shape)
        if self.shared_experts is not None:
            with jax.named_scope("shared"):
                out = out + self.shared_experts(m)
        return out, touched, max_load


class AfmoeBlock(nn.Layer):
    def __init__(self, config: AfmoeConfig, index: int):
        super().__init__(dtype=config.dtype)
        c = config
        self.input_layernorm = RMSNormF32(c.hidden_size, c.rms_norm_eps,
                                          c.dtype)
        self.self_attn = AfmoeAttention(c, c.layer_types[index])
        self.post_attention_layernorm = RMSNormF32(c.hidden_size,
                                                   c.rms_norm_eps, c.dtype)
        self.pre_mlp_layernorm = RMSNormF32(c.hidden_size, c.rms_norm_eps,
                                            c.dtype)
        self.dense = index < c.num_dense_layers
        self.mlp = (SwiGLU(c.hidden_size, c.intermediate_size, c.dtype,
                           c.initializer_range) if self.dense
                    else AfmoeMoE(c))
        self.post_mlp_layernorm = RMSNormF32(c.hidden_size, c.rms_norm_eps,
                                             c.dtype)

    def forward(self, h, cache=None):
        """-> (h, new cache or None, (touched, max_load) or None). Each scope
        takes the two norms round its branch and the residual add."""
        with jax.named_scope("attn"):
            a = self.self_attn(self.input_layernorm(h), cache=cache)
            if cache is not None:
                a, cache = a
            h = h + self.post_attention_layernorm(a)
        if self.dense:
            with jax.named_scope("mlp"):
                f = self.mlp(self.pre_mlp_layernorm(h))
                return h + self.post_mlp_layernorm(f), cache, None
        with jax.named_scope("moe"):
            f, *load = self.mlp(self.pre_mlp_layernorm(h))
            return h + self.post_mlp_layernorm(f), cache, load


class AfmoeModel(nn.Layer):
    """ids [b, s] -> hidden states after the final norm. With `caches` it returns
    (h, new caches, stats): `stats` is what the step's expert layers saw,
    `moe_touched` (mean over the expert layers of the experts that received a
    row) and `moe_max_load` (the most rows one expert received in a layer)."""

    def __init__(self, config: AfmoeConfig):
        super().__init__(dtype=config.dtype)
        self.config = config
        self.embed_tokens = Projection(config.vocab_size, config.hidden_size,
                                       config.dtype, config.initializer_range)
        self.layers = nn.LayerList([AfmoeBlock(config, i)
                                    for i in range(config.num_hidden_layers)])
        self.norm = RMSNormF32(config.hidden_size, config.rms_norm_eps,
                               config.dtype)

    def forward(self, input_ids, caches=None):
        ids = input_ids._data if isinstance(input_ids, Tensor) else input_ids
        with jax.named_scope("embed"):
            h = jnp.take(self.embed_tokens.weight._data, ids, axis=0)
            if self.config.mup_enabled:
                h = (h.astype(jnp.float32)
                     * math.sqrt(self.config.hidden_size)).astype(h.dtype)
        new_caches, loads = [], []
        for i, blk in enumerate(self.layers):
            h, c, load = blk(h, None if caches is None else caches[i])
            new_caches.append(c)
            if load is not None:
                loads.append(load)
        with jax.named_scope("final_norm"):
            h = self.norm(h)
        if caches is None:
            return Tensor(h)
        stats = {}
        if loads:
            stats = {"moe_touched": jnp.stack([t for t, _ in loads]).mean(),
                     "moe_max_load": jnp.stack([m for _, m in loads]).max()}
        return Tensor(h), new_caches, stats


class AfmoeForCausalLM(nn.Layer):
    """forward(ids [b, s]) -> logits [b, s, vocab]."""

    # what a decode dispatch reports beside its tokens, and how the engine
    # folds the values of the steps it fused
    serving_step_stats = {"moe_touched": "mean", "moe_max_load": "max"}

    def __init__(self, config: AfmoeConfig):
        super().__init__(dtype=config.dtype)
        self.config = config
        self.model = AfmoeModel(config)
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
        c = self.config
        return [KVLayerSpec("window", min(c.sliding_window, max_seq_len),
                            c.num_key_value_heads, c.head_dim)
                if t == "sliding_attention" else
                KVLayerSpec("full", max_seq_len, c.num_key_value_heads,
                            c.head_dim)
                for t in c.layer_types]
