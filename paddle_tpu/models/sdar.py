"""The `sdar_moe` decoder family (JetLM SDAR-30B-A3B): the Qwen3-MoE block
under a block-causal mask, which generates by diffusion over blocks and not
one token after another; for the serving path.

What a block is (config keys from the published `config.json`; what is not a
key from SDAR's published modelling code and `generate.py`, listed under
`assumed` in benchmarks/configs/sdar-30b-a3b.json):

- h0 = E[ids];
- h += attn(norm(h)): 32 query heads over 4 key/value heads of 128, q and k
  RMS-normalised per head, RoPE (rotate-half, theta 1e6, absolute position)
  on both, no bias, no gate, no window on any layer;
- h += moe(norm(h)): `nn.RoutedExperts`, softmax router over all 128
  experts, top-8, weights divided by their sum (`norm_topk_prob`), dropless,
  no shared expert; every layer is an expert layer (`decoder_sparse_step` 1,
  no `mlp_only_layers`: `intermediate_size` is the width of a dense MLP
  that no published layer has);
- logits = norm(h) W_head, untied. A logit at position i predicts the token
  AT position i: nothing is shifted.

**The mask is block-causal**: with blocks of `block_length` positions, query
p sees key t iff `t // B <= p // B` (nn/kv_cache.py `block_causal_mask`), in
every forward, over a prompt and over a block being denoised alike. The
model declares how it generates, `generation = BlockDiffusion(block_length,
mask_token_id)`, and `ServingEngine` builds its block-step decode program
from that (serving/engine.py `_build_block_decode`, serving/diffusion.py).

The model computes in the dtype its weights have (`SdarConfig.dtype`):
parameters are drawn on the device straight into that dtype, one at a time.
Norms, softmax, RoPE and the router run in float32. `forward(ids)` gives
logits under the block-causal mask; training is not written (its loss needs
the noise schedule, which `config.json` does not give).

Serving: `kv_cache_spec` declares a `full` cache a layer; the attention
takes the handle it is given, calls `positions` and `update` once and masks
what comes back by the block-causal test. A prefill over a fresh cache
attends the chunk's own keys a block of queries at a time.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import nn
from ..core.tensor import Tensor
from ..nn.kv_cache import BlockDiffusion, KVLayerSpec, block_causal_mask
from .afmoe import _QUERY_BLOCK, _Norm, _Weight, _attend, _rope


class SdarConfig:
    def __init__(self, vocab_size=151936, hidden_size=2048,
                 intermediate_size=6144, moe_intermediate_size=768,
                 num_hidden_layers=48, num_attention_heads=32,
                 num_key_value_heads=4, head_dim=128, num_experts=128,
                 num_experts_per_tok=8, norm_topk_prob=True,
                 rms_norm_eps=1e-6, rope_theta=1000000.0,
                 max_position_embeddings=32768, hidden_act="silu",
                 attention_bias=False, tie_word_embeddings=False,
                 use_sliding_window=False, sliding_window=None,
                 max_window_layers=48, rope_scaling=None,
                 mlp_only_layers=(), decoder_sparse_step=1,
                 dtype="float32", initializer_range=0.02, block_length=4,
                 mask_token_id=151669, experts_held=None):
        # what the published family can say and this model does not compute
        if use_sliding_window:
            raise ValueError("use_sliding_window true: every sdar_moe layer "
                             "here is full attention")
        if rope_scaling is not None:
            raise ValueError(f"rope_scaling {rope_scaling!r}: only the plain "
                             f"RoPE (null) is written")
        if attention_bias:
            raise ValueError("attention_bias true: the projections here "
                             "have no bias")
        if tie_word_embeddings:
            raise ValueError("tie_word_embeddings true: the output head "
                             "here is untied")
        if mlp_only_layers:
            raise ValueError(f"mlp_only_layers {list(mlp_only_layers)!r}: "
                             f"every layer here is an expert layer")
        if int(decoder_sparse_step) != 1:
            raise ValueError(f"decoder_sparse_step {decoder_sparse_step}: "
                             f"every layer here is an expert layer (1)")
        if hidden_act != "silu":
            raise ValueError(f"hidden_act {hidden_act!r}: the experts are "
                             f"SwiGLU (silu)")
        if num_attention_heads % num_key_value_heads:
            raise ValueError("query heads must be a multiple of key heads")
        if not 0 <= int(mask_token_id) < int(vocab_size):
            raise ValueError(f"mask_token_id {mask_token_id} lies outside "
                             f"the vocabulary of {vocab_size}")
        if int(block_length) < 1 or _QUERY_BLOCK % int(block_length):
            raise ValueError(f"block_length {block_length} must divide the "
                             f"prefill's query block of {_QUERY_BLOCK}")
        self.vocab_size = int(vocab_size)
        self.hidden_size = int(hidden_size)
        self.intermediate_size = int(intermediate_size)
        self.moe_intermediate_size = int(moe_intermediate_size)
        self.num_hidden_layers = int(num_hidden_layers)
        self.num_attention_heads = int(num_attention_heads)
        self.num_key_value_heads = int(num_key_value_heads)
        self.head_dim = int(head_dim)
        self.num_experts = int(num_experts)
        self.num_experts_per_tok = int(num_experts_per_tok)
        self.norm_topk_prob = bool(norm_topk_prob)
        self.rms_norm_eps = float(rms_norm_eps)
        self.rope_theta = float(rope_theta)
        self.max_position_embeddings = int(max_position_embeddings)
        self.max_window_layers = int(max_window_layers)     # inert
        self.tie_word_embeddings = False
        self.dtype = dtype
        self.initializer_range = float(initializer_range)
        self.block_length = int(block_length)
        self.mask_token_id = int(mask_token_id)
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
        such as `model_type`, are left aside)."""
        import inspect

        known = set(inspect.signature(cls.__init__).parameters) - {"self"}
        kw = {k: v for k, v in config.items() if k in known}
        kw.update(overrides)
        return cls(**kw)


def sdar_tiny(**kw):
    """The CPU tests' size: 3 layers, 4 / 2 heads of 16, 8 experts top-2,
    blocks of 4, the mask token the vocabulary's last id."""
    base = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                moe_intermediate_size=32, num_hidden_layers=3,
                num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                num_experts=8, num_experts_per_tok=2,
                max_position_embeddings=64, block_length=4,
                mask_token_id=255)
    base.update(kw)
    return SdarConfig(**base)


class SdarAttention(nn.Layer):
    def __init__(self, config: SdarConfig):
        super().__init__(dtype=config.dtype)
        c = config
        self.num_heads, self.kv_heads = (c.num_attention_heads,
                                         c.num_key_value_heads)
        self.head_dim, self.block = c.head_dim, c.block_length
        self.rope_theta = c.rope_theta
        width, std = c.num_attention_heads * c.head_dim, c.initializer_range
        self.q_proj = _Weight(c.hidden_size, width, c.dtype, std)
        self.k_proj = _Weight(c.hidden_size, self.kv_heads * c.head_dim,
                              c.dtype, std)
        self.v_proj = _Weight(c.hidden_size, self.kv_heads * c.head_dim,
                              c.dtype, std)
        self.o_proj = _Weight(width, c.hidden_size, c.dtype, std)
        self.q_norm = _Norm(c.head_dim, c.rms_norm_eps, c.dtype)
        self.k_norm = _Norm(c.head_dim, c.rms_norm_eps, c.dtype)

    def _prefill_core(self, q, k, v):
        """Block-causal attention of a whole chunk whose first token is
        position 0, a block of queries (a multiple of the diffusion block)
        at a time against the keys it can see, to the end of its last
        diffusion block, so that no [s, s] score matrix of all heads is
        alive."""
        s, outs = q.shape[1], []
        for q0 in range(0, s, _QUERY_BLOCK):
            q1 = min(s, q0 + _QUERY_BLOCK)
            k1 = min(s, -(-q1 // self.block) * self.block)
            mask = block_causal_mask(jnp.arange(k1)[None, None, :],
                                     jnp.arange(q0, q1)[None, :], self.block)
            outs.append(_attend(q[:, q0:q1], k[:, :k1], v[:, :k1], mask))
        return jnp.concatenate(outs, axis=1) if len(outs) > 1 else outs[0]

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
        with jax.named_scope("rope"):
            q = _rope(q, pos, self.rope_theta)
            k = _rope(k, pos, self.rope_theta)
        q = q.reshape(b, s, self.kv_heads, groups, self.head_dim)
        if cache is not None:
            with jax.named_scope("cache_write"):
                kc, vc, held, cache = cache.update(k, v)
        with jax.named_scope("core"):
            if alone:
                o = self._prefill_core(q, k, v)
            else:
                o = _attend(q, kc, vc,
                            block_causal_mask(held, pos, self.block))
        with jax.named_scope("out"):
            out = self.o_proj(o.reshape(b, s, self.num_heads * self.head_dim))
        return out if cache is None else (out, cache)


class SdarMoE(nn.RoutedExperts):
    """The routed experts held here; no shared expert. forward(m [b, s,
    hidden]) -> (f, touched, touched_held, max_load)."""

    def __init__(self, config: SdarConfig):
        c = config
        first, count = c.experts_held
        super().__init__(c.hidden_size, c.moe_intermediate_size,
                         c.num_experts, c.num_experts_per_tok, first=first,
                         count=count, route_norm=c.norm_topk_prob,
                         route_scale=1.0, dtype=c.dtype,
                         init_std=c.initializer_range, score_func="softmax")

    def forward(self, m):
        out, load = self.routed_load(m.reshape(-1, m.shape[-1]))
        touched, max_load = self.load_stats(load)
        return (out.reshape(m.shape), touched, self.touched_held(load),
                max_load)


class SdarBlock(nn.Layer):
    def __init__(self, config: SdarConfig):
        super().__init__(dtype=config.dtype)
        c = config
        self.input_layernorm = _Norm(c.hidden_size, c.rms_norm_eps, c.dtype)
        self.self_attn = SdarAttention(c)
        self.post_attention_layernorm = _Norm(c.hidden_size, c.rms_norm_eps,
                                              c.dtype)
        self.mlp = SdarMoE(c)

    def forward(self, h, cache=None):
        """-> (h, new cache or None, (touched, touched_held, max_load)).
        Each scope takes its branch's norm and the residual add."""
        with jax.named_scope("attn"):
            a = self.self_attn(self.input_layernorm(h), cache=cache)
            if cache is not None:
                a, cache = a
            h = h + a
        with jax.named_scope("moe"):
            f, *load = self.mlp(self.post_attention_layernorm(h))
            return h + f, cache, load


class SdarModel(nn.Layer):
    """ids [b, s] -> hidden states after the final norm. With `caches` it
    returns (h, new caches, stats): `stats` is what the forward's expert
    layers saw, `moe_touched` (mean over the layers of the experts of all
    published that received a row), `moe_touched_held` (of those held here)
    and `moe_max_load` (the most rows one expert received in a layer)."""

    def __init__(self, config: SdarConfig):
        super().__init__(dtype=config.dtype)
        self.config = config
        self.embed_tokens = _Weight(config.vocab_size, config.hidden_size,
                                    config.dtype, config.initializer_range)
        self.layers = nn.LayerList([SdarBlock(config)
                                    for _ in range(config.num_hidden_layers)])
        self.norm = _Norm(config.hidden_size, config.rms_norm_eps,
                          config.dtype)

    def forward(self, input_ids, caches=None):
        ids = input_ids._data if isinstance(input_ids, Tensor) else input_ids
        with jax.named_scope("embed"):
            h = jnp.take(self.embed_tokens.weight._data, ids, axis=0)
        new_caches, loads = [], []
        for i, blk in enumerate(self.layers):
            h, c, load = blk(h, None if caches is None else caches[i])
            new_caches.append(c)
            loads.append(load)
        with jax.named_scope("final_norm"):
            h = self.norm(h)
        if caches is None:
            return Tensor(h)
        touched, held, most = (jnp.stack(x) for x in zip(*loads))
        return Tensor(h), new_caches, {"moe_touched": touched.mean(),
                                       "moe_touched_held": held.mean(),
                                       "moe_max_load": most.max()}


class SdarForCausalLM(nn.Layer):
    """forward(ids [b, s]) -> logits [b, s, vocab] under the block-causal
    mask; the logit at position i is of the token at position i."""

    # what a decode dispatch reports beside its tokens, and how the engine
    # folds the values of the forwards it fused
    serving_step_stats = {"moe_touched": "mean", "moe_touched_held": "mean",
                          "moe_max_load": "max"}

    def __init__(self, config: SdarConfig):
        super().__init__(dtype=config.dtype)
        self.config = config
        # how this model generates: what ServingEngine builds its decode
        # program from
        self.generation = BlockDiffusion(config.block_length,
                                         config.mask_token_id)
        self.model = SdarModel(config)
        self.lm_head = _Weight(config.hidden_size, config.vocab_size,
                               config.dtype, config.initializer_range)

    def forward(self, input_ids):
        return self._head_logits(self.model(input_ids))

    def _head_logits(self, h):
        """Hidden states -> vocab logits (shared by forward and decode)."""
        data = h._data if isinstance(h, Tensor) else h
        with jax.named_scope("lm_head"):
            return Tensor(self.lm_head(data))

    def generate(self, *args, **kwargs):
        raise ValueError(
            "SdarForCausalLM generates by diffusion over blocks: generate()'s "
            "loop and beam search emit one token after another from the last "
            "position's logits, which this model's logits are not; serve it "
            "through ServingEngine.submit / step")

    # ---- what ServingEngine asks of a model -----------------------------
    def serving_backbone(self):
        """(the layer called with (ids, caches=...), its prefix in
        state_dict)."""
        return self.model, "model."

    def kv_cache_spec(self, max_seq_len: int):
        c = self.config
        return [KVLayerSpec("full", max_seq_len, c.num_key_value_heads,
                            c.head_dim)
                for _ in range(c.num_hidden_layers)]
