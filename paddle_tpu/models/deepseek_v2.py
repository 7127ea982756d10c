"""The `deepseek_v2` decoder family (DeepSeek-V2, and V2-Lite without the
low-rank queries): multi-head latent attention over ONE cached row a
position, and group-routed experts of which a process may hold a share, for
the serving path.

What a block is (config keys from the published `config.json`; what is not a
key is listed under `assumed` in benchmarks/configs/deepseek-v2.json):

- h = x + attn(RMSNorm(x)); h = h + f(RMSNorm(h)); no bias anywhere;
- attention (MLA), H heads, a = RMSNorm(x):
    q       = RMSNorm(a W_qa) W_qb  -> [H, d_n + d_r]   (`q_lora_rank`; with
              `q_lora_rank` null q = a W_q)
    [c|k_r] = a W_kva               -> kv_lora_rank + d_r: the latent, and
              ONE rotary key for all heads
    n       = RMSNorm(c);  [k_n | v] = n W_kvb -> [H, d_n + d_v]
    RoPE (rotate-half over d_r, absolute position, YaRN's blended
    frequencies) on q_r of every head and on k_r;
    score   = (q_n . k_n + q_r . k_r) * scale, scale = (d_n + d_r)^-1/2 *
              m(factor, mscale_all_dim)^2 with m(s, x) = 0.1 x ln s + 1;
    causal softmax in float32; y = (softmax v) W_o.
  A chunk with nothing held before it computes exactly that, keys and values
  expanded a head (`ops.latent_attention.expanded`). Everything else, a
  decode step first of all, takes the absorbed form of the same numbers
  (`ops.latent_attention.absorbed`): q_l = q_n W_uk^T, scores against the
  cached rows [n_t | rope(k_r,t)], o = (softmax n) W_uv, where W_uk and W_uv
  are the two halves a head of W_kvb (views of the one parameter). A decode
  step over a slot cache hands it the slots' lengths, and on the chip its
  softmax and both products are one kernel (ops/pallas/latent_decode.py).
- f is a SwiGLU MLP on the first `first_k_dense_replace` layers, and on the
  rest `nn.RoutedExperts` (softmax scores over all `n_routed_experts`, the
  `topk_group` best of `n_group` groups, `num_experts_per_tok` of what is
  left, weights not normalised and times `routed_scaling_factor`) beside
  `n_shared_experts` shared experts as one SwiGLU of their joint width;
- logits = RMSNorm(h) W_head, untied.

A process may hold a share of a deployment in which several chips share each
layer: `experts_held` = (first, count) of the published routed experts (the
router still scores all of them; rows for experts held elsewhere are computed
by nobody here), and `vocab_size` is the slice of the vocabulary held.

The model computes in the dtype its weights have: parameters are drawn on
the device straight into that dtype. Norms, softmax, RoPE and the router run
in float32. `forward(ids)` gives logits; training is not written.

Serving: `kv_cache_spec` declares a `latent` row of `kv_lora_rank +
qk_rope_head_dim` values a position a layer (nn/kv_cache.py) and nothing a
head.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..core.tensor import Tensor
from ..nn.kv_cache import LatentLayerSpec
from ..ops import latent_attention
from .afmoe import AfmoeMLP, _Norm, _Weight


def yarn_mscale(factor: float, mscale: float) -> float:
    """m(s, x) = 0.1 x ln s + 1; 1 where nothing is scaled."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_correction_range(rope_dim: int, theta: float, scaling: dict):
    """(low, high): the rotary pairs below `low` keep their frequency, those
    from `high` on are slowed by `factor`, and a ramp blends between."""
    original = scaling["original_max_position_embeddings"]

    def corr(rotations):
        return rope_dim * math.log(original / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(corr(scaling["beta_fast"])), 0)
    high = min(math.ceil(corr(scaling["beta_slow"])), rope_dim - 1)
    return low, high


def rope_inv_freq(rope_dim: int, theta: float, scaling=None):
    """[rope_dim / 2] float32: the rotary frequencies, YaRN's blend of the
    plain ones and the ones slowed by `factor` where `scaling` is given."""
    i = np.arange(0, rope_dim, 2, dtype=np.float64)
    freq = theta ** (-i / rope_dim)
    if scaling is None:
        return freq.astype(np.float32)
    low, high = yarn_correction_range(rope_dim, theta, scaling)
    ramp = np.clip((np.arange(rope_dim // 2) - low) / max(high - low, 1e-3),
                   0.0, 1.0)
    return (freq * (1 - ramp) + freq / scaling["factor"] * ramp).astype(
        np.float32)


class DeepseekV2Config:
    def __init__(self, vocab_size=102400, hidden_size=5120,
                 intermediate_size=12288, moe_intermediate_size=1536,
                 num_hidden_layers=60, first_k_dense_replace=1,
                 moe_layer_freq=1, num_attention_heads=128,
                 num_key_value_heads=None, q_lora_rank=1536,
                 kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
                 v_head_dim=128, n_routed_experts=160, num_experts_per_tok=6,
                 n_shared_experts=2, n_group=8, topk_group=3,
                 topk_method="group_limited_greedy", scoring_func="softmax",
                 norm_topk_prob=False, routed_scaling_factor=16.0,
                 rms_norm_eps=1e-6, rope_theta=10000.0, rope_scaling=None,
                 max_position_embeddings=163840, attention_bias=False,
                 hidden_act="silu", tie_word_embeddings=False,
                 dtype="float32", initializer_range=0.02, experts_held=None):
        if topk_method not in ("greedy", "group_limited_greedy"):
            raise ValueError(
                f"topk_method {topk_method!r}: this model routes 'greedy' "
                f"(the best of all experts) or 'group_limited_greedy'")
        if rope_scaling is not None and rope_scaling.get("type") != "yarn":
            raise ValueError(
                f"rope_scaling.type {rope_scaling.get('type')!r}: this "
                f"model rotates with 'yarn' or with no scaling (null)")
        if scoring_func not in ("softmax", "sigmoid"):
            raise ValueError(f"scoring_func {scoring_func!r}: the router "
                             f"scores with 'softmax' or 'sigmoid'")
        if moe_layer_freq != 1:
            raise ValueError(f"moe_layer_freq {moe_layer_freq}: every layer "
                             f"after the dense ones is an expert layer here")
        if attention_bias:
            raise ValueError("attention_bias: the projections have no bias")
        if hidden_act != "silu":
            raise ValueError(f"hidden_act {hidden_act!r}: the MLPs are SwiGLU")
        if tie_word_embeddings:
            raise ValueError("deepseek_v2's output head is untied")
        if num_key_value_heads not in (None, num_attention_heads):
            raise ValueError(
                f"num_key_value_heads {num_key_value_heads}: latent "
                f"attention has a key a query head "
                f"({num_attention_heads}), all from one latent")
        self.vocab_size = int(vocab_size)
        self.hidden_size = int(hidden_size)
        self.intermediate_size = int(intermediate_size)
        self.moe_intermediate_size = int(moe_intermediate_size)
        self.num_hidden_layers = int(num_hidden_layers)
        self.first_k_dense_replace = int(first_k_dense_replace)
        self.num_attention_heads = int(num_attention_heads)
        self.q_lora_rank = None if q_lora_rank is None else int(q_lora_rank)
        self.kv_lora_rank = int(kv_lora_rank)
        self.qk_nope_head_dim = int(qk_nope_head_dim)
        self.qk_rope_head_dim = int(qk_rope_head_dim)
        self.v_head_dim = int(v_head_dim)
        self.n_routed_experts = int(n_routed_experts)
        self.num_experts_per_tok = int(num_experts_per_tok)
        self.n_shared_experts = int(n_shared_experts)
        grouped = topk_method == "group_limited_greedy"
        self.n_group = int(n_group) if grouped else 1
        self.topk_group = int(topk_group) if grouped else 1
        self.topk_method = topk_method
        self.scoring_func = scoring_func
        self.norm_topk_prob = bool(norm_topk_prob)
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.rms_norm_eps = float(rms_norm_eps)
        self.rope_theta = float(rope_theta)
        self.rope_scaling = None if rope_scaling is None else dict(rope_scaling)
        self.max_position_embeddings = int(max_position_embeddings)
        self.tie_word_embeddings = False
        self.dtype = dtype
        self.initializer_range = float(initializer_range)
        # (first, count) of the published experts this process holds
        self.experts_held = (tuple(int(x) for x in experts_held)
                             if experts_held is not None
                             else (0, self.n_routed_experts))

    # the names the serving engine and the other models' configs use
    @property
    def num_layers(self):
        return self.num_hidden_layers

    @property
    def max_seq_len(self):
        return self.max_position_embeddings

    @property
    def softmax_scale(self) -> float:
        scale = (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5
        if self.rope_scaling is not None:
            m = yarn_mscale(self.rope_scaling["factor"],
                            self.rope_scaling.get("mscale_all_dim", 0))
            scale *= m * m
        return scale

    @property
    def rope_amplitude(self) -> float:
        """What cos and sin are multiplied by: m(s, mscale) / m(s,
        mscale_all_dim), 1 for the published pair of 0.707."""
        if self.rope_scaling is None:
            return 1.0
        s = self.rope_scaling["factor"]
        return yarn_mscale(s, self.rope_scaling.get("mscale", 1)) \
            / yarn_mscale(s, self.rope_scaling.get("mscale_all_dim", 0))

    @classmethod
    def from_dict(cls, config: dict, **overrides):
        """From a huggingface `config.json`, or from a benchmark
        configuration that is one chip's share of it: there
        `n_routed_experts` counts the experts HELD, `published` has the
        router's width under the same key, and `experts_held` says which."""
        import inspect

        known = set(inspect.signature(cls.__init__).parameters) - {"self"}
        kw = {k: v for k, v in config.items() if k in known}
        published = config.get("published", {}).get("n_routed_experts")
        if published is not None:
            first, count = kw.setdefault(
                "experts_held", (0, config["n_routed_experts"]))
            if count != config["n_routed_experts"]:
                raise ValueError(
                    f"experts_held {(first, count)} beside n_routed_experts "
                    f"{config['n_routed_experts']} held")
            kw["n_routed_experts"] = published
        kw.update(overrides)
        return cls(**kw)


def deepseek_v2_tiny(**kw):
    """The CPU tests' size: 1 dense + 3 expert layers, 4 heads, 16 experts
    in 4 groups, top 3 of 2 groups, 2 shared."""
    base = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                moe_intermediate_size=32, num_hidden_layers=4,
                first_k_dense_replace=1, num_attention_heads=4,
                q_lora_rank=24, kv_lora_rank=32, qk_nope_head_dim=16,
                qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=16,
                num_experts_per_tok=3, n_shared_experts=2, n_group=4,
                topk_group=2, routed_scaling_factor=16.0,
                max_position_embeddings=64,
                rope_scaling={"type": "yarn", "factor": 40, "beta_fast": 32,
                              "beta_slow": 1, "mscale": 0.707,
                              "mscale_all_dim": 0.707,
                              "original_max_position_embeddings": 16})
    base.update(kw)
    return DeepseekV2Config(**base)


def _rope(x, pos, inv_freq, amplitude: float):
    """x [b, s, heads, d] rotated by halves at pos [b or 1, s]."""
    d = x.shape[-1]
    ang = pos.astype(jnp.float32)[..., None] * inv_freq          # [b, s, d/2]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, :, None, :]
    y = x.astype(jnp.float32)
    rot = jnp.concatenate([-y[..., d // 2:], y[..., :d // 2]], -1)
    return ((y * cos + rot * sin) * amplitude).astype(x.dtype)


class DeepseekV2Attention(nn.Layer):
    def __init__(self, config: DeepseekV2Config):
        super().__init__(dtype=config.dtype)
        c = config
        self.heads = c.num_attention_heads
        self.d_n, self.d_r, self.d_v = (c.qk_nope_head_dim,
                                        c.qk_rope_head_dim, c.v_head_dim)
        self.rank = c.kv_lora_rank
        self.scale, self.amplitude = c.softmax_scale, c.rope_amplitude
        self.inv_freq = rope_inv_freq(c.qk_rope_head_dim, c.rope_theta,
                                      c.rope_scaling)
        std, q_width = c.initializer_range, self.heads * (self.d_n + self.d_r)
        if c.q_lora_rank is None:
            self.q_proj = _Weight(c.hidden_size, q_width, c.dtype, std)
        else:
            self.q_a_proj = _Weight(c.hidden_size, c.q_lora_rank, c.dtype, std)
            self.q_a_layernorm = _Norm(c.q_lora_rank, c.rms_norm_eps, c.dtype)
            self.q_b_proj = _Weight(c.q_lora_rank, q_width, c.dtype, std)
        self.low_rank_q = c.q_lora_rank is not None
        self.kv_a_proj_with_mqa = _Weight(c.hidden_size, self.rank + self.d_r,
                                          c.dtype, std)
        self.kv_a_layernorm = _Norm(self.rank, c.rms_norm_eps, c.dtype)
        self.kv_b_proj = _Weight(self.rank, self.heads * (self.d_n + self.d_v),
                                 c.dtype, std)
        self.o_proj = _Weight(self.heads * self.d_v, c.hidden_size, c.dtype,
                              std)

    def forward(self, a, cache=None):
        """a [b, s, hidden], already normalised -> [b, s, hidden], and the
        new cache when one was given (a `latent` handle of nn/kv_cache.py)."""
        b, s = a.shape[0], a.shape[1]
        # nothing held before this chunk (no cache, or a fresh one that
        # starts at position 0): its own keys are all there is to see
        alone = cache is None or cache.fresh
        pos = (jnp.arange(s, dtype=jnp.int32)[None, :] if cache is None
               else cache.positions(s))                           # [b|1, s]
        with jax.named_scope("q_lora"):
            q = (self.q_b_proj(self.q_a_layernorm(self.q_a_proj(a)))
                 if self.low_rank_q else self.q_proj(a))
            q = q.reshape(b, s, self.heads, self.d_n + self.d_r)
            q_n, q_r = q[..., :self.d_n], q[..., self.d_n:]
        with jax.named_scope("kv_latent"):
            ckr = self.kv_a_proj_with_mqa(a)
            n = self.kv_a_layernorm(ckr[..., :self.rank])
            k_r = ckr[..., self.rank:]
        with jax.named_scope("rope"):
            q_r = _rope(q_r, pos, self.inv_freq, self.amplitude)
            k_r = _rope(k_r[:, :, None, :], pos, self.inv_freq,
                        self.amplitude)[:, :, 0, :]
        if cache is not None:
            with jax.named_scope("cache_write"):
                rows, held, cache = cache.update(
                    jnp.concatenate([n, k_r], axis=-1))
        w_kvb = self.kv_b_proj.weight._data.reshape(
            self.rank, self.heads, self.d_n + self.d_v)
        if alone:
            with jax.named_scope("expand"):
                kv = jnp.einsum("bsr,rhd->bshd", n.astype(w_kvb.dtype), w_kvb)
            with jax.named_scope("core"):
                o = latent_attention.expanded(
                    q_n, q_r, kv[..., :self.d_n], k_r, kv[..., self.d_n:],
                    self.scale)
        else:
            # W_uk and W_uv, the two halves a head of the one parameter
            with jax.named_scope("absorb"):
                q_l = jnp.einsum("bshd,rhd->bshr", q_n,
                                 w_kvb[..., :self.d_n])
            # the new handle's offset counts the positions held now: one
            # a slot (SlotLatent) lets a decode step bound its rows by it
            with jax.named_scope("core"):
                o_l = latent_attention.absorbed(
                    q_l, q_r, rows, held <= pos[:, :, None], self.scale,
                    lengths=cache.offset)
            with jax.named_scope("unabsorb"):
                o = jnp.einsum("bshr,rhd->bshd", o_l, w_kvb[..., self.d_n:])
        with jax.named_scope("out"):
            out = self.o_proj(o.reshape(b, s, self.heads * self.d_v))
        return out if cache is None else (out, cache)


class DeepseekV2MoE(nn.RoutedExperts):
    """The routed experts held here beside the shared experts. forward(m
    [b, s, hidden]) -> (f, touched, touched_held, max_load)."""

    def __init__(self, config: DeepseekV2Config):
        c = config
        first, count = c.experts_held
        # the published rule: normalised weights are not scaled
        super().__init__(c.hidden_size, c.moe_intermediate_size,
                         c.n_routed_experts, c.num_experts_per_tok,
                         first=first, count=count,
                         route_norm=c.norm_topk_prob,
                         route_scale=(1.0 if c.norm_topk_prob
                                      else c.routed_scaling_factor),
                         dtype=c.dtype, init_std=c.initializer_range,
                         score_func=c.scoring_func, n_group=c.n_group,
                         topk_group=c.topk_group)
        self.shared_experts = (AfmoeMLP(
            c.hidden_size, c.moe_intermediate_size * c.n_shared_experts,
            c.dtype, c.initializer_range) if c.n_shared_experts else None)

    def forward(self, m):
        out, load = self.routed_load(m.reshape(-1, m.shape[-1]))
        out = out.reshape(m.shape)
        if self.shared_experts is not None:
            with jax.named_scope("shared"):
                out = out + self.shared_experts(m)
        touched, max_load = self.load_stats(load)
        return out, touched, self.touched_held(load), max_load


class DeepseekV2Block(nn.Layer):
    def __init__(self, config: DeepseekV2Config, index: int):
        super().__init__(dtype=config.dtype)
        c = config
        self.input_layernorm = _Norm(c.hidden_size, c.rms_norm_eps, c.dtype)
        self.self_attn = DeepseekV2Attention(c)
        self.post_attention_layernorm = _Norm(c.hidden_size, c.rms_norm_eps,
                                              c.dtype)
        self.dense = index < c.first_k_dense_replace
        self.mlp = (AfmoeMLP(c.hidden_size, c.intermediate_size, c.dtype,
                             c.initializer_range) if self.dense
                    else DeepseekV2MoE(c))

    def forward(self, h, cache=None):
        """-> (h, new cache or None, (touched, touched_held, max_load) or
        None). Each scope takes its branch's norm and the residual add."""
        with jax.named_scope("mla"):
            a = self.self_attn(self.input_layernorm(h), cache=cache)
            if cache is not None:
                a, cache = a
            h = h + a
        if self.dense:
            with jax.named_scope("mlp"):
                return (h + self.mlp(self.post_attention_layernorm(h)), cache,
                        None)
        with jax.named_scope("moe"):
            f, *load = self.mlp(self.post_attention_layernorm(h))
            return h + f, cache, load


class DeepseekV2Model(nn.Layer):
    """ids [b, s] -> hidden states after the final norm. With `caches` it
    returns (h, new caches, stats): `stats` is what the step's expert layers
    saw, `moe_touched` (mean over the expert layers of the experts of all
    published that received a row), `moe_touched_held` (of those held here)
    and `moe_max_load` (the most rows one expert received in a layer)."""

    def __init__(self, config: DeepseekV2Config):
        super().__init__(dtype=config.dtype)
        self.config = config
        self.embed_tokens = _Weight(config.vocab_size, config.hidden_size,
                                    config.dtype, config.initializer_range)
        self.layers = nn.LayerList([DeepseekV2Block(config, i)
                                    for i in range(config.num_hidden_layers)])
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
            if load is not None:
                loads.append(load)
        with jax.named_scope("final_norm"):
            h = self.norm(h)
        if caches is None:
            return Tensor(h)
        stats = {}
        if loads:
            touched, held, most = (jnp.stack(x) for x in zip(*loads))
            stats = {"moe_touched": touched.mean(),
                     "moe_touched_held": held.mean(),
                     "moe_max_load": most.max()}
        return Tensor(h), new_caches, stats


class DeepseekV2ForCausalLM(nn.Layer):
    """forward(ids [b, s]) -> logits [b, s, vocab]."""

    # what a decode dispatch reports beside its tokens, and how the engine
    # folds the values of the steps it fused
    serving_step_stats = {"moe_touched": "mean", "moe_touched_held": "mean",
                          "moe_max_load": "max"}

    def __init__(self, config: DeepseekV2Config):
        super().__init__(dtype=config.dtype)
        self.config = config
        self.model = DeepseekV2Model(config)
        self.lm_head = _Weight(config.hidden_size, config.vocab_size,
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
        return [LatentLayerSpec("latent", max_seq_len, c.kv_lora_rank,
                                c.qk_rope_head_dim)
                for _ in range(c.num_hidden_layers)]
