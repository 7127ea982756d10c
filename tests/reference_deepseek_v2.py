"""The `deepseek_v2` decoder (DeepSeek-V2) in plain jax.numpy: float32, every
matmul at the highest precision, no kernel, no cache, no sorting trick, and
attention in the EXPANDED form only: keys and values a head are projected
from the latent and attended as any multi-head attention; the absorbed form
the program decodes with does not appear here. One sequence at a time; `h`
is `[s, hidden]` throughout.

It follows the published `config.json` keys (`cfg` is that dictionary, with
`n_routed_experts` the router's published width):

  h      = h + attn(RMSNorm(h));  h = h + f(RMSNorm(h))
  q      = RMSNorm(a W_qa) W_qb                  (or a W_q, `q_lora_rank` null)
  [c|kr] = a W_kva;  n = RMSNorm(c);  [k_n | v] = n W_kvb
  score  = (q_n . k_n + rope(q_r) . rope(k_r)) * scale, causal softmax
  f      = SwiGLU (dense layers) or sum_k w_k E_k(m) + S(m)
  logits = RMSNorm(h_L) W_head                   (untied)

Departures from the published description, each immaterial with seeded
random weights: the checkpoint stores the rotary columns of W_qb and W_kva
interleaved and de-interleaves them before the rotation, a fixed permutation
of output columns; here, as in the program, the columns are rotated by
halves as stored. `seq_aux` is a training loss and has no part here.

It shares no code with paddle_tpu/models/deepseek_v2.py. It only reads that
model's `state_dict` by name, so it knows the layout the program stores:
every matrix is [in, out] (y = x @ W); the routed experts are stacked,
`w_gate` and `w_up` [held, hidden, width], `w_down` [held, width, hidden],
and hold the experts `first .. first + count` of the published
`n_routed_experts`; the shared experts are one SwiGLU of their joint width.

The share of a deployment is given as arguments: `experts=(first, count)`
computes only the part of an expert layer's result that those routed experts
give (the router still scores all of them and chooses over all of them); the
shared expert is added when `shared` is true; `vocab=(first, count)` gives
the logits of that slice of the head's columns. The default is every expert
and every column the state holds.

The small pieces (`inv_freq`, `softmax_scale`, `kept_row`, `value_head_of`,
`group_limited`, `route_weights`, `shared_expert`) are functions of their
own so that a test can replace one by a wrong one and see the comparison
fail.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

EXPERT_BLOCK = 8      # experts upcast and computed at a time
HEAD_BLOCK = 8        # heads attended at a time


def rms_norm(x, w, eps):
    x = x.astype(jnp.float32)
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def mscale(factor: float, x: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * x * math.log(factor) + 1.0


def softmax_scale(cfg: dict) -> float:
    scale = 1.0 / math.sqrt(cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"])
    rs = cfg.get("rope_scaling")
    if rs:
        scale *= mscale(rs["factor"], rs.get("mscale_all_dim", 0)) ** 2
    return scale


def inv_freq(cfg: dict):
    """[d_r / 2]: YaRN's frequencies. Pair i keeps theta^(-2i/d) below
    `low`, runs `factor` times slower from `high` on, and is blended by a
    linear ramp between; low and high are the pairs that make `beta_fast`
    and `beta_slow` turns over the original context."""
    d, theta = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    f = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    rs = cfg.get("rope_scaling")
    if not rs:
        return f

    def corr(turns):
        return d * math.log(rs["original_max_position_embeddings"]
                            / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), d - 1)
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return f * (1 - ramp) + f / rs["factor"] * ramp


def rope(x, cfg: dict):
    """Rotate-half RoPE at the token's absolute position; x [s, heads, d]."""
    s, _, d = x.shape
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq(cfg)[None, :]
    rs = cfg.get("rope_scaling")
    amp = (mscale(rs["factor"], rs.get("mscale", 1))
           / mscale(rs["factor"], rs.get("mscale_all_dim", 0))) if rs else 1.0
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :] * amp
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :] * amp
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + rot * sin


def value_head_of(query_head: int) -> int:
    return query_head


def kept_row(row):
    """What is kept of a position, [s, kv_lora_rank + d_r], as the keys and
    values are projected from it: the row itself (a control keeps it in a
    lower precision)."""
    return row


def attention(p: dict, a, cfg: dict):
    """a [s, hidden] (already normalised) -> ([s, hidden], row): `row`
    [s, kv_lora_rank + d_r] = [n_t | rope(k_r,t)] is what a latent cache of
    this layer would hold a position."""
    s = a.shape[0]
    nh, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    r = cfg["kv_lora_rank"]
    f32 = {k: v.astype(jnp.float32) for k, v in p.items()}
    if cfg.get("q_lora_rank"):
        q = rms_norm(a @ f32["q_a_proj.weight"], f32["q_a_layernorm.weight"],
                     eps) @ f32["q_b_proj.weight"]
    else:
        q = a @ f32["q_proj.weight"]
    q = q.reshape(s, nh, dn + dr)
    q_n, q_r = q[..., :dn], rope(q[..., dn:], cfg)
    ckr = a @ f32["kv_a_proj_with_mqa.weight"]
    n = rms_norm(ckr[:, :r], f32["kv_a_layernorm.weight"], eps)
    k_r = rope(ckr[:, None, r:], cfg)[:, 0]                       # [s, dr]
    row = kept_row(jnp.concatenate([n, k_r], axis=-1))
    n, k_r = row[:, :r], row[:, r:]
    kv = (n @ f32["kv_b_proj.weight"]).reshape(s, nh, dn + dv)
    k_n, v = kv[..., :dn], kv[..., dn:]
    scale = softmax_scale(cfg)
    mask = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    outs = []
    for h0 in range(0, nh, HEAD_BLOCK):
        hs = list(range(h0, min(nh, h0 + HEAD_BLOCK)))
        vs = jnp.stack([v[:, value_head_of(i)] for i in hs], 0)
        scores = (jnp.einsum("shd,thd->hst", q_n[:, hs], k_n[:, hs])
                  + jnp.einsum("shd,td->hst", q_r[:, hs], k_r)) * scale
        att = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("hst,htd->shd", att, vs))
    o = jnp.concatenate(outs, axis=1).reshape(s, nh * dv)
    return o @ f32["o_proj.weight"], row


def swiglu(m, w_gate, w_up, w_down):
    return (jax.nn.silu(m @ w_gate) * (m @ w_up)) @ w_down


def group_limited(scores, cfg: dict):
    """The scores the choice is made over: every expert outside the
    `topk_group` best groups (a group's score is its best expert's) at 0."""
    groups = cfg["n_group"] if cfg.get("topk_method") == \
        "group_limited_greedy" else 1
    if groups == 1:
        return scores
    s, e = scores.shape
    best = scores.reshape(s, groups, e // groups).max(-1)          # [s, G]
    _, kept = jax.lax.top_k(best, cfg["topk_group"])
    stays = jax.nn.one_hot(kept, groups, dtype=jnp.float32).sum(1) > 0
    return jnp.where(jnp.repeat(stays, e // groups, axis=1), scores, 0.0)


def route_weights(scores, sel, cfg: dict):
    """The weights of the chosen experts: their scores, times
    `routed_scaling_factor` where they are not normalised."""
    w = jnp.take_along_axis(scores, sel, axis=-1)
    if cfg["norm_topk_prob"]:
        return w / (w.sum(-1, keepdims=True) + 1e-20)
    return w * cfg["routed_scaling_factor"]


def route(p: dict, m, cfg: dict):
    """-> (sel [s, k] int, w [s, k], margin [s]): the experts of each token,
    their weights, and how far the k-th choice's score stands above the
    (k+1)-th's among those the choice is made over (a small margin is a
    choice that rounding can flip)."""
    k = cfg["num_experts_per_tok"]
    logits = m @ p["router.weight"].astype(jnp.float32)
    if cfg["scoring_func"] == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    else:
        scores = jax.nn.sigmoid(logits)
    top, sel = jax.lax.top_k(group_limited(scores, cfg), k + 1)
    sel = sel[:, :k]
    return sel, route_weights(scores, sel, cfg), top[:, k - 1] - top[:, k]


def shared_expert(p: dict, m):
    return swiglu(m, *(p[f"shared_experts.{n}.weight"].astype(jnp.float32)
                       for n in ("gate_proj", "up_proj", "down_proj")))


def moe(p: dict, m, cfg: dict, experts=None, shared=True, base: int = 0):
    """m [s, hidden] -> ([s, hidden], sel, margin). Every token gets its k
    experts: no capacity, nothing dropped. `base` is the published number of
    the first expert the state holds."""
    first, count = experts if experts is not None else (
        base, p["experts.w_gate"].shape[0])
    sel, w, margin = route(p, m, cfg)
    coef = (jax.nn.one_hot(sel, cfg["n_routed_experts"], dtype=jnp.float32)
            * w[..., None]).sum(1)                              # [s, E]
    out = jnp.zeros(m.shape, jnp.float32)
    for e0 in range(first, first + count, EXPERT_BLOCK):
        n = min(EXPERT_BLOCK, first + count - e0)
        blk = slice(e0 - base, e0 - base + n)
        wg = p["experts.w_gate"][blk].astype(jnp.float32)
        wu = p["experts.w_up"][blk].astype(jnp.float32)
        wd = p["experts.w_down"][blk].astype(jnp.float32)
        y = jax.nn.silu(jnp.einsum("sh,ehi->sei", m, wg)) \
            * jnp.einsum("sh,ehi->sei", m, wu)
        d = jnp.einsum("sei,eih->seh", y, wd)
        out = out + jnp.einsum("seh,se->sh", d, coef[:, e0:e0 + n])
    if shared and cfg["n_shared_experts"]:
        out = out + shared_expert(p, m)
    return out, sel, margin


def layer_state(state: dict, l: int) -> dict:
    """The arrays of layer `l`, by their names inside the layer."""
    prefix = f"model.layers.{l}."
    return {k[len(prefix):]: v for k, v in state.items()
            if k.startswith(prefix)}


def embed(state: dict, ids, cfg: dict):
    return state["model.embed_tokens.weight"][ids].astype(jnp.float32)


def layer(p: dict, h, l: int, cfg: dict, experts=None, shared=True,
          base: int = 0):
    """One block. -> (h, info); info holds the layer's latent `row`s (see
    `attention`) and, on an expert layer, `sel` and `margin`."""
    eps = cfg["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        attn_p = {k[len("self_attn."):]: v for k, v in p.items()
                  if k.startswith("self_attn.")}
        a, row = attention(
            attn_p, rms_norm(h, p["input_layernorm.weight"], eps), cfg)
        h = h + a
        m = rms_norm(h, p["post_attention_layernorm.weight"], eps)
        mlp_p = {k[len("mlp."):]: v for k, v in p.items()
                 if k.startswith("mlp.")}
        info = {"row": row}
        if l < cfg["first_k_dense_replace"]:
            f = swiglu(m, *(mlp_p[f"{n}.weight"].astype(jnp.float32)
                            for n in ("gate_proj", "up_proj", "down_proj")))
        else:
            f, sel, margin = moe(mlp_p, m, cfg, experts, shared, base)
            info.update(sel=sel, margin=margin)
        return h + f, info


def head(state: dict, h, cfg: dict, vocab=None):
    """[n, hidden] hidden states -> [n, vocab] logits, of the columns
    `vocab` = (first, count) where given."""
    w = state["lm_head.weight"]
    if vocab is not None:
        w = w[:, vocab[0]:vocab[0] + vocab[1]]
    with jax.default_matmul_precision("highest"):
        return rms_norm(h, state["model.norm.weight"], cfg["rms_norm_eps"]) \
            @ w.astype(jnp.float32)


def hidden_states(state: dict, ids, cfg: dict, experts=None, base: int = 0):
    """[s] token ids -> ([s, hidden] before the final norm, [info a layer])."""
    h, infos = embed(state, ids, cfg), []
    for l in range(cfg["num_hidden_layers"]):
        h, info = layer(layer_state(state, l), h, l, cfg, experts, base=base)
        infos.append(info)
    return h, infos


def logits(state: dict, ids, cfg: dict, experts=None, base: int = 0,
           vocab=None):
    """[s] ids -> [s, vocab] float32 logits."""
    return head(state, hidden_states(state, ids, cfg, experts, base)[0], cfg,
                vocab)
