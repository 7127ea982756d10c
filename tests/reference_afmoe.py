"""The `afmoe` decoder (Arcee Trinity) in plain jax.numpy: float32, every
matmul at the highest precision, no kernel, no cache, no sorting trick. One
sequence at a time; `h` is `[s, hidden]` throughout.

It follows the published `config.json` keys and, for what is not a key, the
published `afmoe` modelling code (transformers `models/afmoe`): a gate on the
attention output, RMS-normalised q and k per head, RoPE on sliding layers
only, four norms a block with the second of each pair on the branch's output.

  h0     = E[ids] * sqrt(hidden)                       (mup_enabled)
  h      = h + RMSNorm_post_attn(attn(RMSNorm_in(h)))
  h      = h + RMSNorm_post_mlp(f(RMSNorm_pre_mlp(h)))
  logits = RMSNorm(h_L) W_head                         (untied)

It shares no code with paddle_tpu/models/afmoe.py. It only reads that model's
`state_dict` by name, so it knows the layout the program stores: every
matrix is [in, out] (y = x @ W); the routed experts are stacked, `w_gate` and
`w_up` [held, hidden, width], `w_down` [held, width, hidden], and hold the
experts `first .. first + count` of the published `num_experts`.

`experts=(first, count)` computes only the part of an expert layer's result
that those routed experts give (the router still scores all of them, top-k is
over all of them); the shared expert is added when `shared` is true. The
default is every expert the state holds and the shared expert.

The small pieces (`window_mask`, `uses_rope`, `kv_head_of`, `apply_gate`,
`qk_norm`, `route_weights`) are functions of their own so that a test can
replace one by a wrong one and see the comparison fail.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

EXPERT_BLOCK = 8      # experts upcast and computed at a time


def rms_norm(x, w, eps):
    x = x.astype(jnp.float32)
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def qk_norm(x, w, eps):
    """q or k, [s, heads, head_dim], normalised over the head's width."""
    return rms_norm(x, w, eps)


def uses_rope(layer_type: str) -> bool:
    return layer_type == "sliding_attention"


def rope(x, theta: float):
    """Rotate-half RoPE at the token's absolute position; x [s, heads, d]."""
    s, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + rot * sin


def window_mask(s: int, layer_type: str, window: int):
    """[s, s] bool: query i sees key j."""
    i = jnp.arange(s)[:, None]
    j = jnp.arange(s)[None, :]
    mask = j <= i
    if layer_type == "sliding_attention":
        mask = mask & (j > i - window)
    return mask


def kv_head_of(query_head: int, q_heads: int, kv_heads: int) -> int:
    return query_head // (q_heads // kv_heads)


def apply_gate(o, g):
    return o * jax.nn.sigmoid(g)


def attention(p: dict, a, layer_type: str, cfg: dict):
    """a [s, hidden] (already normalised) -> ([s, hidden], k, v): the keys
    (normalised, and rotated where the layer rotates) and the values, each
    [s, kv_heads, head_dim], are what a cache of this layer would hold."""
    s = a.shape[0]
    nh, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    f32 = {k: v.astype(jnp.float32) for k, v in p.items()}
    q = (a @ f32["q_proj.weight"]).reshape(s, nh, hd)
    k = (a @ f32["k_proj.weight"]).reshape(s, kvh, hd)
    v = (a @ f32["v_proj.weight"]).reshape(s, kvh, hd)
    g = a @ f32["gate_proj.weight"]
    q = qk_norm(q, f32["q_norm.weight"], eps)
    k = qk_norm(k, f32["k_norm.weight"], eps)
    if uses_rope(layer_type):
        q, k = rope(q, cfg["rope_theta"]), rope(k, cfg["rope_theta"])
    mask = window_mask(s, layer_type, cfg["sliding_window"])
    heads = []
    for i in range(nh):
        j = kv_head_of(i, nh, kvh)
        scores = q[:, i] @ k[:, j].T / math.sqrt(hd)
        att = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        heads.append(att @ v[:, j])
    o = jnp.stack(heads, axis=1).reshape(s, nh * hd)
    return apply_gate(o, g) @ f32["o_proj.weight"], k, v


def swiglu(m, w_gate, w_up, w_down):
    return (jax.nn.silu(m @ w_gate) * (m @ w_up)) @ w_down


def route_weights(scores, sel, bias, cfg: dict):
    """The weights of the chosen experts: the scores themselves (the bias
    chose, it does not weigh), normalised and scaled."""
    w = jnp.take_along_axis(scores, sel, axis=-1)
    if cfg["route_norm"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return w * cfg["route_scale"]


def route(p: dict, m, cfg: dict):
    """-> (sel [s, k] int, w [s, k], margin [s]): the experts of each token,
    their weights, and how far the k-th choice's biased score stands above
    the (k+1)-th's (a small margin is a choice that rounding can flip)."""
    k = cfg["num_experts_per_tok"]
    scores = jax.nn.sigmoid(m @ p["router.weight"].astype(jnp.float32))
    bias = p["expert_bias"].astype(jnp.float32)
    top, sel = jax.lax.top_k(scores + bias, k + 1)
    sel = sel[:, :k]
    return sel, route_weights(scores, sel, bias, cfg), top[:, k - 1] - top[:, k]


def moe(p: dict, m, cfg: dict, experts=None, shared=True, base: int = 0):
    """m [s, hidden] -> ([s, hidden], sel, margin). Every token gets its k
    experts: no capacity, nothing dropped. `base` is the published number of
    the first expert the state holds."""
    first, count = experts if experts is not None else (
        base, p["experts.w_gate"].shape[0])
    sel, w, margin = route(p, m, cfg)
    coef = (jax.nn.one_hot(sel, cfg["num_experts"], dtype=jnp.float32)
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
    if shared and cfg["num_shared_experts"]:
        out = out + swiglu(
            m, *(p[f"shared_experts.{n}.weight"].astype(jnp.float32)
                 for n in ("gate_proj", "up_proj", "down_proj")))
    return out, sel, margin


def layer_state(state: dict, l: int) -> dict:
    """The arrays of layer `l`, by their names inside the layer."""
    prefix = f"model.layers.{l}."
    return {k[len(prefix):]: v for k, v in state.items()
            if k.startswith(prefix)}


def embed(state: dict, ids, cfg: dict):
    h = state["model.embed_tokens.weight"][ids].astype(jnp.float32)
    return h * math.sqrt(cfg["hidden_size"]) if cfg["mup_enabled"] else h


def layer(p: dict, h, l: int, cfg: dict, experts=None, shared=True):
    """One block. -> (h, info); info holds the layer's `k` and `v` (see
    `attention`) and, on an expert layer, `sel` and `margin`."""
    eps = cfg["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        attn_p = {k[len("self_attn."):]: v for k, v in p.items()
                  if k.startswith("self_attn.")}
        a, keys, values = attention(
            attn_p, rms_norm(h, p["input_layernorm.weight"], eps),
            cfg["layer_types"][l], cfg)
        h = h + rms_norm(a, p["post_attention_layernorm.weight"], eps)
        m = rms_norm(h, p["pre_mlp_layernorm.weight"], eps)
        mlp_p = {k[len("mlp."):]: v for k, v in p.items()
                 if k.startswith("mlp.")}
        info = {"k": keys, "v": values}
        if l < cfg["num_dense_layers"]:
            f = swiglu(m, *(mlp_p[f"{n}.weight"].astype(jnp.float32)
                            for n in ("gate_proj", "up_proj", "down_proj")))
        else:
            f, sel, margin = moe(mlp_p, m, cfg, experts, shared)
            info.update(sel=sel, margin=margin)
        return h + rms_norm(f, p["post_mlp_layernorm.weight"], eps), info


def head(state: dict, h, cfg: dict):
    """[n, hidden] hidden states -> [n, vocab] logits."""
    with jax.default_matmul_precision("highest"):
        return rms_norm(h, state["model.norm.weight"], cfg["rms_norm_eps"]) \
            @ state["lm_head.weight"].astype(jnp.float32)


def hidden_states(state: dict, ids, cfg: dict, experts=None):
    """[s] token ids -> ([s, hidden] before the final norm, [info a layer])."""
    h, infos = embed(state, ids, cfg), []
    for l in range(cfg["num_hidden_layers"]):
        h, info = layer(layer_state(state, l), h, l, cfg, experts)
        infos.append(info)
    return h, infos


def logits(state: dict, ids, cfg: dict):
    """[s] ids -> [s, vocab] float32 logits."""
    return head(state, hidden_states(state, ids, cfg)[0], cfg)
