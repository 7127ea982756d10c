"""The `sdar_moe` decoder (JetLM SDAR-30B-A3B) and its generation by
diffusion over blocks in plain jax.numpy: float32, every matmul at the
highest precision, no kernel, no cache, no sorting trick, no batching. One
sequence at a time; `h` is `[s, hidden]` throughout.

It follows the published `config.json` keys and, for what is not a key,
SDAR's published modelling code (the Qwen3-MoE block: RMS-normalised q and k
per head, RoPE on both, a softmax router whose top-k weights are divided by
their sum) and its `generate.py` (`block_diffusion_generate`):

  h0     = E[ids]
  h      = h + attn(RMSNorm(h))          block-causal: query p sees key t
  h      = h + moe(RMSNorm(h))           iff t // B <= p // B
  logits = RMSNorm(h_L) W_head           (untied; the logit AT position i is
                                          of the token at position i)

Generation, for a prompt of P tokens and N new ones, blocks of B: the tokens
`[0, B * floor(P / B))` are context; the `P mod B` left over open the first
block as given tokens, the rest of it is the mask token. Then, a block at a
time: forward over the whole sequence so far and the block; if the block
holds no mask it is committed (its tokens are output, the next block starts
all mask); else a token is drawn at every position, its confidence is its
probability under the distribution it was drawn from, and the masked
positions that `select` names take their draw.

Departures from the published description, each on purpose:
- the mask token's own logit is minus infinity before a draw (`draw`): a
  trained model never predicts it, random weights would;
- at temperature 0 the draw is the argmax and its confidence its probability
  under the plain softmax of the logits (a delta would call every position
  certain and the order of unmasking would be left to right);
- sampled draws are not reproduced (no generator is shared with the
  program): `generate` is greedy, and `confidence` gives the probability of a
  GIVEN token under the tempered, filtered distribution, so that a sampled
  run can be judged draw by draw.

It shares no code with paddle_tpu/models/sdar.py or paddle_tpu/serving. It
only reads that model's `state_dict` by name, so it knows the layout the
program stores: every matrix is [in, out] (y = x @ W); the routed experts
are stacked, `w_gate` and `w_up` [experts, hidden, width], `w_down`
[experts, width, hidden].

The small pieces (`block_causal_mask`, `kv_head_of`, `qk_norm`,
`route_weights`, `logit_position`, `confidence`) are functions of their own
so that a test can replace one by a wrong one and see the comparison fail.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

EXPERT_BLOCK = 8      # experts upcast and computed at a time

REMASKING = ("low_confidence_static", "low_confidence_dynamic", "sequential")


def rms_norm(x, w, eps):
    x = x.astype(jnp.float32)
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def qk_norm(x, w, eps):
    """q or k, [s, heads, head_dim], normalised over the head's width."""
    return rms_norm(x, w, eps)


def rope(x, theta: float):
    """Rotate-half RoPE at the token's absolute position; x [s, heads, d]."""
    s, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + rot * sin


def block_causal_mask(s: int, block_length: int):
    """[s, s] bool: query i sees key j iff j's block is i's or an earlier
    one."""
    i = jnp.arange(s)[:, None] // block_length
    j = jnp.arange(s)[None, :] // block_length
    return j <= i


def kv_head_of(query_head: int, q_heads: int, kv_heads: int) -> int:
    return query_head // (q_heads // kv_heads)


def attention(p: dict, a, cfg: dict, block_length: int):
    """a [s, hidden] (already normalised) -> ([s, hidden], k, v): the keys
    (normalised and rotated) and the values, each [s, kv_heads, head_dim],
    are what a cache of this layer would hold."""
    s = a.shape[0]
    nh, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    f32 = {k: v.astype(jnp.float32) for k, v in p.items()}
    q = (a @ f32["q_proj.weight"]).reshape(s, nh, hd)
    k = (a @ f32["k_proj.weight"]).reshape(s, kvh, hd)
    v = (a @ f32["v_proj.weight"]).reshape(s, kvh, hd)
    q = rope(qk_norm(q, f32["q_norm.weight"], eps), cfg["rope_theta"])
    k = rope(qk_norm(k, f32["k_norm.weight"], eps), cfg["rope_theta"])
    mask = block_causal_mask(s, block_length)
    heads = []
    for i in range(nh):
        j = kv_head_of(i, nh, kvh)
        scores = q[:, i] @ k[:, j].T / math.sqrt(hd)
        att = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        heads.append(att @ v[:, j])
    o = jnp.stack(heads, axis=1).reshape(s, nh * hd)
    return o @ f32["o_proj.weight"], k, v


def route_weights(scores, sel, cfg: dict):
    """The weights of the chosen experts: their softmax scores, divided by
    their sum where `norm_topk_prob`; no scaling."""
    w = jnp.take_along_axis(scores, sel, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / w.sum(-1, keepdims=True)
    return w


def route(p: dict, m, cfg: dict):
    """-> (sel [s, k] int, w [s, k], margin [s]): the experts of each token,
    their weights, and how far the k-th choice's score stands above the
    (k+1)-th's, as a share of it (a small margin is a choice that rounding
    can flip; 128 softmax scores lie near 1/128, so the share says more
    than the difference)."""
    k = cfg["num_experts_per_tok"]
    scores = jax.nn.softmax(m @ p["router.weight"].astype(jnp.float32), -1)
    top, sel = jax.lax.top_k(scores, k + 1)
    sel = sel[:, :k]
    return (sel, route_weights(scores, sel, cfg),
            (top[:, k - 1] - top[:, k]) / top[:, k - 1])


def moe(p: dict, m, cfg: dict):
    """m [s, hidden] -> ([s, hidden], sel, margin): a dense mixture over all
    the experts, a token's weight zero at those it did not choose. Every
    token gets its k experts: no capacity, nothing dropped."""
    sel, w, margin = route(p, m, cfg)
    count = p["experts.w_gate"].shape[0]
    coef = (jax.nn.one_hot(sel, count, dtype=jnp.float32)
            * w[..., None]).sum(1)                              # [s, E]
    out = jnp.zeros(m.shape, jnp.float32)
    for e0 in range(0, count, EXPERT_BLOCK):
        blk = slice(e0, min(count, e0 + EXPERT_BLOCK))
        wg = p["experts.w_gate"][blk].astype(jnp.float32)
        wu = p["experts.w_up"][blk].astype(jnp.float32)
        wd = p["experts.w_down"][blk].astype(jnp.float32)
        y = jax.nn.silu(jnp.einsum("sh,ehi->sei", m, wg)) \
            * jnp.einsum("sh,ehi->sei", m, wu)
        d = jnp.einsum("sei,eih->seh", y, wd)
        out = out + jnp.einsum("seh,se->sh", d, coef[:, blk])
    return out, sel, margin


def layer_state(state: dict, l: int) -> dict:
    """The arrays of layer `l`, by their names inside the layer."""
    prefix = f"model.layers.{l}."
    return {k[len(prefix):]: v for k, v in state.items()
            if k.startswith(prefix)}


def embed(state: dict, ids):
    return state["model.embed_tokens.weight"][ids].astype(jnp.float32)


def layer(p: dict, h, cfg: dict, block_length: int):
    """One block. -> (h, info); info holds the layer's `k` and `v` (see
    `attention`), `sel` and `margin`."""
    eps = cfg["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        attn_p = {k[len("self_attn."):]: v for k, v in p.items()
                  if k.startswith("self_attn.")}
        a, keys, values = attention(
            attn_p, rms_norm(h, p["input_layernorm.weight"], eps), cfg,
            block_length)
        h = h + a
        mlp_p = {k[len("mlp."):]: v for k, v in p.items()
                 if k.startswith("mlp.")}
        f, sel, margin = moe(
            mlp_p, rms_norm(h, p["post_attention_layernorm.weight"], eps),
            cfg)
        return h + f, {"k": keys, "v": values, "sel": sel, "margin": margin}


def head(state: dict, h, cfg: dict):
    """[n, hidden] hidden states -> [n, vocab] logits."""
    with jax.default_matmul_precision("highest"):
        return rms_norm(h, state["model.norm.weight"], cfg["rms_norm_eps"]) \
            @ state["lm_head.weight"].astype(jnp.float32)


def hidden_states(state: dict, ids, cfg: dict, block_length: int):
    """[s] token ids -> ([s, hidden] before the final norm, [info a layer])."""
    h, infos = embed(state, ids), []
    for l in range(cfg["num_hidden_layers"]):
        h, info = layer(layer_state(state, l), h, cfg, block_length)
        infos.append(info)
    return h, infos


def forward(params: dict, ids, block_length: int, cfg: dict):
    """[s] ids -> [s, vocab] float32 logits under the block-causal mask."""
    return head(params, hidden_states(params, ids, cfg, block_length)[0], cfg)


# ---- generation ----------------------------------------------------------
def logit_position(i: int) -> int:
    """The row of a forward's logits that predicts the token at position i:
    the row AT i, nothing is shifted."""
    return i


def filtered(logits, temperature: float, top_k: int, top_p: float):
    """[V] logits -> the logits a token is drawn from: tempered, then only
    the `top_k` largest (ties at the k-th stay), then of those the nucleus: a
    value stays iff the mass of the values strictly above it is under
    `top_p` (so the value that crosses `top_p` stays, and the maximum always
    does). By a sort."""
    x = logits.astype(jnp.float32) / temperature
    if top_k > 0:
        kth = jnp.sort(x)[::-1][min(int(top_k), x.shape[0]) - 1]
        x = jnp.where(x < kth, -jnp.inf, x)
    if top_p < 1.0:
        order = jnp.argsort(-x)
        p = jax.nn.softmax(x)[order]
        # the mass strictly above a value: ties share the mass above the
        # first of them
        above = jnp.cumsum(p) - p
        first = jnp.searchsorted(-x[order], -x[order], side="left")
        keep = jnp.zeros(x.shape, bool).at[order].set(
            (above[first] < top_p) | (jnp.arange(x.shape[0]) == 0))
        x = jnp.where(keep, x, -jnp.inf)
    return x


def confidence(logits, token: int, temperature: float, top_k: int = 0,
               top_p: float = 1.0):
    """The probability of `token` under the distribution a token at this
    position is drawn from: the tempered, filtered softmax, or at
    temperature 0 the plain softmax."""
    x = logits.astype(jnp.float32) if temperature == 0.0 \
        else filtered(logits, temperature, top_k, top_p)
    return jax.nn.softmax(x)[token]


def draw(logits, mask_token_id: int):
    """[B, V] logits of a block -> (greedy tokens [B], their confidences
    [B]), the mask token never drawn."""
    logits = logits.astype(jnp.float32).at[:, mask_token_id].set(-jnp.inf)
    tokens = jnp.argmax(logits, axis=-1)
    return tokens, jnp.stack([confidence(logits[i], tokens[i], 0.0)
                              for i in range(logits.shape[0])])


def transfer_counts(block_length: int, denoising_steps: int):
    """How many positions each of a block's forwards unmasks: the block's
    positions spread evenly over the steps, the remainder on the first."""
    base, extra = divmod(block_length, denoising_steps)
    return [base + (i < extra) for i in range(denoising_steps)]


def select(masked, conf, n: int, remasking: str, threshold: float):
    """The positions of a block that take their draw: `masked` [B] bool
    (python), `conf` [B] floats -> a sorted list of positions."""
    if remasking not in REMASKING:
        raise ValueError(f"remasking {remasking!r}")
    cand = [i for i, m in enumerate(masked) if m]
    if remasking == "sequential":
        return cand[:n]
    # most confident first; of equals the leftmost
    ranked = sorted(cand, key=lambda i: (-float(conf[i]), i))
    if remasking == "low_confidence_dynamic":
        high = [i for i in cand if float(conf[i]) > threshold]
        if len(high) >= n:
            return high
    return sorted(ranked[:n])


def generate(params: dict, prompt, n_new: int, block_length: int,
             denoising_steps: int, remasking: str, threshold: float,
             cfg: dict, mask_token_id: int, eos_token_id=None,
             forward_fn=forward):
    """Greedy generation by diffusion over blocks. -> (tokens, trace): the
    `n_new` new tokens (fewer after an end token), and one entry a forward:
    {"offset", "block" (after the forward), "committed", "unmasked" (the
    positions this forward filled)}. Every forward runs over the whole
    sequence so far."""
    B = block_length
    prompt = [int(t) for t in prompt]
    head_len = len(prompt) // B * B
    held, given = prompt[:head_len], prompt[head_len:]
    block = given + [mask_token_id] * (B - len(given))
    counts = transfer_counts(B, denoising_steps)
    out, trace, step = [], [], 0
    while True:
        logits = forward_fn(params, jnp.asarray(held + block), B, cfg)
        rows = jnp.stack([logits[logit_position(len(held) + i)]
                          for i in range(B)])
        masked = [t == mask_token_id for t in block]
        if not any(masked):
            trace.append({"offset": len(held), "block": list(block),
                          "committed": True, "unmasked": []})
            new = block[len(given):][:n_new - len(out)]
            if eos_token_id is not None and eos_token_id in new:
                return out + new[:new.index(eos_token_id) + 1], trace
            out += new
            if len(out) >= n_new:
                return out, trace
            held, given = held + block, []
            block, step = [mask_token_id] * B, 0
            continue
        tokens, conf = draw(rows, mask_token_id)
        n = counts[step] if step < len(counts) else max(1, counts[-1])
        chosen = select(masked, [float(c) for c in conf], n, remasking,
                        threshold)
        for i in chosen:
            block[i] = int(tokens[i])
        trace.append({"offset": len(held), "block": list(block),
                      "committed": False, "unmasked": chosen})
        step += 1
