"""The `olmo_hybrid` decoder (Olmo-Hybrid) in plain jax.numpy: float32, every
matmul at the highest precision, no kernel, no cache, no batching, and the
gated delta rule ONE POSITION AT A TIME (never the chunked form). One
sequence at a time; `h` is `[s, hidden]` throughout.

It follows the published `config.json` keys. What is not a key follows the
conventions of the families the keys come from, each listed under `assumed`
in benchmarks/configs/olmo-hybrid-7b.json:

  h      = x + RMSNorm(mixer(x))                  (OLMo 2 / 3: the norm is
  h      = h + RMSNorm(W_down(silu(h W_gate) * (h W_up)))   on the branch)
  logits = RMSNorm(h_L) W_head                    (untied)

  full_attention:   q, k RMS-normalised over the whole projection; causal
                    softmax attention at head_dim^-1/2; no rotary encoding
                    (`rope_parameters.rope_theta` is null, read literally)
  linear_attention: z = x W_qkv; c_t = silu(sum_j w_j z_{t-3+j}); q, k
                    L2-normalised a head, q scaled by d_k^-1/2;
                    beta_t = 2 sigmoid(x W_b); g_t = -exp(A_log)
                    softplus(x W_a + dt_bias); S' = exp(g_t) S;
                    u = beta_t (v_t - S'^T k_t); S = S' + k_t u^T;
                    o_t = S^T q_t; y = (RMSNorm_dv(o_t) * silu(x W_g)) W_o

Departures from the published description: none known; the checkpoint's
tensor names are not: the three projections are read as one matrix
`qkv_proj.weight` [hidden, 2 H d_k + H d_v] and the three convolutions as
one `conv_weight` [width, channels], the layout the program stores.

`length` (a traced count) says how many of the `s` positions are real: the
state and the convolution's tail stop there, so that `info` holds what a
slot that has reached `length` positions should hold. The positions before
`length` do not depend on it.

It shares no code with paddle_tpu/models/olmo_hybrid.py. It only reads that
model's `state_dict` by name; every matrix is [in, out] (y = x @ W).

The small pieces (`real_positions`, `beta_of`, `delta_update`, `conv_input`,
`unit`, `held_state`, `held_tail`) are functions of their own so that a test can replace
one by a wrong one and see the comparison fail.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def rms_norm(x, w, eps):
    x = x.astype(jnp.float32)
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def swiglu(m, w_gate, w_up, w_down):
    return (jax.nn.silu(m @ w_gate) * (m @ w_up)) @ w_down


def attention(p: dict, x, cfg: dict):
    """x [s, hidden] -> ([s, hidden], k, v): the keys (normalised) and the
    values, [s, kv_heads, head_dim], are what a cache of this layer holds."""
    s = x.shape[0]
    nh, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, eps = cfg["hidden_size"] // nh, cfg["rms_norm_eps"]
    f32 = {k: v.astype(jnp.float32) for k, v in p.items()}
    q = rms_norm(x @ f32["q_proj.weight"], f32["q_norm.weight"], eps)
    k = rms_norm(x @ f32["k_proj.weight"], f32["k_norm.weight"], eps)
    q, k = q.reshape(s, nh, hd), k.reshape(s, kvh, hd)
    v = (x @ f32["v_proj.weight"]).reshape(s, kvh, hd)
    mask = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    heads = []
    for i in range(nh):
        j = i // (nh // kvh)
        scores = q[:, i] @ k[:, j].T / math.sqrt(hd)
        att = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        heads.append(att @ v[:, j])
    o = jnp.stack(heads, axis=1).reshape(s, nh * hd)
    return o @ f32["o_proj.weight"], k, v


def real_positions(s: int, length):
    """[s] bool: the positions whose inputs reach the state."""
    return jnp.arange(s) < length


def beta_of(b, cfg: dict):
    """The write strength from the projection `b = x W_b`."""
    return (2.0 if cfg["linear_allow_neg_eigval"] else 1.0) * jax.nn.sigmoid(b)


def unit(x):
    """L2-normalised over the last axis."""
    return x / jnp.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)


def conv_input(padded, t, width: int):
    """The `width` inputs the convolution reads at position t: z_{t-width+1}
    .. z_t, where `padded` is z with `width - 1` zero rows in front."""
    return jax.lax.dynamic_slice_in_dim(padded, t, width, axis=0)


def delta_update(S, k, v, alpha, beta):
    """One position of one sequence: S [heads, d_k, d_v], k [heads, d_k],
    v [heads, d_v], alpha, beta [heads] -> the new S."""
    S = alpha[:, None, None] * S
    u = beta[:, None] * (v - jnp.einsum("hkv,hk->hv", S, k))
    return S + k[:, :, None] * u[:, None, :]


def held_tail(padded, length, width: int):
    """The convolution's last `width - 1` inputs before position `length`
    (`padded` is z with `width - 1` zero rows in front)."""
    return jax.lax.dynamic_slice_in_dim(padded, length, width - 1, axis=0)


def held_state(S):
    """What is kept of S between two positions (float32: all of it)."""
    return S


def linear_attention(p: dict, x, cfg: dict, length):
    """x [s, hidden] -> ([s, hidden], the state [heads, d_k, d_v] after
    `length` positions, the convolution's last `width - 1` inputs before
    position `length`)."""
    s = x.shape[0]
    heads = cfg["linear_num_key_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    width = cfg["linear_conv_kernel_dim"]
    f32 = {k: v.astype(jnp.float32) for k, v in p.items()}
    z = x @ f32["qkv_proj.weight"]                            # [s, channels]
    padded = jnp.concatenate(
        [jnp.zeros((width - 1, z.shape[1]), jnp.float32), z], 0)
    beta = beta_of(x @ f32["b_proj.weight"], cfg)             # [s, heads]
    g = -jnp.exp(f32["A_log"]) * jax.nn.softplus(
        x @ f32["a_proj.weight"] + f32["dt_bias"])
    real = real_positions(s, length)

    def position(S, t):
        c = jax.nn.silu((f32["conv_weight"]
                         * conv_input(padded, t, width)).sum(0))
        q = unit(c[:heads * dk].reshape(heads, dk)) * dk ** -0.5
        k = unit(c[heads * dk:2 * heads * dk].reshape(heads, dk))
        v = c[2 * heads * dk:].reshape(heads, dv)
        new = held_state(delta_update(S, k, v, jnp.exp(g[t]), beta[t]))
        o = jnp.einsum("hkv,hk->hv", new, q)
        return jnp.where(real[t], new, S), o

    S, o = jax.lax.scan(position, jnp.zeros((heads, dk, dv), jnp.float32),
                        jnp.arange(s))
    o = rms_norm(o, f32["o_norm.weight"], cfg["rms_norm_eps"])
    gate = jax.nn.silu(x @ f32["g_proj.weight"])
    y = (o.reshape(s, heads * dv) * gate) @ f32["o_proj.weight"]
    return y, S, held_tail(padded, length, width)


def layer_state(state: dict, l: int) -> dict:
    """The arrays of layer `l`, by their names inside the layer."""
    prefix = f"model.layers.{l}."
    return {k[len(prefix):]: v for k, v in state.items()
            if k.startswith(prefix)}


def embed(state: dict, ids, cfg: dict):
    return state["model.embed_tokens.weight"][ids].astype(jnp.float32)


def layer(p: dict, h, l: int, cfg: dict, length=None):
    """One block. -> (h, info): `k`, `v` of a full layer, `state`, `tail` of
    a linear layer (after `length` positions; all of them by default)."""
    eps = cfg["rms_norm_eps"]
    length = h.shape[0] if length is None else length
    with jax.default_matmul_precision("highest"):
        mixer = {k[len("mixer."):]: v for k, v in p.items()
                 if k.startswith("mixer.")}
        if cfg["layer_types"][l] == "linear_attention":
            a, S, tail = linear_attention(mixer, h, cfg, length)
            info = {"state": S, "tail": tail}
        else:
            a, keys, values = attention(mixer, h, cfg)
            info = {"k": keys, "v": values}
        h = h + rms_norm(a, p["post_attention_layernorm.weight"], eps)
        f = swiglu(h, *(p[f"mlp.{n}.weight"].astype(jnp.float32)
                        for n in ("gate_proj", "up_proj", "down_proj")))
        return h + rms_norm(f, p["post_feedforward_layernorm.weight"],
                            eps), info


def head(state: dict, h, cfg: dict):
    """[n, hidden] hidden states -> [n, vocab] logits."""
    with jax.default_matmul_precision("highest"):
        return rms_norm(h, state["model.norm.weight"], cfg["rms_norm_eps"]) \
            @ state["lm_head.weight"].astype(jnp.float32)


def hidden_states(state: dict, ids, cfg: dict, length=None):
    """[s] token ids -> ([s, hidden] before the final norm, [info a layer])."""
    h, infos = embed(state, ids, cfg), []
    for l in range(cfg["num_hidden_layers"]):
        h, info = layer(layer_state(state, l), h, l, cfg, length)
        infos.append(info)
    return h, infos


def logits(state: dict, ids, cfg: dict):
    """[s] ids -> [s, vocab] float32 logits."""
    return head(state, hidden_states(state, ids, cfg)[0], cfg)
