"""GPT-2's forward pass and loss in plain jax.numpy: float32, every matmul at
the highest precision, no kernel, no cache, no batching trick. It follows the
published architecture (Radford et al. 2019; huggingface `GPT2LMHeadModel`):
learned token and position embeddings, pre-LayerNorm blocks (eps 1e-5) of
causal multi-head attention and a 4x tanh-GELU MLP, a final LayerNorm, and
the token embedding reused as the output head.

It shares no code with paddle_tpu/models/gpt.py. It only reads that model's
`state_dict` by name, so it knows the layout the program stores:

- Linear weights are [in, out] (y = x @ W + b);
- `qkv_proj` packs its 3*h outputs as (3, heads, head_dim), q first.

The served model pads the vocabulary (50257 -> 50304 rows); the reference
computes over whatever rows the state holds, as the program does.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _ln(x, w, b, eps=1e-5):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def hidden_states(state: dict, ids, num_layers: int, num_heads: int):
    """[b, s] token ids -> [b, s, h] final hidden states (after ln_f)."""
    f32 = {k: v.astype(jnp.float32) for k, v in state.items()}
    b, s = ids.shape
    x = f32["gpt.wte.weight"][ids] + f32["gpt.wpe.weight"][jnp.arange(s)]
    h = x.shape[-1]
    hd = h // num_heads
    causal = jnp.tril(jnp.ones((s, s), bool))
    for i in range(num_layers):
        p = f"gpt.blocks.{i}."
        y = _ln(x, f32[p + "ln1.weight"], f32[p + "ln1.bias"])
        qkv = y @ f32[p + "attn.qkv_proj.weight"] + f32[p + "attn.qkv_proj.bias"]
        q, k, v = jnp.moveaxis(qkv.reshape(b, s, 3, num_heads, hd), 2, 0)
        att = jnp.einsum("bqnd,bknd->bnqk", q, k) / math.sqrt(hd)
        att = jax.nn.softmax(jnp.where(causal, att, -jnp.inf), axis=-1)
        y = jnp.einsum("bnqk,bknd->bqnd", att, v).reshape(b, s, h)
        x = x + y @ f32[p + "attn.out_proj.weight"] + f32[p + "attn.out_proj.bias"]
        y = _ln(x, f32[p + "ln2.weight"], f32[p + "ln2.bias"])
        y = _gelu_tanh(y @ f32[p + "mlp.fc1.weight"] + f32[p + "mlp.fc1.bias"])
        x = x + y @ f32[p + "mlp.fc2.weight"] + f32[p + "mlp.fc2.bias"]
    return _ln(x, f32["gpt.ln_f.weight"], f32["gpt.ln_f.bias"])


def loss_per_sequence(state: dict, ids, labels, num_layers: int,
                      num_heads: int):
    """Mean next-token cross-entropy of each sequence, [b] float32; `labels`
    are given already shifted, as the train engine takes them. One sequence
    at a time (lax.map), so the [s, vocab] logits of one sequence are the
    largest thing alive."""
    def one(pair):
        row, lab = pair
        hid = hidden_states(state, row[None], num_layers, num_heads)[0]
        logits = hid @ state["gpt.wte.weight"].astype(jnp.float32).T
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, lab[:, None], axis=-1).mean()

    with jax.default_matmul_precision("highest"):
        return jax.lax.map(one, (ids, labels))


def logits_at(state: dict, ids, positions, num_layers: int, num_heads: int):
    """[b, s] ids, [b, n] positions -> [b, n, vocab] float32 logits at those
    positions. Right-padding a row is inert for the positions before the pad
    (causal), so rows of different true lengths share one call."""
    with jax.default_matmul_precision("highest"):
        hid = hidden_states(state, ids, num_layers, num_heads)
        picked = jnp.take_along_axis(hid, positions[:, :, None], axis=1)
        return picked @ state["gpt.wte.weight"].astype(jnp.float32).T
