"""The bytes one decode step of an `olmo_hybrid` configuration must move
through HBM, from shapes: every weight matrix a step multiplies by once, the
head, the rows the full-attention layers' queries read, and each live slot's
recurrent state, which a step reads AND writes whole. Kept with the
benchmark so that no PR that claims a gain can move the numerator of
`serve.decode_bytes_roofline.olmo`.

Left out, all under 0.1%: the norms' weights, `A_log` and `dt_bias`, the
embedding rows of the step's tokens, the rows written, activations (16 rows).
A step cannot move less: each of these arrays is used by the step, none is
used twice, and the delta rule rewrites every element of the state.
"""
from __future__ import annotations

from typing import Sequence

STATE_ITEMSIZE = 4        # the state matrix is float32


def linear_mixer_parameters(config: dict) -> int:
    """The matrices of one `linear_attention` mixer: the q, k, v projection,
    the convolution, the two gate projections, the output gate, the output
    projection."""
    h = int(config["hidden_size"])
    heads = int(config["linear_num_key_heads"])
    keys = heads * int(config["linear_key_head_dim"])
    values = heads * int(config["linear_value_head_dim"])
    channels = 2 * keys + values
    return (h * channels + int(config["linear_conv_kernel_dim"]) * channels
            + 2 * h * heads + h * values + values * h)


def full_mixer_parameters(config: dict) -> int:
    h = int(config["hidden_size"])
    kv = h // int(config["num_attention_heads"]) \
        * int(config["num_key_value_heads"])
    return 2 * h * h + 2 * h * kv                      # q, o; k, v


def mlp_parameters(config: dict) -> int:
    return 3 * int(config["hidden_size"]) * int(config["intermediate_size"])


def state_bytes(config: dict, tail_itemsize: int = 2) -> dict:
    """What one slot keeps for one `linear_attention` layer."""
    heads = int(config["linear_num_key_heads"])
    dk = int(config["linear_key_head_dim"])
    dv = int(config["linear_value_head_dim"])
    channels = heads * (2 * dk + dv)
    matrix = heads * dk * dv * STATE_ITEMSIZE
    tail = (int(config["linear_conv_kernel_dim"]) - 1) * channels \
        * tail_itemsize
    return {"matrix": matrix, "tail": tail, "total": matrix + tail}


def decode_step_bytes(config: dict, contexts: Sequence[int],
                      weight_itemsize: int = 2,
                      cache_itemsize: int = 2) -> dict:
    """`contexts`: positions held by each live slot. Returns the parts and
    their `total`, in bytes."""
    layers = list(config["layer_types"])
    linear = sum(t == "linear_attention" for t in layers)
    full = len(layers) - linear
    h = int(config["hidden_size"])
    kv = h // int(config["num_attention_heads"]) \
        * int(config["num_key_value_heads"])
    state = state_bytes(config, cache_itemsize)["total"]
    parts = {
        "linear_mixers": linear * linear_mixer_parameters(config)
        * weight_itemsize,
        "full_mixers": full * full_mixer_parameters(config) * weight_itemsize,
        "mlps": len(layers) * mlp_parameters(config) * weight_itemsize,
        "head": h * int(config["vocab_size"]) * weight_itemsize,
        "cache_rows": full * sum(int(c) for c in contexts) * 2 * kv
        * cache_itemsize,                                   # k and v
        "state_read": linear * len(contexts) * state,
        "state_written": linear * len(contexts) * state,
    }
    parts["total"] = sum(parts.values())
    return parts
