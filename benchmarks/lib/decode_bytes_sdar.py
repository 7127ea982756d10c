"""The bytes one FORWARD of an `sdar_moe` configuration's block-step decode
program must read from HBM, from shapes: every attention matrix, the router
in float32 and the output head once a forward, the routed experts held here
that received a row (`moe_touched_held`: with 64 slots x 4 positions x 8
choices on 128 experts, all of them), and the rows the slots' attention
reads: keys and values of `num_key_value_heads` heads of `head_dim`, 2,048 B
a held position a layer as published, the block's own positions included.
Kept with the benchmark so that no PR that claims a gain can move the
numerator of `serve.decode_bytes_roofline.sdar`.

The unit is a forward, not a token: a block of B tokens takes several
forwards (B + 1 under the static schedule at as many steps as positions), so
a change that needs fewer forwards a block shows in
`diffusion.forwards_per_block.sdar`, `diffusion.tokens_per_forward.sdar` and
`serve_tokens_per_s`, and cannot read as a share over 100% here.

Left out, all under 1%: the norms' weights, the embedding rows of the
block's tokens (256 x 4 KB), the rows written, activations (256 rows). A
forward cannot read less: each of these arrays is used by the forward and
none is used twice. Its operations (about 333 GFLOP at 256 rows, 1.7 ms at
the bf16 peak) lie far under the bytes' bound (about 11 ms), so the bytes
alone are the bound.
"""
from __future__ import annotations

from typing import Sequence


def attention_parameters(config: dict) -> int:
    """The four matrices of one attention layer."""
    h = int(config["hidden_size"])
    d = int(config["head_dim"])
    return h * d * (2 * int(config["num_attention_heads"])
                    + 2 * int(config["num_key_value_heads"]))


def expert_parameters(config: dict) -> int:
    return 3 * int(config["hidden_size"]) * int(config["moe_intermediate_size"])


def row_bytes(config: dict, itemsize: int = 2) -> int:
    """What a slot keeps a position a layer: a key and a value a key head."""
    return 2 * int(config["num_key_value_heads"]) * int(config["head_dim"]) \
        * itemsize


def forward_bytes(config: dict, contexts: Sequence[int],
                  experts_touched_held: float, weight_itemsize: int = 2,
                  cache_itemsize: int = 2) -> dict:
    """`contexts`: positions each live slot's forward reads (those it holds
    and its block's own); `experts_touched_held`: mean over the layers of
    the experts held here that received a row. Returns the parts and their
    `total`, in bytes."""
    h, layers = int(config["hidden_size"]), int(config["num_hidden_layers"])
    parts = {
        "experts": layers * float(experts_touched_held)
        * expert_parameters(config) * weight_itemsize,
        "attention_weights": layers * attention_parameters(config)
        * weight_itemsize,
        "head": h * int(config["vocab_size"]) * weight_itemsize,
        "router": float(layers * h * int(config["num_experts"]) * 4),
        "rows": layers * sum(int(c) for c in contexts)
        * row_bytes(config, cache_itemsize),
    }
    parts["total"] = sum(parts.values())
    return parts
