"""The bytes one decode step of an `afmoe` configuration must read from HBM,
from shapes: every weight matrix a step multiplies by once, the routed
experts that received a row, and the cache rows the slots' attention reads.
Kept with the benchmark so that no PR that claims a gain can move the
numerator of `serve.decode_bytes_roofline.*`.

Left out, all under 1%: the norms' weights, the embedding rows of the step's
tokens, the rows written, activations (16 rows). A step cannot read less:
each of these arrays is used by the step and none is used twice.
"""
from __future__ import annotations

from typing import Sequence


def decode_step_bytes(config: dict, contexts: Sequence[int],
                      experts_touched: float, weight_itemsize: int = 2,
                      cache_itemsize: int = 2) -> dict:
    """`contexts`: positions held by each live slot; `experts_touched`: mean
    over the expert layers of the experts that received a row. Returns the
    parts and their `total`, in bytes."""
    h = int(config["hidden_size"])
    heads = int(config["num_attention_heads"]) * int(config["head_dim"])
    kv = int(config["num_key_value_heads"]) * int(config["head_dim"])
    layers = list(config["layer_types"])
    dense = int(config["num_dense_layers"])
    moe_layers = len(layers) - dense
    width = int(config["moe_intermediate_size"])
    window = int(config["sliding_window"])

    attn = len(layers) * (3 * h * heads + 2 * h * kv)    # q, gate, o; k, v
    dense_mlp = dense * 3 * h * int(config["intermediate_size"])
    shared = moe_layers * int(config["num_shared_experts"]) * 3 * h * width
    experts = moe_layers * float(experts_touched) * 3 * h * width
    router = moe_layers * h * int(config["num_experts"]) * 4     # float32
    head = h * int(config["vocab_size"])
    rows = sum(min(int(c), window) if t == "sliding_attention" else int(c)
               for t in layers for c in contexts)
    parts = {
        "experts": experts * weight_itemsize,
        "head": head * weight_itemsize,
        "attention_weights": attn * weight_itemsize,
        "shared_experts": shared * weight_itemsize,
        "dense_mlp": dense_mlp * weight_itemsize,
        "router": float(router),
        "cache_rows": rows * 2 * kv * cache_itemsize,            # k and v
    }
    parts["total"] = sum(parts.values())
    return parts
