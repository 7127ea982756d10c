"""The bytes one decode step of a `deepseek_v2` configuration must read from
HBM, and the operations it needs, from shapes: every weight matrix a step
multiplies by once (`W_kvb` once: the absorbed form uses its two halves a
head, `W_uk` on the way in and `W_uv` on the way out), the routed experts
HELD HERE that received a row, the slice of the head held here, the router
in float32, and the latent rows the slots' attention reads: `kv_lora_rank +
qk_rope_head_dim` values a held position a layer, ONE row for all heads.
Kept with the benchmark so that no PR that claims a gain can move the
numerator of `serve.decode_bytes_roofline.deepseek`.

The configuration is one chip's share (benchmarks/configs/deepseek-v2.json):
`n_routed_experts` counts the experts held, `published.n_routed_experts` is
the router's width, `vocab_size` the slice of the vocabulary.

Left out, all under 1%: the norms' weights, the embedding rows of the step's
tokens, the rows written, activations (64 rows). A step cannot read less:
each of these arrays is used by the step and none is used twice.
"""
from __future__ import annotations

from typing import Sequence


def attention_parameters(config: dict) -> int:
    """The matrices of one latent-attention layer."""
    h = int(config["hidden_size"])
    heads = int(config["num_attention_heads"])
    dn, dr = int(config["qk_nope_head_dim"]), int(config["qk_rope_head_dim"])
    dv, r = int(config["v_head_dim"]), int(config["kv_lora_rank"])
    rq = config.get("q_lora_rank")
    q = (h * int(rq) + int(rq) * heads * (dn + dr)) if rq \
        else h * heads * (dn + dr)
    return q + h * (r + dr) + r * heads * (dn + dv) + heads * dv * h


def expert_parameters(config: dict) -> int:
    return 3 * int(config["hidden_size"]) * int(config["moe_intermediate_size"])


def router_width(config: dict) -> int:
    return int(config.get("published", {}).get(
        "n_routed_experts", config["n_routed_experts"]))


def layer_counts(config: dict):
    """(dense layers, expert layers)."""
    layers = int(config["num_hidden_layers"])
    dense = min(layers, int(config["first_k_dense_replace"]))
    return dense, layers - dense


def latent_row_bytes(config: dict, itemsize: int = 2) -> int:
    """What a slot keeps a position a layer: 1,152 B as published."""
    return (int(config["kv_lora_rank"]) + int(config["qk_rope_head_dim"])) \
        * itemsize


def decode_step_bytes(config: dict, contexts: Sequence[int],
                      experts_touched_held: float, weight_itemsize: int = 2,
                      cache_itemsize: int = 2) -> dict:
    """`contexts`: positions held by each live slot; `experts_touched_held`:
    mean over the expert layers of the experts held here that received a
    row. Returns the parts and their `total`, in bytes."""
    h = int(config["hidden_size"])
    dense, moe_layers = layer_counts(config)
    layers = dense + moe_layers
    parts = {
        "experts": moe_layers * float(experts_touched_held)
        * expert_parameters(config) * weight_itemsize,
        "attention_weights": layers * attention_parameters(config)
        * weight_itemsize,
        "shared_experts": moe_layers * int(config["n_shared_experts"])
        * expert_parameters(config) * weight_itemsize,
        "dense_mlp": dense * 3 * h * int(config["intermediate_size"])
        * weight_itemsize,
        "head": h * int(config["vocab_size"]) * weight_itemsize,
        "router": float(moe_layers * h * router_width(config) * 4),  # float32
        "latent_rows": layers * sum(int(c) for c in contexts)
        * latent_row_bytes(config, cache_itemsize),
    }
    parts["total"] = sum(parts.values())
    return parts


def decode_step_flops(config: dict, contexts: Sequence[int]) -> dict:
    """The operations of the same step: 2 x every matrix a row passes
    through x rows (a row meets `num_experts_per_tok` x held / published
    routed experts here on average), and the absorbed core, 2 x heads x
    ((kv_lora_rank + qk_rope_head_dim) for the score + kv_lora_rank for the
    weighted sum) a held position a layer."""
    h = int(config["hidden_size"])
    dense, moe_layers = layer_counts(config)
    layers = dense + moe_layers
    rows = len(contexts)
    routed = int(config["num_experts_per_tok"]) \
        * int(config["n_routed_experts"]) / router_width(config)
    matrices = (layers * attention_parameters(config)
                + dense * 3 * h * int(config["intermediate_size"])
                + moe_layers * (int(config["n_shared_experts"]) + routed)
                * expert_parameters(config)
                + moe_layers * h * router_width(config)
                + h * int(config["vocab_size"]))
    r, dr = int(config["kv_lora_rank"]), int(config["qk_rope_head_dim"])
    parts = {
        "matrices": 2 * matrices * rows,
        "core": layers * sum(int(c) for c in contexts) * 2
        * int(config["num_attention_heads"]) * ((r + dr) + r),
    }
    parts["total"] = sum(parts.values())
    return parts
