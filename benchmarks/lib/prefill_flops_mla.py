"""The operations one prefill of a `deepseek_v2` configuration needs for a
prompt of `length` REAL tokens, from shapes, in the expanded form (keys and
values a head from the latent). Kept with the benchmark so that no PR that
claims a gain can move the numerator of
`serve.prefill_flops_roofline.deepseek`. The rung's right-pad is the
program's waste and is not counted, nor are rows of experts held elsewhere,
which nobody here computes.

- 2 x every matrix parameter a token passes through x tokens: the attention
  matrices (`W_kvb` expands every latent into keys and values a head), the
  dense MLP, the shared experts, the router, and `num_experts_per_tok` x
  held / published routed experts a token a layer (1.5 of 6 where 40 of 160
  are held); the embedding is a gather;
- causal attention: QK^T over `qk_nope_head_dim + qk_rope_head_dim` and PV
  over `v_head_dim`, 2 x length^2 x width a head each, halved by causality;
- the head for ONE position (the first token is sampled from the last).
"""
from __future__ import annotations

from benchmarks.lib.decode_bytes_mla import (attention_parameters,
                                             expert_parameters, layer_counts,
                                             router_width)


def prefill_flops(config: dict, length: int) -> dict:
    n = int(length)
    h = int(config["hidden_size"])
    dense, moe_layers = layer_counts(config)
    layers = dense + moe_layers
    routed = int(config["num_experts_per_tok"]) \
        * int(config["n_routed_experts"]) / router_width(config)
    matrices = (layers * attention_parameters(config)
                + dense * 3 * h * int(config["intermediate_size"])
                + moe_layers * (int(config["n_shared_experts"]) + routed)
                * expert_parameters(config)
                + moe_layers * h * router_width(config))
    width = (int(config["qk_nope_head_dim"]) + int(config["qk_rope_head_dim"])
             + int(config["v_head_dim"]))
    parts = {
        "matrices": 2 * matrices * n,
        # 2 n^2 x width a head, halved
        "attention": layers * int(config["num_attention_heads"]) * n * n
        * width,
        "head": 2 * h * int(config["vocab_size"]),
    }
    parts["total"] = sum(parts.values())
    return parts
