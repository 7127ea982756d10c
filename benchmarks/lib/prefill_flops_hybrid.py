"""The operations one prefill of an `olmo_hybrid` configuration needs for a
prompt of `length` REAL tokens, from shapes. Kept with the benchmark so that
no PR that claims a gain can move the numerator of
`serve.prefill_flops_roofline.olmo`. The rung's right-pad is the program's
waste and is not counted; nor is what the chunked form of the delta rule
computes beyond the recurrence (the products inside a chunk).

- 2 x every matrix parameter of the layers x tokens (mixers and MLPs; the
  embedding is a gather);
- causal attention in the full layers: QK^T and PV, 2 x length^2 x head_dim
  a head each, halved by causality;
- the delta rule, a position a head: S'^T k, k u^T and S^T q, 2 x d_k x d_v
  each;
- the head for ONE position (the first token is sampled from the last).
"""
from __future__ import annotations

from benchmarks.lib.decode_bytes_hybrid import (full_mixer_parameters,
                                                linear_mixer_parameters,
                                                mlp_parameters)


def prefill_flops(config: dict, length: int) -> dict:
    n = int(length)
    layers = list(config["layer_types"])
    linear = sum(t == "linear_attention" for t in layers)
    full = len(layers) - linear
    h = int(config["hidden_size"])
    heads = int(config["linear_num_key_heads"])
    dk = int(config["linear_key_head_dim"])
    dv = int(config["linear_value_head_dim"])
    matrices = (linear * linear_mixer_parameters(config)
                + full * full_mixer_parameters(config)
                + len(layers) * mlp_parameters(config))
    parts = {
        "matrices": 2 * matrices * n,
        # 2 matmuls x 2 n^2 d a head, halved: 2 n^2 x (heads x head_dim = h)
        "attention": full * 2 * n * n * h,
        "delta_rule": linear * n * heads * 3 * 2 * dk * dv,
        "head": 2 * h * int(config["vocab_size"]),
    }
    parts["total"] = sum(parts.values())
    return parts
