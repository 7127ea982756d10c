"""Peaks of the chips the benchmark knows, and the operations and bytes the
algorithms need, computed from shapes. Kept with the benchmark so that no PR
that claims a gain can move the denominator.
"""
from __future__ import annotations

# One chip, keyed by jax's `device_kind`. Source: Google Cloud documentation,
# "TPU v5e" system architecture page: 197 TFLOP/s bf16, 16 GB HBM2e at
# 819 GB/s per chip. A kind that is not here is an error, never a default.
PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
}


def peak(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no peak on record for device_kind {device_kind!r} (known: "
            f"{sorted(PEAKS)}); add it to benchmarks/lib/peaks.py with its "
            f"source before reporting a share of it")
    return PEAKS[device_kind]


def train_flops_per_token(n_params: int, num_layers: int, hidden: int,
                          seq: int) -> int:
    """Operations the forward and backward passes REQUIRE per token:
    6*N over every parameter (2 forward, 4 backward) plus causal attention,
    6*L*h*s: QK^T and PV forward (4*h*s over the full matrix), twice that
    backward, and only the causal half of the matrix is needed. bench.py and
    observability/flops.py count the full matrix (12*L*h*s); this count is
    the smaller one, so an MFU by it reads lower for the same speed."""
    return 6 * n_params + 6 * num_layers * hidden * seq


def causal_attention_flops(batch: int, heads: int, seq: int, head_dim: int,
                           backward: bool = True) -> int:
    """Required operations of causal self-attention over `batch` sequences:
    forward 2 matmuls (QK^T, PV) of 2*s*s*d each, halved by causality;
    backward 4 matmuls (dV, dP, dQ, dK), halved likewise. The flash
    backward's recomputation of S is not required work and is not counted."""
    fwd = 2 * seq * seq * head_dim
    return batch * heads * (fwd + (2 * fwd if backward else 0))


def causal_attention_bytes(batch: int, heads: int, seq: int, head_dim: int,
                           itemsize: int = 2, backward: bool = True) -> int:
    """Least HBM traffic of the same call: forward reads q, k, v and writes
    o; backward reads q, k, v, o, do and writes dq, dk, dv (the f32 lse and
    delta rows, seq*4 bytes each, are left out: under 2%)."""
    tensor = seq * head_dim * itemsize
    return batch * heads * tensor * (4 + (8 if backward else 0))


def roofline_seconds(flops: float, nbytes: float, device_kind: str):
    """(least seconds, which bound holds) on one chip."""
    p = peak(device_kind)
    t_flops = flops / p["bf16_flops_per_s"]
    t_bytes = nbytes / p["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")
