"""Shared model-FLOPs accounting for throughput/MFU telemetry.

One home for the convention bench.py already uses
(PaLM appendix B): 6*N parameter FLOPs per token plus the full causal
attention matmul term 12*L*h*s. StepTelemetry, bench, and the offline tools
must all divide by the same number or cross-checking them is meaningless.
"""
from __future__ import annotations

# bf16 peak TFLOP/s of ONE chip, keyed by jax's `device_kind`, each with its
# source. A device that is not here has no MFU: peak_flops_per_sec raises
# rather than assume a figure for hardware nobody looked up.
PEAK_TFLOPS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 per chip
    "TPU v5 lite": 197.0,
}


def transformer_flops_per_token(n_params: int, num_layers: int = 0,
                                hidden_size: int = 0, seq_len: int = 0) -> int:
    """Training FLOPs per token: 6*N (fwd + 2x bwd over every parameter)
    plus the attention-matmul term. Counts FULL attention matmuls even when
    a causal flash kernel skips ~half the blocks — same deliberate choice as
    bench.py so MFU series stay comparable."""
    return 6 * n_params + 12 * num_layers * hidden_size * seq_len


def peak_flops_per_sec(device_kind: str) -> float:
    """Per-chip bf16 peak in FLOP/s for the MFU denominator, for the
    `device_kind` jax reports (`jax.devices()[0].device_kind`)."""
    if device_kind not in PEAK_TFLOPS:
        raise KeyError(
            f"no peak FLOP/s on record for device_kind {device_kind!r} "
            f"(known: {sorted(PEAK_TFLOPS)}); add it to "
            f"observability/flops.py:PEAK_TFLOPS with its source before "
            f"reporting MFU on it")
    return PEAK_TFLOPS[device_kind] * 1e12
