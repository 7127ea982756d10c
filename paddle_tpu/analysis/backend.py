"""Backend capability probes shared by contracts, gates, and the CLI.

The one that matters today: does this XLA pipeline run the
AllReduceCombiner? Collective-SHAPE contracts (a handful of fused
all-reduces for N params) only hold where it does — TPU/GPU. This
container's XLA CPU keeps one all-reduce per operand and resharding
emits device-order collective-permutes, so every contract marked
``requires_combining`` is *skipped* (not weakened) on it. This predicate
used to live as a private lru-cached helper inside
tests/test_hlo_perf_gates.py; the analyzer and the 4 probe-skipped gates
now share this single copy, so "which backends can gate collectives" has
exactly one answer.
"""
from __future__ import annotations

import functools
from typing import Optional

from .program import Program


@functools.lru_cache(maxsize=1)
def collective_combining_reason() -> Optional[str]:
    """None when the backend combines collectives (contracts must run),
    else the human-readable skip reason.

    Probe: compile a tiny TWO-parameter psum program and count all-reduce
    ops — a combining backend (TPU, GPU) folds them into one variadic
    all-reduce; the reduced CPU pipeline keeps one per operand. Cached:
    one ~100ms compile per process, at first use rather than import.
    """
    import jax
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    devs = jax.devices()
    if len(devs) < 2:
        return "single-device backend: no collectives to gate"
    mesh = Mesh(np.array(devs), ("dp",))

    def two_psums(a, b):
        return jax.lax.psum(a, "dp"), jax.lax.psum(b, "dp")

    fm = jax.shard_map(two_psums, mesh=mesh,
                       in_specs=(P("dp"), P("dp")), out_specs=(P(), P()))
    z = np.zeros((len(devs), 4), np.float32)
    txt = jax.jit(fm).lower(z, z).compile().as_text()
    n = Program("combining-probe", hlo_text=txt).count_ops("all-reduce")
    if n <= 1:
        return None
    return (f"XLA {jax.default_backend()} backend does not run the "
            f"AllReduceCombiner (probe: 2-param psum compiled to {n} "
            f"all-reduce ops, a combining backend emits 1 fused) — "
            f"collective-shape gates need a TPU/GPU pipeline")


def backend_combines_collectives() -> bool:
    return collective_combining_reason() is None


@functools.lru_cache(maxsize=1)
def native_bf16_collective_reason() -> Optional[str]:
    """None when the backend keeps bf16 collective payloads in bf16 on the
    wire (wire-dtype contracts must run), else the skip reason.

    Probe: compile a bf16 psum and look at the all-reduce's payload dtype.
    CPU's float-normalization pass legalizes bf16 compute to f32, turning
    ``convert_f32(psum(convert_bf16(x)))`` into an f32 all-reduce — so on
    such backends a declared-bf16 grad-comm region ALWAYS shows f32
    reduction payloads and the dtype-upcast pass must skip, not fail.
    """
    import jax
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    devs = jax.devices()
    if len(devs) < 2:
        return "single-device backend: no collectives to gate"
    mesh = Mesh(np.array(devs), ("dp",))

    def halfwire(a):
        return jax.lax.psum(a.astype(jax.numpy.bfloat16),
                            "dp").astype(jax.numpy.float32)

    fm = jax.shard_map(halfwire, mesh=mesh, in_specs=(P("dp"),),
                       out_specs=P())
    z = np.zeros((len(devs), 4), np.float32)
    txt = jax.jit(fm).lower(z).compile().as_text()
    # read the parsed RESULT type: the metadata tail of the line can spell
    # any dtype in op_name
    for ins in Program("bf16-probe", hlo_text=txt).op_defs("all-reduce"):
        if "bf16[" in ins.result:
            return None
    return (f"XLA {jax.default_backend()} backend upcasts bf16 collective "
            f"payloads to f32 (float normalization legalizes bf16 compute) "
            f"— wire-dtype contracts need a TPU/GPU pipeline")


def backend_keeps_bf16_on_wire() -> bool:
    return native_bf16_collective_reason() is None


def aot_serving_reason(device_count: Optional[int] = None,
                       platform: Optional[str] = None) -> Optional[str]:
    """None when AOT serving precompilation is safe on this backend, else
    the human-readable skip reason.

    Cache-SERVED multi-device executables are nondeterministic on this
    jax/XLA CPU (the collective-result leak core.compile_cache documents),
    and the AOT warm-start bundle exists precisely to serve executables
    from the persistent store — so a multi-device CPU serving mesh must
    fall back to lazy compilation rather than risk replica divergence.
    Single-device (any platform) and TPU/GPU meshes precompile freely.

    ``device_count``/``platform`` are injectable for tests; the live values
    come from jax at call time (NOT lru-cached: serving meshes reform)."""
    if device_count is None or platform is None:
        import jax

        devs = jax.devices()
        if device_count is None:
            device_count = len(devs)
        if platform is None:
            platform = jax.default_backend()
    if device_count <= 1:
        return None
    if platform == "cpu":
        return (f"multi-device XLA cpu mesh ({device_count} devices): "
                f"cache-served executables are nondeterministic on this "
                f"jax — AOT bundle serving needs a single-device or "
                f"TPU/GPU mesh")
    return None


def backend_supports_aot_serving(device_count: Optional[int] = None,
                                 platform: Optional[str] = None) -> bool:
    return aot_serving_reason(device_count, platform) is None
