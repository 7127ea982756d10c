"""Shared helpers for the TPU Pallas kernels (flash_attention, lm_loss,
latent_decode)."""
from __future__ import annotations

import contextlib
import threading

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

# Index-map constants must be i32: the framework enables jax_enable_x64
# (paddle's int64 default), and a weak `0` literal would trace to i64, which
# Mosaic rejects.
I0 = np.int32(0)

NEG_INF = -1e30  # finite (not -inf): keeps exp() and Mosaic happy


def interpret() -> bool:
    """Kernels run in Pallas interpret mode on CPU (tests)."""
    return jax.default_backend() == "cpu"


def vmem(shape, dtype=jnp.float32):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM(shape, dtype)


def pick_block(n: int, preferred: int = 512) -> int:
    """Largest power-of-two tile from (preferred..8) dividing n; falls back to
    n itself (callers' supported() predicates reject unaligned sizes)."""
    for b in (preferred, 512, 256, 128, 64, 32, 16, 8):
        if b <= preferred and n % b == 0 and b <= n:
            return b
    return n


# ---- running a kernel under a mesh ------------------------------------------
# GSPMD cannot partition a Mosaic kernel: jax refuses to lower one inside a
# multi-device program unless every mesh axis is manual there ("Mosaic kernels
# cannot be automatically partitioned. Please wrap the call in a shard_map").
# So whoever traces a model over a mesh names that mesh here, and a kernel
# entry point wraps its Pallas calls in a shard_map over the axes that are
# not manual yet.
_trace = threading.local()


@contextlib.contextmanager
def mesh_scope(mesh):
    """Name the mesh the program being traced is partitioned over."""
    prev = getattr(_trace, "mesh", None)
    _trace.mesh = mesh
    try:
        yield
    finally:
        _trace.mesh = prev


def single_device_program() -> bool:
    """Whether the program being traced runs on one device, as far as a
    trace can see: no mesh of several devices is scoped here or set in
    jax's own context. A kernel with no shard_map of its own asks this."""
    mesh = getattr(_trace, "mesh", None)
    return ((mesh is None or mesh.size == 1)
            and jax.sharding.get_abstract_mesh().size <= 1)


def attention_partition():
    """How a [batch, seq, heads, head_dim] kernel call splits over the scoped
    mesh: (mesh, q/k/v PartitionSpec, axes to make manual), or None when the
    plain call is right — no mesh in scope, one device, or a region that is
    already manual over every axis (the shard_map'd grad_comm/ZeRO/FSDP
    steps). Batch splits over the data axes, heads over 'mp'; any other axis
    still automatic is made manual with the operands replicated over it."""
    mesh = getattr(_trace, "mesh", None)
    if mesh is None or mesh.size == 1:
        return None
    manual = set(jax.sharding.get_abstract_mesh().manual_axes)
    auto = frozenset(a for a in mesh.axis_names if a not in manual)
    if not auto:
        return None

    def live(names):
        return tuple(a for a in names if a in auto and mesh.shape[a] > 1)

    spec = P(live(("dp", "sharding")) or None, None, live(("mp",)) or None,
             None)
    # nested in a partly manual region, shard_map takes the context's mesh
    return (None if manual else mesh), spec, auto
