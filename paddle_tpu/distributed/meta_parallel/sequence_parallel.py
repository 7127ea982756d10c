"""Sequence/context parallelism: ring attention + Ulysses (all-to-all) attention.

The reference has NO sequence parallelism (SURVEY.md §2: "SP ... ABSENT in the
reference"); this is a first-class addition mirroring how dp/mp/pp compose via
HybridCommunicateGroup. The 'sp' mesh axis shards the sequence dimension of
activations; attention — the only op that mixes positions — is computed either by:

- **ring attention** (Liu et al., arXiv:2310.01889): each shard keeps its query
  block and rotates KV blocks around the ring with `jax.lax.ppermute` (ICI
  neighbor exchange), merging partial results with online-softmax (running max +
  logsumexp) so the full [s, s] score matrix never exists anywhere; or
- **Ulysses** (arXiv:2309.14509): `jax.lax.all_to_all` re-shards from
  sequence-split to head-split, runs dense local attention (the Pallas flash
  kernel), and re-shards back. Needs num_heads % sp == 0.

Both run inside `jax.shard_map` manual regions over ONLY the 'sp' axis
(`axis_names={'sp'}`) so dp/mp/sharding stay under GSPMD auto-sharding — the
TPU-native analogue of composing a new communicator into the 4-D topology.
"""
from __future__ import annotations

import contextlib
import functools
import math
import threading

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


NEG_INF = -1e30

_state = threading.local()


def active() -> bool:
    """True when a sequence-parallel scope is installed (engine sets it when sp>1)."""
    return getattr(_state, "ctx", None) is not None


@contextlib.contextmanager
def sequence_parallel_scope(mesh, axis: str = "sp", impl: str = "ulysses"):
    """Route scaled_dot_product_attention to ring/Ulysses attention over
    `axis`. Default matches DistributedStrategy.sep_impl ("ulysses")."""
    if impl not in ("ring", "ulysses"):
        raise ValueError(
            f"sequence-parallel impl must be 'ring' or 'ulysses', got "
            f"{impl!r}")
    prev = getattr(_state, "ctx", None)
    _state.ctx = (mesh, axis, impl)
    try:
        yield
    finally:
        _state.ctx = prev


def apply_ring_attention(q, k, v, causal: bool):
    """Entry used by ops.nn_functional when a scope is active. q,k,v: Tensors
    [b, s_global, h, d] (traced global arrays inside pjit)."""
    from ...core.dispatch import apply

    mesh, axis, impl = _state.ctx
    fn = ring_attention if impl == "ring" else ulysses_attention

    @jax.jit  # partial-manual shard_map must run under jit (inlined when already traced)
    def kernel(qa, ka, va):
        return fn(qa, ka, va, mesh=mesh, axis=axis, causal=causal)

    return apply("ring_attention", kernel, [q, k, v])


# ------------------------------------------------------------------- ring ----

def _chunk_attn(q, k, v, sm_scale, mask):
    """One KV-chunk attention returning unnormalized accum + row stats.

    q: [b, sq, h, d], k/v: [b, sk, h, d], mask: [sq, sk] bool or None.
    Returns (acc [b,h,sq,d] f32, m [b,h,sq] f32, l [b,h,sq] f32).
    """
    # matmul inputs stay in storage dtype (bf16 under amp) for MXU rate;
    # f32 accumulation + f32 softmax stats keep the numerics
    qt = jnp.swapaxes(q, 1, 2)  # [b,h,sq,d]
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    s = jnp.einsum("bhqd,bhkd->bhqk", qt, kt,
                   preferred_element_type=jnp.float32) * sm_scale
    if mask is not None:
        s = jnp.where(mask[None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1)                              # [b,h,sq]
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bhqk,bhkd->bhqd", p.astype(vt.dtype), vt,
                     preferred_element_type=jnp.float32)
    return acc, m, l


def _ring_use_flash(s_loc: int, d: int) -> bool:
    """Per-shard block compute runs the Pallas flash kernel when the shapes
    qualify (SURVEY §5.7's Pallas-ring requirement). The flag policy is the
    SHARED one (ops.nn_functional._flash_flag_allows — so a user disabling
    use_flash_attention disables ring's kernel too, on any backend), with
    the test env knob PADDLE_TPU_RING_FLASH=1 as a CPU-only extra opt-in."""
    import os

    from ...ops.nn_functional import _flash_flag_allows
    from ...ops.pallas.flash_attention import supported

    from ...core import flags as _flags

    if not supported(s_loc, s_loc, d):
        return False
    if not _flags.flag("use_flash_attention"):
        return False  # an explicit disable beats every opt-in, env included
    if (jax.default_backend() == "cpu"
            and os.environ.get("PADDLE_TPU_RING_FLASH") == "1"):
        return True
    return _flash_flag_allows()


def _block_attn_normalized(q, kc, vc, sm_scale, *, diag, use_flash):
    """One KV-block attention -> (o [b,h,sq,d] f32 normalized, lse [b,h,sq]).

    diag=True applies the within-block causal mask (ring diagonal block, where
    q and kv share global offsets). Pallas flash kernel when available; jnp
    chunk attention otherwise.
    """
    if use_flash:
        from ...ops.pallas.flash_attention import flash_attention_with_lse

        o, lse = flash_attention_with_lse(q, kc, vc, causal=diag,
                                          sm_scale=sm_scale)
        return jnp.swapaxes(o, 1, 2).astype(jnp.float32), lse
    mask = None
    if diag:
        sq = q.shape[1]
        mask = jnp.arange(sq)[:, None] >= jnp.arange(sq)[None, :]
    acc, m, l = _chunk_attn(q, kc, vc, sm_scale, mask)
    safe_l = jnp.where(l == 0.0, 1.0, l)
    return acc / safe_l[..., None], m + jnp.log(safe_l)


def _ring_shard(q, k, v, *, axis, causal, sm_scale):
    """Per-shard ring attention body (runs under shard_map, manual over `axis`).

    q,k,v: [b, s_local, h, d] — this rank's sequence shard. Partial results
    are carried normalized with their logsumexp and merged as
    o <- w1*o_acc + w2*o_t, w_i = exp(lse_i - logaddexp(lse_acc, lse_t)),
    so the Pallas flash kernel (which returns normalized output + lse) drops
    straight into the loop.
    """
    p_size = jax.lax.axis_size(axis)
    my_idx = jax.lax.axis_index(axis)
    b, s_loc, h, d = q.shape
    use_flash = _ring_use_flash(s_loc, d)

    def body(t, carry):
        o_acc, lse_acc, kc, vc = carry

        def merge(stats, diag):
            o_acc, lse_acc = stats
            o_t, lse_t = _block_attn_normalized(q, kc, vc, sm_scale,
                                                diag=diag, use_flash=use_flash)
            lse_new = jnp.logaddexp(lse_acc, lse_t)
            w1 = jnp.exp(lse_acc - lse_new)
            w2 = jnp.exp(lse_t - lse_new)
            return o_acc * w1[..., None] + o_t * w2[..., None], lse_new

        stats = (o_acc, lse_acc)
        if causal:
            kv_idx = (my_idx - t) % p_size  # whose block we currently hold
            # 3-way block dispatch: entirely-future blocks skip compute, the
            # diagonal block masks within, past blocks run unmasked
            stats = jax.lax.cond(
                kv_idx > my_idx,
                lambda s: s,
                lambda s: jax.lax.cond(
                    kv_idx == my_idx,
                    lambda s2: merge(s2, True),
                    lambda s2: merge(s2, False),
                    s),
                stats)
        else:
            stats = merge(stats, False)
        o_acc, lse_acc = stats
        # rotate kv to the next rank (neighbor exchange on the ICI ring)
        perm = [(i, (i + 1) % p_size) for i in range(p_size)]
        kc = jax.lax.ppermute(kc, axis, perm)
        vc = jax.lax.ppermute(vc, axis, perm)
        return o_acc, lse_acc, kc, vc

    o0 = jnp.zeros((b, h, s_loc, d), jnp.float32)
    lse0 = jnp.full((b, h, s_loc), NEG_INF, jnp.float32)
    o, lse, _, _ = jax.lax.fori_loop(
        0, p_size, body, (o0, lse0, k, v), unroll=True)
    return jnp.swapaxes(o.astype(q.dtype), 1, 2)        # [b,sq,h,d]


def ring_attention(q, k, v, mesh, axis: str = "sp", causal: bool = False,
                   sm_scale: float | None = None):
    """Global-view ring attention: q,k,v [b, s, h, d] with s sharded over `axis`."""
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    spec = P(None, axis, None, None)
    fn = functools.partial(_ring_shard, axis=axis, causal=causal, sm_scale=sm_scale)
    return jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, axis_names={axis},
                         check_vma=False)(q, k, v)


# ---------------------------------------------------------------- ulysses ----

def _ulysses_shard(q, k, v, *, axis, causal, sm_scale):
    """Per-shard Ulysses: seq-sharded [b, s/P, h, d] -> all_to_all ->
    head-sharded [b, s, h/P, d] -> dense local attention -> back."""
    p_size = jax.lax.axis_size(axis)

    def scatter_heads(x):
        # tiled all_to_all: heads scattered across ranks, sequence gathered
        # [b, s_loc, h, d] -> [b, s_loc * P, h / P, d]
        return jax.lax.all_to_all(x, axis, split_axis=2, concat_axis=1, tiled=True)

    def gather_heads(x, s_loc):
        # inverse: [b, s, h/P, d] -> [b, s_loc, h, d]
        return jax.lax.all_to_all(x, axis, split_axis=1, concat_axis=2, tiled=True)

    s_loc = q.shape[1]
    qg, kg, vg = scatter_heads(q), scatter_heads(k), scatter_heads(v)
    from ...ops.nn_functional import _use_flash

    if _use_flash(qg, kg):
        from ...ops.pallas.flash_attention import flash_attention

        out = flash_attention(qg, kg, vg, causal=causal, sm_scale=sm_scale)
    else:
        mask = None
        if causal:
            sq = qg.shape[1]
            mask = jnp.arange(sq)[:, None] >= jnp.arange(sq)[None, :]
        acc, m, l = _chunk_attn(qg, kg, vg, sm_scale, mask)
        out = jnp.swapaxes((acc / l[..., None]), 1, 2).astype(q.dtype)
    return gather_heads(out, s_loc)


def ulysses_attention(q, k, v, mesh, axis: str = "sp", causal: bool = False,
                      sm_scale: float | None = None):
    """DeepSpeed-Ulysses-style attention; requires num_heads % axis_size == 0."""
    sp_size = mesh.shape[axis]
    n_heads = q.shape[2]
    if n_heads % sp_size:
        # validate here, where the head count is known: the all_to_all's own
        # failure is an opaque shape error deep inside shard_map tracing
        # that never names the knob (matters since ulysses became the
        # sep_impl default)
        raise ValueError(
            f"ulysses sequence parallelism scatters heads over the '{axis}' "
            f"axis and needs num_heads ({n_heads}) divisible by its size "
            f"({sp_size}); use strategy.sep_impl = 'ring' (no divisibility "
            f"requirement) or change the head count / sep_degree")
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    spec = P(None, axis, None, None)
    fn = functools.partial(_ulysses_shard, axis=axis, causal=causal, sm_scale=sm_scale)
    return jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, axis_names={axis},
                         check_vma=False)(q, k, v)
