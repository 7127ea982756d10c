"""Collective communication API — the `xccl` backend.

Reference: python/paddle/distributed/collective.py (all_reduce/all_gather/... over
ProcessGroupNCCL, #20/#27 in SURVEY.md §2) and the static-graph c_* op family (#22).

TPU-native semantics: a communicator is a named mesh axis; collectives lower to
`jax.lax.{psum, all_gather, psum_scatter, ppermute, all_to_all}` inside `shard_map`.
Two call modes, mirroring the reference's eager-vs-graph split:

1. **Eager on sharded data**: the tensor is a global array sharded over the group axis
   ("each shard = one rank's tensor"); the collective runs one compiled shard_map program.
2. **Traced** (inside a pjit/shard_map program built by the engine): the same functions
   detect they are under a mesh trace and emit the lax collective directly.

Single-process single-device groups (world_size 1) are identity — matching the
reference's fast path when a group has one rank.
"""
from __future__ import annotations

import functools as _functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.tensor import Tensor
from .mesh import CommGroup, fleet_default_mesh, get_hybrid_communicate_group

# Reference ReduceOp enum (distributed/collective/Types.h)
class ReduceOp:
    SUM = 0
    MAX = 1
    MIN = 2
    PROD = 3
    AVG = 4


_group_counter = [0]
_group_registry = {}


def new_group(ranks=None, backend=None, timeout=None):
    """Reference collective.py:325 — on TPU a subgroup over explicit ranks maps to a
    sub-axis when the ranks align with one; arbitrary subsets keep the rank list and
    use gather-style emulation (sufficient for the CPU-mesh test harness)."""
    _group_counter[0] += 1
    mesh = fleet_default_mesh()
    if ranks is None:
        ranks = list(range(int(np.prod(list(mesh.shape.values())))))
    g = CommGroup(None, list(ranks), mesh, id=_group_counter[0])
    _group_registry[g.id] = g
    return g


def get_group(gid=0):
    if gid == 0 and gid not in _group_registry:
        hcg = get_hybrid_communicate_group()
        if hcg is not None:
            return hcg.get_check_parallel_group()
    return _group_registry.get(gid)


def _in_trace(x) -> bool:
    return isinstance(x, jax.core.Tracer)


def _axis_in_scope(axis: str) -> bool:
    """True when `axis` is a bound axis name in the current trace (inside shard_map)."""
    try:
        jax.lax.axis_index(axis)
        return True
    except Exception:
        return False


def spec_has_axis(spec, axis_name) -> bool:
    """Axis membership in a PartitionSpec (flattening tuple entries)."""
    if spec is None:
        return False
    flat = []
    for e in spec:
        if e is None:
            continue
        if isinstance(e, tuple):
            flat.extend(e)
        else:
            flat.append(e)
    return axis_name in flat


def _sharded_over(data, axis_name):
    """Check if a global array is sharded over the given mesh axis."""
    sharding = getattr(data, "sharding", None)
    if sharding is None or not hasattr(sharding, "spec"):
        return False
    return spec_has_axis(sharding.spec, axis_name)


def _eager_axis_collective(x, axis, fn_traced):
    """Run a collective over a mesh axis on an axis-sharded global array via shard_map."""
    from jax.sharding import PartitionSpec as P

    mesh = fleet_default_mesh()
    spec = x.sharding.spec if hasattr(x.sharding, "spec") else P()
    # check_vma=False: ops like broadcast (all_gather + index) produce values
    # that ARE replicated but can't be statically inferred as such
    f = jax.shard_map(fn_traced, mesh=mesh, in_specs=(spec,), out_specs=spec,
                      check_vma=False)
    return f(x)


def _resolve(tensor, group, op_name):
    """Common preamble: unwrap, decide identity/traced/eager-sharded path."""
    x = tensor._data if isinstance(tensor, Tensor) else tensor
    axis = getattr(group, "axis", None) if group is not None else None
    if axis is None:
        hcg = get_hybrid_communicate_group()
        if hcg is None or hcg.nranks == 1:
            return x, None, "identity"
        raise ValueError(
            f"{op_name}: pass a CommGroup bound to a mesh axis (e.g. "
            f"hcg.get_model_parallel_group()) — arbitrary-rank groups only support "
            f"point-to-point emulation")
    hcg = get_hybrid_communicate_group()
    if hcg is not None and hcg.degrees.get(axis, 1) == 1:
        return x, axis, "identity"
    if _in_trace(x):
        return x, axis, "traced"
    return x, axis, "eager"


def all_reduce(tensor, op=ReduceOp.SUM, group=None, sync_op=True):
    x, axis, mode = _resolve(tensor, group, "all_reduce")
    if mode == "identity":
        return tensor
    def _pprod(v, a):
        # no pprod primitive in lax: gather then multiply (rare op; fine off hot path)
        return jnp.prod(jax.lax.all_gather(v, a, axis=0), axis=0)

    red = {ReduceOp.SUM: jax.lax.psum, ReduceOp.MAX: jax.lax.pmax,
           ReduceOp.MIN: jax.lax.pmin, ReduceOp.PROD: _pprod,
           ReduceOp.AVG: lambda v, a: jax.lax.pmean(v, a)}[op]
    if mode == "traced":
        out = red(x, axis)
    else:
        out = _eager_axis_collective(x, axis, lambda v: red(v, axis))
    if isinstance(tensor, Tensor):
        tensor._data = out
        return tensor
    return out


def all_gather(tensor_list, tensor, group=None, sync_op=True, axis=0):
    x, ax, mode = _resolve(tensor, group, "all_gather")
    if mode == "identity":
        if tensor_list is not None:
            tensor_list.append(tensor)
            return tensor_list
        return tensor
    if mode == "traced":
        out = jax.lax.all_gather(x, ax, axis=0, tiled=False)
    else:
        out = _eager_axis_collective(x, ax, lambda v: jax.lax.all_gather(v, ax, axis=0))
    if tensor_list is not None:
        n = out.shape[0] if mode == "traced" else get_hybrid_communicate_group().degrees[ax]
        for i in range(n):
            tensor_list.append(Tensor(out[i]))
        return tensor_list
    return Tensor(out) if isinstance(tensor, Tensor) else out


def reduce_scatter(tensor, tensor_or_tensor_list, op=ReduceOp.SUM, group=None, sync_op=True):
    """Eager contract (rank-major): input global [n, n*k, ...] sharded over the axis —
    row i is rank i's tensor; output global [n, k, ...] — row i is rank i's reduced
    shard. Traced: plain lax.psum_scatter on the local value."""
    src = tensor_or_tensor_list
    if isinstance(src, (list, tuple)):
        from ..ops.manipulation import concat

        src = concat(list(src), axis=0)
    x, ax, mode = _resolve(src, group, "reduce_scatter")
    if mode == "identity":
        out = x
    elif mode == "traced":
        out = jax.lax.psum_scatter(x, ax, scatter_dimension=0, tiled=True)
    else:
        def rs(v):  # v local [1, n*k, ...]
            red = jax.lax.psum_scatter(v[0], ax, scatter_dimension=0, tiled=True)
            return red[None]

        out = _eager_axis_collective(x, ax, rs)
    if isinstance(tensor, Tensor):
        tensor._data = out
        return tensor
    return Tensor(out)


def broadcast(tensor, src=0, group=None, sync_op=True):
    x, ax, mode = _resolve(tensor, group, "broadcast")
    if mode == "identity":
        return tensor
    src_local = group.get_group_rank(src) if group is not None and src in group.ranks else src

    def bcast(v):
        return jax.lax.all_gather(v, ax, axis=0)[src_local]

    if mode == "traced":
        out = bcast(x)
    else:
        out = _eager_axis_collective(x, ax, bcast)
    if isinstance(tensor, Tensor):
        tensor._data = out
        return tensor
    return out


def reduce(tensor, dst=0, op=ReduceOp.SUM, group=None, sync_op=True):
    # on a mesh axis, reduce == all_reduce (every shard gets the result; the dst
    # distinction is meaningless under SPMD — reference ranks other than dst simply
    # ignore their copy)
    return all_reduce(tensor, op, group, sync_op)


def scatter(tensor, tensor_list=None, src=0, group=None, sync_op=True):
    x, ax, mode = _resolve(tensor, group, "scatter")
    if mode == "identity":
        if tensor_list:
            tensor._data = tensor_list[0]._data
        return tensor
    if tensor_list is not None:
        stacked = jnp.stack([t._data if isinstance(t, Tensor) else t for t in tensor_list])

        def sc(v):
            return stacked[jax.lax.axis_index(ax)]

        if mode == "traced":
            out = sc(x)
        else:
            out = _eager_axis_collective(x, ax, sc)
        tensor._data = out
    return tensor


def all_to_all(out_tensor_list, in_tensor_list, group=None, sync_op=True):
    """MoE dispatch primitive (reference global_scatter/global_gather use this)."""
    from ..ops.manipulation import concat

    src = in_tensor_list
    if isinstance(src, (list, tuple)):
        src = concat(list(src), axis=0)
    x, ax, mode = _resolve(src, group, "all_to_all")
    if mode == "identity":
        if out_tensor_list is not None and isinstance(in_tensor_list, (list, tuple)):
            out_tensor_list.extend(in_tensor_list)
        return out_tensor_list
    n = get_hybrid_communicate_group().degrees[ax]

    def a2a_local(v):  # v: one rank's tensor [n*chunk, ...]
        chunk = v.shape[0] // n
        vr = v.reshape((n, chunk) + v.shape[1:])
        return jax.lax.all_to_all(vr, ax, split_axis=0, concat_axis=0, tiled=False).reshape(
            (n * chunk,) + v.shape[1:])

    if mode == "traced":
        out = a2a_local(x)
    else:
        out = _eager_axis_collective(x, ax, lambda v: a2a_local(v[0])[None])
    if out_tensor_list is not None:
        chunk = out.shape[0] // n
        for i in range(n):
            out_tensor_list.append(Tensor(out[i * chunk:(i + 1) * chunk]))
        return out_tensor_list
    return Tensor(out)


alltoall = all_to_all


# ---- eager point-to-point (ProcessGroup::Send/Recv,
# /root/reference/paddle/fluid/distributed/collective/ProcessGroup.h:104,110) ----
#
# TPU-native design: the payload moves DEVICE-to-device through a ppermute
# program compiled over a 2-row submesh containing ONLY the two endpoints'
# devices — uninvolved processes never participate (no world-sized barrier),
# and on a TPU slice the permute rides ICI exactly like the reference's NCCL
# send/recv rides NVLink. Only shape/dtype metadata goes through the
# coordinator KV service (the TCPStore analogue), which is how recv
# "negotiates" when its buffer is not preallocated. Per-(src,dst) sequence
# numbers keep transfers matched; programs on the same endpoint pair must be
# issued in the same order on both processes (SPMD launch-order rule — the
# same constraint NCCL puts on a stream). For bidirectional/neighbor
# exchange use batch_isend_irecv, which fuses all ops into ONE program.

_p2p_seq = {}


def _kv_client():
    try:
        from jax._src import distributed as _dist

        return _dist.global_state.client
    except Exception:
        return None


def _p2p_pair_program(src: int, dst: int, shape, dtype_str: str):
    """Compiled single-direction transfer over the {src, dst} pair submesh.

    Cached per (pair, direction, shape, dtype): pipeline loops re-issuing
    same-shape transfers must not pay a retrace per call."""
    return _p2p_program_cached(src, dst, tuple(shape), dtype_str)


@_functools.lru_cache(maxsize=256)
def _p2p_program_cached(src, dst, shape, dtype_str):
    from jax.sharding import NamedSharding, PartitionSpec as P

    # one device per endpoint process (rank = process; a multi-chip host
    # stages its payload on its first device — a local D2D move at most)
    def first_dev(proc):
        return min((d for d in jax.devices() if d.process_index == proc),
                   key=lambda d: d.id)

    mesh = jax.sharding.Mesh(np.array([first_dev(src), first_dev(dst)]),
                             ("pair",))
    sharding = NamedSharding(mesh, P("pair"))

    def f(v):  # v: [1, *shape] — this endpoint's row; src=pair-index 0
        moved = jax.lax.ppermute(v, "pair", [(0, 1)])
        keep = jax.lax.axis_index("pair") == 1
        return jnp.where(keep, moved, v)

    fn = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=(P("pair"),),
                               out_specs=P("pair"), check_vma=False))
    return fn, mesh, sharding


def _p2p_local_row(x, sharding):
    """This process's [1, *shape] shard on its endpoint device, avoiding a
    host round-trip when the payload is already a device array."""
    dev = next(d for d in sharding.mesh.devices.flat
               if d.process_index == jax.process_index())
    row = jax.device_put(jnp.asarray(x)[None], jax.sharding.SingleDeviceSharding(dev))
    return row


def _p2p_transfer(x, src: int, dst: int):
    """Run the pair program; returns this process's (post-transfer) row."""
    fn, mesh, sharding = _p2p_pair_program(src, dst, x.shape, str(x.dtype))
    row = _p2p_local_row(x, sharding)
    glob = jax.make_array_from_single_device_arrays(
        (2,) + tuple(x.shape), sharding, [row])
    out = fn(glob)
    shard = out.addressable_shards[0]
    return jnp.asarray(shard.data)[0]


def _p2p_rank_bounds(rank: int, other: int, op: str):
    world = jax.process_count()
    if world <= 1:
        raise ValueError(
            f"{op}: point-to-point needs a multi-process environment "
            f"(init_parallel_env/launch); within one controller move data "
            f"with reshard()/ppermute instead")
    if not 0 <= other < world:
        raise ValueError(f"{op}: peer rank {other} out of range [0, {world})")
    if other == rank:
        raise ValueError(f"{op}: peer rank {other} is this process")


def _p2p_meta_key(src: int, dst: int, seq: int) -> str:
    return f"paddle_tpu_p2p/{src}->{dst}/{seq}"


def _p2p_get_meta(src: int, rank: int, seq: int, timeout_ms: int = 60_000):
    """Blocking metadata fetch; returns None only when no coordinator KV
    service exists. Timeouts and malformed values raise — silently skipping
    negotiation converts shape mismatches into undebuggable hangs."""
    client = _kv_client()
    if client is None:
        return None
    raw = client.blocking_key_value_get(_p2p_meta_key(src, rank, seq),
                                        timeout_ms)
    shape_s, dtype_s = raw.split("|")
    return tuple(int(s) for s in shape_s.split(",") if s), dtype_s


class P2POp:
    """Transfer handle (paddle isend/irecv contract). The SPMD program has
    already synchronized both endpoints by construction, so wait() is a
    no-op; the class also serves as the op descriptor for batch_isend_irecv
    (op="isend"/"irecv")."""

    def __init__(self, op, tensor=None, peer=None, group=None):
        # descriptor form: P2POp(dist.isend | "isend", tensor, peer) — op is
        # a string/callable, never a Tensor (Tensor.__eq__ is elementwise)
        if isinstance(op, str) or callable(op):
            self.op = getattr(op, "__name__", op)
            self.tensor = tensor
            self.peer = peer
            self.group = group
        else:  # completed-handle form: P2POp(result_tensor)
            self.op = "done"
            self.tensor = op

    def wait(self):
        return self.tensor

    def is_completed(self):
        return True


def send(tensor, dst=0, group=None, sync_op=True):
    rank = jax.process_index()
    _p2p_rank_bounds(rank, dst, "send")
    x = tensor._data if isinstance(tensor, Tensor) else jnp.asarray(tensor)
    seq = _p2p_seq.get((rank, dst), 0) + 1
    client = _kv_client()
    if client is not None:
        client.key_value_set(
            _p2p_meta_key(rank, dst, seq),
            f"{','.join(map(str, x.shape))}|{x.dtype}")
    _p2p_seq[(rank, dst)] = seq  # committed: the transfer WILL be dispatched
    _p2p_transfer(x, rank, dst)
    return tensor


def recv(tensor, src=0, group=None, sync_op=True):
    rank = jax.process_index()
    _p2p_rank_bounds(rank, src, "recv")
    seq = _p2p_seq.get((src, rank), 0) + 1
    meta = _p2p_get_meta(src, rank, seq)  # raises on timeout: seq NOT consumed,
    #                                       a retried recv still matches the sender
    if tensor is None:
        if meta is None:
            raise ValueError(
                "recv: pass a preallocated tensor (metadata negotiation "
                "needs the jax coordinator KV service)")
        local = jnp.zeros(meta[0], dtype=meta[1])
    else:
        local = tensor._data if isinstance(tensor, Tensor) else jnp.asarray(tensor)
        if meta is not None and (tuple(local.shape) != meta[0]
                                 or str(local.dtype) != meta[1]):
            raise ValueError(
                f"recv: buffer {tuple(local.shape)}/{local.dtype} does not "
                f"match sent {meta[0]}/{meta[1]} (negotiated via coordinator)")
    _p2p_seq[(src, rank)] = seq
    got = _p2p_transfer(local, src, rank)
    if isinstance(tensor, Tensor):
        tensor._data = got
        return tensor
    return Tensor(got)


def isend(tensor, dst=0, group=None):
    return P2POp(send(tensor, dst, group))


def irecv(tensor, src=0, group=None):
    return P2POp(recv(tensor, src, group))


_p2p_batch_counter = [0]


def batch_isend_irecv(p2p_op_list):
    """Fuse P2POp("isend"/"irecv") descriptors into ONE world collective —
    the reference's batch_isend_irecv (communication/batch_isend_irecv.py).

    Contract (matches the reference's NCCL-group requirement): EVERY process
    in the job calls this at the same point, with its own (possibly empty)
    op list. Each rank publishes its send pairs through the coordinator KV
    service; the union forms one ppermute over a world mesh, so asymmetric
    neighbor topologies (pipeline lines) compile the SAME program on every
    process — per-pair local derivations cannot deadlock-by-disagreement.
    Limits: at most one isend and one irecv per rank per batch (one mesh
    row each way), all tensors one shape/dtype.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    rank = jax.process_index()
    world = jax.process_count()
    if world <= 1:
        raise ValueError("batch_isend_irecv: needs a multi-process "
                         "environment (init_parallel_env/launch)")
    sends = [op for op in p2p_op_list if op.op == "isend"]
    recvs = [op for op in p2p_op_list if op.op == "irecv"]
    if len(sends) + len(recvs) != len(p2p_op_list):
        bad = [op.op for op in p2p_op_list
               if op.op not in ("isend", "irecv")]
        raise ValueError(f"batch_isend_irecv: bad op(s) {bad!r}")
    if len(sends) > 1 or len(recvs) > 1:
        raise ValueError(
            "batch_isend_irecv: at most one isend and one irecv per rank "
            "per batch (one ppermute row each way); split into several "
            "batches for multi-peer fan-out")
    for op in sends + recvs:
        _p2p_rank_bounds(rank, op.peer, "batch_isend_irecv")

    client = _kv_client()
    if client is None:
        raise RuntimeError(
            "batch_isend_irecv: the jax coordinator KV service is required "
            "to agree on the global pair list")
    # every process calls every batch, so a local counter is globally
    # consistent — it names this batch's KV namespace
    _p2p_batch_counter[0] += 1
    bidx = _p2p_batch_counter[0]
    my_pair = f"{rank}->{sends[0].peer}" if sends else ""
    client.key_value_set(f"paddle_tpu_p2p_batch/{bidx}/{rank}", my_pair)
    perm = set()
    for r in range(world):
        raw = client.blocking_key_value_get(
            f"paddle_tpu_p2p_batch/{bidx}/{r}", 60_000)
        if raw:
            a, b = raw.split("->")
            perm.add((int(a), int(b)))
    perm = sorted(perm)

    # payload prototype: my tensors, else negotiated from any sender's
    # metadata (all tensors in a batch share shape/dtype)
    protos = [op.tensor._data if isinstance(op.tensor, Tensor)
              else jnp.asarray(op.tensor) for op in sends + recvs]
    if any(p.shape != protos[0].shape or p.dtype != protos[0].dtype
           for p in protos):
        raise ValueError("batch_isend_irecv: all tensors must share one "
                         "shape/dtype in a batch")

    def first_dev(proc):
        return min((d for d in jax.devices() if d.process_index == proc),
                   key=lambda d: d.id)

    mesh = jax.sharding.Mesh(np.array([first_dev(r) for r in range(world)]),
                             ("p",))
    sharding = NamedSharding(mesh, P("p"))
    if protos:
        shape, dtype = tuple(protos[0].shape), protos[0].dtype
    else:  # pure bystander: learn the payload shape from any sender
        if not perm:
            return []
        src0 = perm[0][0]
        seqs = client.blocking_key_value_get(
            f"paddle_tpu_p2p_batch_meta/{bidx}/{src0}", 60_000)
        shape_s, dtype_s = seqs.split("|")
        shape = tuple(int(s) for s in shape_s.split(",") if s)
        dtype = dtype_s
    if sends:
        client.key_value_set(
            f"paddle_tpu_p2p_batch_meta/{bidx}/{rank}",
            f"{','.join(map(str, protos[0].shape))}|{protos[0].dtype}")
    if recvs:
        if (recvs[0].peer, rank) not in perm:
            raise ValueError(
                f"batch_isend_irecv: irecv from {recvs[0].peer} has no "
                f"matching isend in this batch (pairs: {perm})")
        raw = client.blocking_key_value_get(
            f"paddle_tpu_p2p_batch_meta/{bidx}/{recvs[0].peer}", 60_000)
        shape_s, dtype_s = raw.split("|")
        sent = (tuple(int(s) for s in shape_s.split(",") if s), dtype_s)
        if tuple(shape) != sent[0] or str(dtype) != sent[1]:
            raise ValueError(
                f"batch_isend_irecv: recv buffer {tuple(shape)}/{dtype} "
                f"does not match sent {sent[0]}/{sent[1]}")
    local = (sends[0].tensor._data if sends and isinstance(sends[0].tensor,
                                                           Tensor)
             else jnp.asarray(sends[0].tensor) if sends
             else jnp.zeros(shape, dtype))
    row = jax.device_put(jnp.asarray(local)[None],
                         jax.sharding.SingleDeviceSharding(first_dev(rank)))
    glob = jax.make_array_from_single_device_arrays(
        (world,) + tuple(shape), sharding, [row])

    def f(v):
        return jax.lax.ppermute(v, "p", perm)

    out = jax.shard_map(f, mesh=mesh, in_specs=(P("p"),), out_specs=P("p"),
                        check_vma=False)(glob)
    my_row = jnp.asarray(out.addressable_shards[0].data)[0]
    results = []
    for op in p2p_op_list:
        if op.op == "irecv":
            if isinstance(op.tensor, Tensor):
                op.tensor._data = my_row
                results.append(P2POp(op.tensor))
            else:  # raw-array buffer: hand back the received Tensor
                results.append(P2POp(Tensor(my_row)))
        else:
            results.append(P2POp(op.tensor))
    return results


def barrier(group=None):
    # single-controller: all local devices are driven by this process; only
    # multi-host needs an actual sync
    import jax as _j

    try:
        from jax.experimental import multihost_utils

        if _j.process_count() > 1:
            multihost_utils.sync_global_devices("paddle_tpu_barrier")
    except Exception:
        pass


def wait(tensor, group=None, use_calc_stream=True):
    if isinstance(tensor, Tensor):
        tensor._data.block_until_ready()
    return tensor


# ---- traced-mode helpers used by meta_parallel layers ----

def p_split(x, axis_name: str, dim: int):
    """c_split analogue: take this shard's slice along `dim` (traced mode)."""
    idx = jax.lax.axis_index(axis_name)
    hcg = get_hybrid_communicate_group()
    n = hcg.degrees[axis_name]
    size = x.shape[dim] // n
    return jax.lax.dynamic_slice_in_dim(x, idx * size, size, axis=dim)


def p_concat(x, axis_name: str, dim: int):
    """c_concat analogue: all_gather along `dim` (traced mode)."""
    return jax.lax.all_gather(x, axis_name, axis=dim, tiled=True)
