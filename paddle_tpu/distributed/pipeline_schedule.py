"""SPMD pipeline-parallel schedule: scan + ppermute over the 'pp' mesh axis.

Reference analogue: fleet/meta_parallel/pipeline_parallel.py:31 (PipelineParallel,
forward_backward_pipeline:81 — host-driven 1F1B over NCCL p2p with SendRecvMeta shape
negotiation, p2p_communication.py:26,39,217) and the static-graph SectionWorker
(device_worker.h:615) running micro-batch sections in per-device threads.

TPU-native redesign: the whole pipeline is ONE XLA computation. Each pp rank holds its
stage's parameters (leading stage dim sharded over 'pp'); micro-batches rotate through
the stages with `jax.lax.ppermute` (ICI neighbor hop) inside a `lax.scan` over
M + S - 1 "clock ticks" (GPipe fill/steady/drain). There is no Python scheduler, no
shape handshake (shapes are static in the traced program), and no separate comm stream
(XLA overlaps the permute with the next tick's compute). The backward schedule is not
hand-written: `jax.vjp` through scan+ppermute replays the ring in reverse, which is
exactly the reference's backward pass ordering, and XLA pipelines it the same way.

Cost model: bubble fraction = (S-1)/(M+S-1), same as GPipe/1F1B; activation working set
is one micro-batch per stage plus the scan residuals (use jax.checkpoint in the body to
trade FLOPs for HBM, the recompute_interval analogue).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P



def spmd_pipeline(body_fn, stage_params, x_mb, mesh, axis: str = "pp"):
    """Run a homogeneous pipeline over the `axis` mesh dimension.

    body_fn(stage_params_local, x) -> y
        one stage's compute; x and y must share shape/dtype (activation shape is
        uniform across stages, as in the reference's SendRecvMeta contract).
    stage_params: pytree whose leaves have leading dim S (= mesh.shape[axis]); leaf i
        along that dim is stage i's parameters. Sharded over `axis` by this call.
    x_mb: [M, micro_batch, ...] micro-batched activations, replicated over `axis`
        (other mesh axes — dp/mp/sp — stay under GSPMD auto sharding).
    Returns [M, micro_batch, ...] outputs of the last stage, replicated over `axis`.

    Differentiable: reverse-mode AD through the scan gives the backward pipeline.
    """
    S = int(mesh.shape[axis])
    if S == 1:
        squeezed = jax.tree.map(lambda l: jnp.squeeze(l, 0), stage_params)
        return jax.vmap(lambda x: body_fn(squeezed, x))(x_mb)

    param_specs = jax.tree.map(lambda _: P(axis), stage_params)
    xspec = P()

    def local(params, mb):
        params = jax.tree.map(lambda l: jnp.squeeze(l, 0), params)
        stage = jax.lax.axis_index(axis)
        M = mb.shape[0]
        n_ticks = M + S - 1
        state = jnp.zeros_like(mb[0])
        out = jnp.zeros_like(mb)
        perm = [(i, (i + 1) % S) for i in range(S)]

        def tick(carry, t):
            state, out = carry
            # stage 0 ingests micro-batch t (clamped reads past the end are
            # discarded: their outputs never land in a valid out slot)
            inp = jax.lax.dynamic_index_in_dim(mb, jnp.clip(t, 0, M - 1), 0,
                                               keepdims=False)
            cur = jnp.where(stage == 0, inp, state)
            y = body_fn(params, cur)
            # last stage emits micro-batch t-(S-1) once the pipe is full
            oidx = t - (S - 1)
            upd = jax.lax.dynamic_update_index_in_dim(
                out, y.astype(out.dtype), jnp.clip(oidx, 0, M - 1), 0)
            out = jnp.where(jnp.logical_and(stage == S - 1, oidx >= 0), upd, out)
            # rotate activations one hop along the ring (stage s -> s+1)
            state = jax.lax.ppermute(y, axis, perm)
            return (state, out), None

        (_, out), _ = jax.lax.scan(tick, (state, out), jnp.arange(n_ticks))
        # replicate the result over the pp axis (only the last stage holds it)
        return jax.lax.psum(jnp.where(stage == S - 1, out, jnp.zeros_like(out)), axis)

    return jax.shard_map(local, mesh=mesh, in_specs=(param_specs, xspec),
                         out_specs=xspec, axis_names={axis},
                         check_vma=False)(stage_params, x_mb)


def _interleaved_schedule(P_: int, V: int, M: int):
    """Static interleaved (circular/virtual-stage) schedule.

    Logical stage s = v*P + r lives on rank r = s % P; an activation leaving
    rank P-1 at chunk v re-enters rank 0 as chunk v+1. Each tick every rank
    processes at most ONE (chunk, microbatch); arrivals it cannot process yet
    wait in a buffer. Work-conserving, higher-chunk-first priority (drain the
    deep end — the 1F1B-flavored order). Returns per-rank int arrays, each
    [P, T]:

      v_sel      chunk whose params to apply (0 when idle)
      ingest     microbatch index to read from x_mb (rank0/chunk0), else -1
      buf_read   buffer slot holding the input activation, else -1
      buf_write  slot where THIS tick's arriving activation is stored, -1
      out_write  output microbatch index emitted this tick, else -1
      valid      1 when the rank does real work this tick

    plus (T, buf_slots). The simulator mirrors the reference's interleaved
    SectionWorker schedule (device_worker.h:615) in tick-synchronous form;
    total ticks ~ M*V + (V-1) + 2*(P-1) vs the sequential stacking's
    V*(M + P - 1) — the bubble shrinks by ~V.
    """
    ingest_next = 0
    # per-rank waiting queues of (v, m, slot); slot == -1 means "from mb"
    waiting = [[] for _ in range(P_)]
    free_slots = [list(range(64)) for _ in range(P_)]  # generous; trimmed below
    arrivals = [dict() for _ in range(P_)]  # tick -> (v, m)
    rows = {k: [[] for _ in range(P_)]
            for k in ("v_sel", "ingest", "buf_read", "buf_write", "out_write",
                      "valid")}
    max_slot = -1
    done = 0
    t = 0
    while done < M:
        if t > 4 * (M * V + P_ * V + 8):
            raise RuntimeError("interleaved schedule did not converge")
        sent = []  # (dst_rank, v, m) arriving at t+1
        for r in range(P_):
            # 1. store this tick's arrival into a buffer slot
            bw = -1
            if t in arrivals[r]:
                v, m = arrivals[r].pop(t)
                bw = free_slots[r].pop(0)
                max_slot = max(max_slot, bw)
                waiting[r].append((v, m, bw))
            rows["buf_write"][r].append(bw)
            # 2. pick work: highest chunk first, then lowest microbatch
            choice = None
            if waiting[r]:
                choice = max(waiting[r], key=lambda it: (it[0], -it[1]))
            if choice is None and r == 0 and ingest_next < M:
                choice = (0, ingest_next, -1)
                ingest_next += 1
            if choice is None:
                rows["v_sel"][r].append(0)
                rows["ingest"][r].append(-1)
                rows["buf_read"][r].append(-1)
                rows["out_write"][r].append(-1)
                rows["valid"][r].append(0)
                continue
            v, m, slot = choice
            if slot >= 0:
                waiting[r].remove(choice)
                free_slots[r].insert(0, slot)
            rows["v_sel"][r].append(v)
            rows["ingest"][r].append(m if slot == -1 else -1)
            rows["buf_read"][r].append(slot)
            rows["valid"][r].append(1)
            if r == P_ - 1 and v == V - 1:
                rows["out_write"][r].append(m)
                done += 1
            else:
                rows["out_write"][r].append(-1)
                nxt_v = v if r < P_ - 1 else v + 1
                sent.append(((r + 1) % P_, nxt_v, m))
        for dst, v, m in sent:
            arrivals[dst][t + 1] = (v, m)
        t += 1
    T = t
    import numpy as np

    return ({k: np.asarray(rows[k], np.int32) for k in rows}, T,
            max(max_slot + 1, 1))


def spmd_pipeline_interleaved(body_fn, stage_params, x_mb, mesh,
                              axis: str = "pp", num_chunks: int = 2):
    """Interleaved virtual-stage pipeline (reference SectionWorker's
    interleaved 1F1B, device_worker.h:615) as ONE tick-synchronous SPMD
    scan: each rank holds `num_chunks` stage chunks (logical stage
    v*P + rank), activations ride `ppermute` around the ring V times, and a
    static host-computed schedule (buffer slots, chunk selection, emission
    ticks) resolves the arrival/processing order — so the pipeline bubble
    is ~(P-1) ticks TOTAL instead of the V*(P-1) that stacking chunks
    sequentially pays. Reverse-mode AD through the scan replays the
    mirrored schedule as the backward pipeline.

    stage_params: pytree whose leaves have leading dims [V, P] — leaf
    [v, r] is the parameters of logical stage v*P + r (chunk-major), so a
    plain NamedSharding P(None, axis) puts each rank's V chunks where they
    execute. x_mb: [M, micro_batch, ...].
    """
    P_ = int(mesh.shape[axis])
    V = int(num_chunks)
    if P_ == 1:
        # degenerate ring: run the V chunks sequentially (spmd_pipeline's
        # S==1 squeeze path would choke on the V-sized stage dim)
        chunks = jax.tree.map(lambda l: jnp.squeeze(l, 1), stage_params)
        out = x_mb
        for v in range(V):
            pv = jax.tree.map(lambda l: l[v], chunks)
            out = jax.vmap(lambda x, pv=pv: body_fn(pv, x))(out)
        return out
    if V == 1:
        merged = jax.tree.map(lambda l: jnp.squeeze(l, 0), stage_params)
        return spmd_pipeline(body_fn, merged, x_mb, mesh, axis)
    M = int(x_mb.shape[0])
    sched, T, n_slots = _interleaved_schedule(P_, V, M)

    vp_params = stage_params
    jax.tree.map(lambda l: None if l.shape[:2] == (V, P_) else
                 (_ for _ in ()).throw(ValueError(
                     f"interleaved stage leaf needs leading dims "
                     f"[{V}, {P_}], got {l.shape}")), vp_params)
    param_specs = jax.tree.map(lambda _: P(None, axis), vp_params)
    xspec = P()
    sspec = P(axis)

    def local(params, mb, v_sel, ingest, buf_read, buf_write, out_write,
              valid):
        # drop the sharded rank dim (size 1 per shard)
        params = jax.tree.map(lambda l: jnp.squeeze(l, 1), params)
        for a in (v_sel, ingest, buf_read, buf_write, out_write, valid):
            assert a.shape[0] == 1
        v_sel, ingest, buf_read, buf_write, out_write, valid = (
            a[0] for a in (v_sel, ingest, buf_read, buf_write, out_write,
                           valid))
        rank = jax.lax.axis_index(axis)
        out = jnp.zeros_like(mb)
        # +1 dummy slot: buf_write == -1 parks the (masked) arrival there
        buf = jnp.zeros((n_slots + 1,) + mb.shape[1:], mb.dtype)
        state = jnp.zeros_like(mb[0])
        perm = [(i, (i + 1) % P_) for i in range(P_)]

        def tick(carry, t):
            state, buf, out = carry
            bw = buf_write[t]
            buf = jax.lax.dynamic_update_index_in_dim(
                buf, state.astype(buf.dtype),
                jnp.where(bw >= 0, bw, n_slots), 0)
            from_mb = jax.lax.dynamic_index_in_dim(
                mb, jnp.clip(ingest[t], 0, M - 1), 0, keepdims=False)
            from_buf = jax.lax.dynamic_index_in_dim(
                buf, jnp.clip(buf_read[t], 0, n_slots), 0, keepdims=False)
            cur = jnp.where(ingest[t] >= 0, from_mb, from_buf)
            p_v = jax.tree.map(
                lambda l: jax.lax.dynamic_index_in_dim(
                    l, v_sel[t], 0, keepdims=False), params)
            y = body_fn(p_v, cur)
            # only real work may land anywhere: idle ticks emit zeros
            y = jnp.where(valid[t] > 0, y, jnp.zeros_like(y))
            oidx = out_write[t]
            upd = jax.lax.dynamic_update_index_in_dim(
                out, y.astype(out.dtype), jnp.clip(oidx, 0, M - 1), 0)
            out = jnp.where(oidx >= 0, upd, out)
            state = jax.lax.ppermute(y, axis, perm)
            return (state, buf, out), None

        (_, _, out), _ = jax.lax.scan(tick, (state, buf, out),
                                      jnp.arange(T))
        last = rank == P_ - 1
        return jax.lax.psum(jnp.where(last, out, jnp.zeros_like(out)), axis)

    sch_args = tuple(jnp.asarray(sched[k]) for k in
                     ("v_sel", "ingest", "buf_read", "buf_write",
                      "out_write", "valid"))
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(param_specs, xspec) + (sspec,) * 6,
        out_specs=xspec, axis_names={axis},
        check_vma=False)(vp_params, x_mb, *sch_args)


def microbatch_split(x, num_micro: int):
    """[B, ...] -> [M, B/M, ...]; B must divide by num_micro."""
    b = x.shape[0]
    if b % num_micro != 0:
        raise ValueError(f"batch {b} not divisible by {num_micro} micro-batches")
    return x.reshape((num_micro, b // num_micro) + tuple(x.shape[1:]))


def microbatch_merge(x):
    """[M, mb, ...] -> [M*mb, ...]."""
    return x.reshape((x.shape[0] * x.shape[1],) + tuple(x.shape[2:]))
