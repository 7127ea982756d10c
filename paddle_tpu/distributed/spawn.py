"""paddle.distributed.spawn — single-node multiprocess entry (reference spawn.py).

On a TPU host a single controller drives all local chips, and a chip belongs
to one process at a time: nprocs > 1 is refused there (multi-host jobs start
one process per host through the launcher CLI). spawn with nprocs=1 (or
default) simply runs the function; on the CPU platform nprocs > 1 starts
that many worker processes.
"""
from __future__ import annotations

import multiprocessing as mp
import os


def _worker(func, args, env):
    os.environ.update(env)
    func(*args)


def spawn(func, args=(), nprocs=-1, join=True, daemon=False, **options):
    if nprocs in (-1, 0, 1):
        func(*args)
        return None

    from .launch.main import one_controller_per_host

    one_controller_per_host(nprocs, "paddle_tpu.distributed.spawn")
    # spawn (not fork): the parent may have initialized JAX, which is
    # multithreaded — forking a multithreaded process can deadlock children
    # on PJRT/threadpool locks. spawn requires func/args to be picklable
    # (same contract as torch).
    ctx = mp.get_context("spawn")
    procs = []
    for rank in range(nprocs):
        env = {
            "PADDLE_TRAINER_ID": str(rank),
            "PADDLE_TRAINERS_NUM": str(nprocs),
        }
        p = ctx.Process(target=_worker, args=(func, args, env), daemon=daemon)
        p.start()
        procs.append(p)
    if join:
        for p in procs:
            p.join()
    return procs
