"""Launcher implementation.

Reference call path: launch/main.py -> Controller.build_pod (collective.py:32)
-> spawn N procs with the PADDLE_TRAINER* env -> watch().  Same shape here:
parse args, rendezvous (multi-node via TCPStore), build the env for each local
process, spawn, watch, tear down on failure. PS mode (--server_num/--trainer_num)
sets the PS env contract (controllers/ps.py:21).
"""
from __future__ import annotations

import argparse
import glob
import os
import signal
import socket
import subprocess
import sys
import time
from typing import Dict, List, Optional


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def tpu_chips_on_host() -> int:
    """TPU chips this host exposes to a process that is not pinned to the CPU
    platform, counted from their device nodes — without touching jax, so the
    caller does not become a holder of the chips it is asking about."""
    if os.environ.get("JAX_PLATFORMS", "").split(",")[0].strip() == "cpu":
        return 0
    return len(glob.glob("/dev/accel[0-9]*")
               + glob.glob("/dev/vfio/[0-9]*"))


def one_controller_per_host(nproc: int, what: str) -> None:
    """Refuse more than one worker per TPU host. A chip belongs to one
    process at a time and nothing here partitions the chips between workers,
    so a second worker fails or hangs when its backend starts; one controller
    process drives every local chip. CPU-platform jobs (JAX_PLATFORMS=cpu)
    may start as many workers as they like."""
    chips = tpu_chips_on_host()
    if nproc > 1 and chips:
        raise SystemExit(
            f"{what}: {nproc} workers asked for on a host with {chips} TPU "
            f"chip(s). One controller process drives all local chips; start "
            f"one worker per host (the mesh spans jax.devices()), or set "
            f"JAX_PLATFORMS=cpu for a CPU-platform job.")


def _parse_args(argv=None):
    p = argparse.ArgumentParser(
        prog="python -m paddle_tpu.distributed.launch",
        description="paddle_tpu distributed launcher")
    p.add_argument("--master", default=None,
                   help="rendezvous endpoint host:port (rank-0 node hosts the store)")
    p.add_argument("--nnodes", type=int, default=int(os.environ.get("PADDLE_NNODES", 1)))
    p.add_argument("--node_rank", type=int,
                   default=int(os.environ.get("PADDLE_NODE_RANK", -1)),
                   help="-1 = assign via the store's arrival counter")
    p.add_argument("--nproc_per_node", type=int,
                   default=int(os.environ.get("PADDLE_NPROC_PER_NODE", 1)))
    p.add_argument("--devices", default=os.environ.get("PADDLE_DEVICES", ""),
                   help="comma-separated device ordinals handed to workers")
    p.add_argument("--job_id", default=os.environ.get("PADDLE_JOB_ID", "default"))
    p.add_argument("--log_dir", default=os.environ.get("PADDLE_LOG_DIR", "log"))
    p.add_argument("--run_mode", default="collective",
                   choices=["collective", "ps"])
    p.add_argument("--server_num", type=int, default=0, help="PS mode: #servers")
    p.add_argument("--trainer_num", type=int, default=None, help="PS mode: #trainers")
    p.add_argument("--elastic_level", type=int, default=0,
                   help=">0: restart failed workers in place (single-node)")
    p.add_argument("--max_restarts", type=int, default=3)
    p.add_argument("-m", "--module", default=None,
                   help="run a module (python -m style) instead of a script")
    p.add_argument("training_script", nargs="?", default=None,
                   help="training script to run (or use -m MODULE)")
    p.add_argument("training_script_args", nargs=argparse.REMAINDER)
    args = p.parse_args(argv)
    if args.module is None and args.training_script is None:
        p.error("a training script or -m MODULE is required")
    return args


class ProcList:
    def __init__(self, log_dir: str):
        self.procs: List[subprocess.Popen] = []
        self.specs: List[dict] = []
        self.log_dir = log_dir
        os.makedirs(log_dir, exist_ok=True)

    def spawn(self, cmd: List[str], env: Dict[str, str], name: str):
        log_path = os.path.join(self.log_dir, f"{name}.log")
        log_f = open(log_path, "ab")
        proc = subprocess.Popen(cmd, env=env, stdout=log_f, stderr=subprocess.STDOUT)
        self.procs.append(proc)
        self.specs.append({"cmd": cmd, "env": env, "name": name, "log": log_path,
                           "file": log_f})
        return proc

    def respawn(self, i: int):
        spec = self.specs[i]
        spec["file"].close()
        spec["file"] = open(spec["log"], "ab")
        self.procs[i] = subprocess.Popen(spec["cmd"], env=spec["env"],
                                         stdout=spec["file"],
                                         stderr=subprocess.STDOUT)

    def poll(self) -> Optional[int]:
        """Index of the first failed proc, or None; -1 when all exited cleanly."""
        all_done = True
        for i, p in enumerate(self.procs):
            rc = p.poll()
            if rc is None:
                all_done = False
            elif rc != 0:
                return i
        return -1 if all_done else None

    def terminate(self):
        self.terminate_alive(grace=10.0)
        for s in self.specs:
            s["file"].close()

    def terminate_alive(self, grace: float = 5.0):
        """SIGTERM then SIGKILL stragglers, keeping log files open so the
        procs can be respawned (terminate() additionally closes the pool)."""
        alive = [p for p in self.procs if p.poll() is None]
        for p in alive:
            p.send_signal(signal.SIGTERM)
        deadline = time.time() + grace
        for p in alive:
            try:
                p.wait(max(0.1, deadline - time.time()))
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()

    def tail_log(self, i: int, n: int = 30) -> str:
        try:
            with open(self.specs[i]["log"], "r", errors="replace") as f:
                return "".join(f.readlines()[-n:])
        except OSError:
            return "<no log>"


def _advertised_host() -> str:
    """The address peers can reach this node at (the reference reads it from
    POD_IP / the endpoint list; we resolve the hostname with a localhost guard)."""
    ip = os.environ.get("POD_IP")
    if ip:
        return ip
    try:
        ip = socket.gethostbyname(socket.gethostname())
    except OSError:
        ip = "127.0.0.1"
    return ip


def _rendezvous(args, nproc: int):
    """Return (node_rank, master_addr, master_port, all_endpoints, store-or-None).

    Multi-node: node rank is either given (--node_rank) or assigned by arrival
    order through the store's atomic counter (the reference's HTTP/ETCD master,
    controllers/master.py). Every node publishes its worker endpoints — its OWN
    advertised host + locally free ports — and reads back the full list, so all
    ranks agree; rank 0 also publishes a dedicated coordinator port for the
    workers' jax.distributed.initialize (distinct from the store's port)."""
    if args.nnodes <= 1:
        base = _free_port()
        eps = [f"127.0.0.1:{base + i}" for i in range(nproc)]
        return 0, "127.0.0.1", _free_port(), eps, None

    assert args.master, "--master host:port is required when --nnodes > 1"
    host, port_s = args.master.rsplit(":", 1)
    port = int(port_s)
    from ..store import TCPStore

    # The node whose --node_rank is 0 hosts the store. With auto-assign (-1),
    # try joining as a client first; only if no server answers, try to become
    # the host (losing the bind race falls back to client) — so exactly one
    # node ever runs a store server.
    if args.node_rank == 0:
        store = TCPStore(host, port, is_master=True, world_size=args.nnodes)
    elif args.node_rank > 0:
        store = TCPStore(host, port, is_master=False, world_size=args.nnodes)
    else:
        try:
            store = TCPStore(host, port, is_master=False,
                             world_size=args.nnodes, timeout=3.0)
        except (RuntimeError, TimeoutError):
            try:
                store = TCPStore(host, port, is_master=True,
                                 world_size=args.nnodes)
            except RuntimeError:  # lost the bind race to another auto node
                store = TCPStore(host, port, is_master=False,
                                 world_size=args.nnodes)
    rank = args.node_rank
    if rank < 0:
        rank = store.add(f"{args.job_id}/node_arrival", 1) - 1

    my_host = _advertised_host()
    base = _free_port()
    my_eps = ",".join(f"{my_host}:{base + i}" for i in range(nproc))
    store.set(f"{args.job_id}/endpoints/{rank}", my_eps)
    if rank == 0:
        store.set(f"{args.job_id}/worker_master", f"{my_host}:{_free_port()}")
    store.barrier(f"{args.job_id}/nodes_ready", args.nnodes)

    all_endpoints = []
    for n in range(args.nnodes):
        all_endpoints.extend(
            store.get(f"{args.job_id}/endpoints/{n}").decode().split(","))
    master_addr, worker_master_port = \
        store.get(f"{args.job_id}/worker_master").decode().rsplit(":", 1)
    return rank, master_addr, int(worker_master_port), all_endpoints, store


def launch(argv=None) -> int:
    args = _parse_args(argv)
    ps_servers = args.server_num if args.run_mode == "ps" else 0
    trainers = args.trainer_num if (args.run_mode == "ps"
                                    and args.trainer_num is not None) else \
        args.nproc_per_node

    nproc = trainers  # trainer processes per node
    one_controller_per_host(nproc, "paddle_tpu.distributed.launch")
    node_rank, master_addr, master_port, all_endpoints, store = \
        _rendezvous(args, nproc)
    world = args.nnodes * nproc
    devices = [d for d in args.devices.split(",") if d]

    procs = ProcList(args.log_dir)
    if args.module is not None:
        script_cmd = [sys.executable, "-m", args.module]
        if args.training_script is not None:  # first arg swallowed the positional
            script_cmd.append(args.training_script)
    else:
        script_cmd = [sys.executable, args.training_script]

    # Children run `python script.py`, which puts the script's dir (not our cwd)
    # on sys.path — make the framework importable from a source checkout by
    # exporting its package root on PYTHONPATH (reference launcher relies on an
    # installed package; launch/controllers/collective.py:23).
    pkg_root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))
    child_pythonpath = os.pathsep.join(
        p for p in [pkg_root, os.environ.get("PYTHONPATH", "")] if p)

    def worker_env(local_rank: int, role: str = "TRAINER") -> Dict[str, str]:
        global_rank = node_rank * nproc + local_rank
        env = {**os.environ, "PYTHONPATH": child_pythonpath}
        env.update({
            "PADDLE_TRAINER_ID": str(global_rank),
            "PADDLE_TRAINERS_NUM": str(world),
            "PADDLE_TRAINER_ENDPOINTS": ",".join(all_endpoints),
            "PADDLE_CURRENT_ENDPOINT": all_endpoints[global_rank],
            "PADDLE_NNODES": str(args.nnodes),
            "PADDLE_NODE_RANK": str(node_rank),
            "PADDLE_LOCAL_RANK": str(local_rank),
            "MASTER_ADDR": master_addr,
            "MASTER_PORT": str(master_port),
            "PADDLE_JOB_ID": args.job_id,
            "TRAINING_ROLE": role,
        })
        if devices:
            env["FLAGS_selected_tpus"] = devices[local_rank % len(devices)]
        if args.elastic_level > 0:
            # per-worker preemption flag file: the launcher touches it when a
            # notice arrives; workers poll fleet.elastic.preemption_requested()
            env["PADDLE_ELASTIC_PREEMPT_FILE"] = os.path.join(
                args.log_dir, f".preempt.{role.lower()}.{local_rank}")
        return env

    if args.run_mode == "ps":
        # each node hosts its own ps_servers instances; endpoints are published
        # through the store so every node sees the full, correct list
        server_ports = [_free_port() for _ in range(ps_servers)]
        my_host = _advertised_host() if args.nnodes > 1 else "127.0.0.1"
        my_server_eps = [f"{my_host}:{p}" for p in server_ports]
        if store is not None:
            store.set(f"{args.job_id}/ps_endpoints/{node_rank}",
                      ",".join(my_server_eps))
            store.barrier(f"{args.job_id}/ps_ready", args.nnodes)
            server_eps = []
            for nr in range(args.nnodes):
                server_eps.extend(
                    store.get(f"{args.job_id}/ps_endpoints/{nr}").decode()
                    .split(","))
        else:
            server_eps = my_server_eps
        for i in range(ps_servers):
            env = worker_env(0, role="PSERVER")
            env.update({"PADDLE_PORT": str(server_ports[i]),
                        "PADDLE_PSERVERS_IP_PORT_LIST": ",".join(server_eps),
                        "PADDLE_PSERVER_ID": str(node_rank * ps_servers + i)})
            procs.spawn(script_cmd + args.training_script_args, env, f"server.{i}")
        for i in range(trainers):
            env = worker_env(i, role="TRAINER")
            env["PADDLE_PSERVERS_IP_PORT_LIST"] = ",".join(server_eps)
            procs.spawn(script_cmd + args.training_script_args, env, f"trainer.{i}")
    else:
        for i in range(nproc):
            procs.spawn(script_cmd + args.training_script_args, worker_env(i),
                        f"workerlog.{i}")

    def _preemption_notice():
        """Pending preemption notice for THIS node: a `preempt.notice` file in
        log_dir (single-node / tests / local infra hook) or the elastic store
        key `<job>/preempt/<node_rank>` (multi-node; SURVEY §5.3 maintenance-
        notice contract)."""
        fpath = os.path.join(args.log_dir, "preempt.notice")
        if os.path.exists(fpath):
            return {"source": fpath}
        if store is not None:
            # ElasticManager.announce_preemption keys by HOST; rank is also
            # accepted for infra that addresses nodes by index
            for who in _notice_ids:
                try:
                    store.get(f"{args.job_id}/preempt/{who}", wait=False)
                    return {"source": f"store:{args.job_id}/preempt/{who}"}
                except Exception:
                    pass
        return None

    # host resolved ONCE (DNS can stall); store round-trips throttled to every
    # 4th watch tick so steady-state polling stays cheap
    _notice_ids = ((_advertised_host() if store is not None else ""),
                   str(node_rank))
    _notice_tick = [0]

    def _preemption_notice_throttled():
        _notice_tick[0] += 1
        fpath = os.path.join(args.log_dir, "preempt.notice")
        if os.path.exists(fpath):
            return {"source": fpath}
        if _notice_tick[0] % 4 == 0:
            return _preemption_notice()
        return None

    def _drain_and_respawn():
        """Checkpoint-and-respawn: flag every worker, give it a grace window
        to checkpoint and exit, then restart the whole local pod."""
        for spec in procs.specs:
            flag = spec["env"].get("PADDLE_ELASTIC_PREEMPT_FILE")
            if flag:
                open(flag, "w").close()
        deadline = time.time() + 30.0
        while time.time() < deadline and any(
                p.poll() is None for p in procs.procs):
            time.sleep(0.2)
        procs.terminate_alive()
        fpath = os.path.join(args.log_dir, "preempt.notice")
        if os.path.exists(fpath):
            os.unlink(fpath)
        if store is not None:
            for who in (_advertised_host(), str(node_rank)):
                try:
                    store.delete_key(f"{args.job_id}/preempt/{who}")
                except Exception:
                    pass
        for spec in procs.specs:
            flag = spec["env"].get("PADDLE_ELASTIC_PREEMPT_FILE")
            if flag and os.path.exists(flag):
                os.unlink(flag)
        for i in range(len(procs.procs)):
            procs.respawn(i)

    restarts = 0
    try:
        while True:
            if args.elastic_level > 0 and restarts < args.max_restarts \
                    and _preemption_notice_throttled() is not None:
                restarts += 1
                print(f"paddle_tpu.launch: preemption notice — checkpoint-and-"
                      f"respawn ({restarts}/{args.max_restarts})", flush=True)
                _drain_and_respawn()
                continue
            status = procs.poll()
            if status is None:
                time.sleep(0.5)
                continue
            if status == -1:
                print(f"paddle_tpu.launch: all {len(procs.procs)} processes "
                      f"finished", flush=True)
                return 0
            rc = procs.procs[status].returncode
            name = procs.specs[status]["name"]
            if args.elastic_level > 0 and restarts < args.max_restarts:
                restarts += 1
                print(f"paddle_tpu.launch: {name} exited rc={rc}; restart "
                      f"{restarts}/{args.max_restarts}", flush=True)
                procs.respawn(status)
                continue
            print(f"paddle_tpu.launch: {name} failed rc={rc}; terminating pod.\n"
                  f"--- tail of {procs.specs[status]['log']} ---\n"
                  f"{procs.tail_log(status)}", file=sys.stderr, flush=True)
            procs.terminate()
            return rc or 1
    except KeyboardInterrupt:
        procs.terminate()
        return 130


def main():
    sys.exit(launch())
