"""TCPStore: rendezvous key-value store for distributed bootstrap.

Reference: paddle/fluid/distributed/store/tcp_store.h:91 (C++ TCPStore with
set/get/wait/add); built here on the C++ backend in core/native/tcp_store.cc via
ctypes, with a pure-Python socket fallback implementing the same wire protocol
semantics. Rank 0 hosts the server; every rank (including 0) is a client —
exactly the reference's master-socket topology (tcp_utils.cc).
"""
from __future__ import annotations

import ctypes
import os
import random
import time
from typing import Dict, List, Optional

from ..core import monitor as _monitor
from ..core.native import load_library

_DEFAULT_TIMEOUT = 900.0  # seconds, matches the reference's default store timeout
RETRIES = _monitor.stat("store.retries")
LEASE_EXPIRIES = _monitor.stat("store.lease_expiries")
GC_KEYS = _monitor.stat("store.gc_keys")


class _StoreOps:
    """Shared high-level helpers over the primitive set/get/add/wait/
    delete_key/list_keys surface — mixed into TCPStore AND FileStore so the
    elastic membership coordinator runs identically on either backend.

    Generation scoping: a live mesh reformation (distributed/membership.py)
    bumps a world generation; every coordination key a generation touches
    (barrier rounds, member leases, join/leave announcements) lives under a
    ``gen<N>`` namespace so a re-formed world can never trip over counters
    or done-flags a dead generation left behind. ``gc_generation`` sweeps a
    retired generation's keys (counted in ``store.gc_keys``).
    """

    def barrier(self, name: str, world_size: Optional[int] = None,
                timeout: Optional[float] = None,
                generation: Optional[int] = None) -> None:
        """All ranks arrive, then all ranks proceed. Reusable: the round is
        derived from the arrival counter, so the same name synchronizes every
        call (reference uses add+wait loops the same way). ``generation``
        namespaces the round keys per world generation — barrier("resume",
        generation=3) can never consume an arrival generation 2 banked."""
        n = world_size or self.world_size
        ns = (f"__barrier__/gen{int(generation)}/{name}"
              if generation is not None else f"__barrier__/{name}")
        arrived = self.add(f"{ns}/count", 1)
        round_idx = (arrived - 1) // n
        done_key = f"{ns}/round{round_idx}/done"
        if arrived == (round_idx + 1) * n:
            self.set(done_key, b"1")
        self.wait([done_key], timeout)

    def gc_generation(self, generation: int) -> int:
        """Delete every key a retired world generation owned (membership
        leases, join/leave announcements, barrier rounds, fleet metric
        snapshots). Returns the number of keys removed; each removal
        counts in ``store.gc_keys``."""
        removed = 0
        for prefix in (f"__elastic__/gen{int(generation)}/",
                       f"__barrier__/gen{int(generation)}/",
                       f"__fleet__/gen{int(generation)}/"):
            for key in self.list_keys(prefix):
                if self.delete_key(key):
                    removed += 1
        if removed:
            GC_KEYS.increase(removed)
        return removed


def _connect_with_retry(connect, host, port, timeout,
                        max_attempts: Optional[int] = None,
                        base_delay: float = 0.05, max_delay: float = 2.0):
    """Bounded retry with exponential backoff + full jitter around a store
    connect. A rank that races its master (the normal elastic-restart case)
    sees ECONNREFUSED on the first attempts; previously that failed the job
    hard. `connect(per_attempt_timeout)` returns a client or None/raises
    OSError; retries are bounded by the store timeout (the rendezvous
    contract) and optionally by PADDLE_TPU_STORE_CONNECT_ATTEMPTS. Jitter
    decorrelates a pod of ranks hammering a just-restarted master. Every
    retry counts in `store.retries`."""
    if max_attempts is None:
        max_attempts = int(os.environ.get(
            "PADDLE_TPU_STORE_CONNECT_ATTEMPTS", "0") or 0) or None
    deadline = time.monotonic() + timeout
    delay = base_delay
    attempt = 0
    last_exc = None
    while True:
        remaining = deadline - time.monotonic()
        if remaining <= 0:
            break
        attempt += 1
        try:
            client = connect(min(remaining, 5.0))
            if client:
                return client
            last_exc = None
        except OSError as e:  # includes TimeoutError / ConnectionRefused
            last_exc = e
        if max_attempts is not None and attempt >= max_attempts:
            break
        remaining = deadline - time.monotonic()
        if remaining <= 0:
            break
        RETRIES.increase()
        time.sleep(min(delay, max_delay, remaining)
                   * (0.5 + random.random() * 0.5))
        delay *= 2
    raise TimeoutError(
        f"TCPStore: cannot connect to {host}:{port} after {attempt} "
        f"attempt(s) within {timeout}s"
        + (f" (last error: {last_exc!r})" if last_exc is not None else ""))


def _lib():
    lib = load_library("tcp_store")
    if lib is None:
        return None
    lib.ts_server_start.restype = ctypes.c_void_p
    lib.ts_server_start.argtypes = [ctypes.c_int, ctypes.POINTER(ctypes.c_int)]
    lib.ts_server_stop.argtypes = [ctypes.c_void_p]
    lib.ts_client_connect.restype = ctypes.c_void_p
    lib.ts_client_connect.argtypes = [ctypes.c_char_p, ctypes.c_int, ctypes.c_int]
    lib.ts_client_free.argtypes = [ctypes.c_void_p]
    lib.ts_set.restype = ctypes.c_int
    lib.ts_set.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p,
                           ctypes.c_int]
    lib.ts_get.restype = ctypes.c_int
    lib.ts_get.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p,
                           ctypes.c_int, ctypes.POINTER(ctypes.c_int), ctypes.c_int]
    lib.ts_add.restype = ctypes.c_int64
    lib.ts_add.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64]
    lib.ts_wait.restype = ctypes.c_int
    lib.ts_wait.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64]
    lib.ts_num_keys.restype = ctypes.c_int64
    lib.ts_num_keys.argtypes = [ctypes.c_void_p]
    lib.ts_delete.restype = ctypes.c_int
    lib.ts_delete.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.ts_list_prefix.restype = ctypes.c_int
    lib.ts_list_prefix.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p,
                                   ctypes.c_int, ctypes.POINTER(ctypes.c_int)]
    return lib


class TCPStore(_StoreOps):
    """paddle.distributed.TCPStore parity: TCPStore(host, port, is_master,
    world_size, timeout)."""

    def __init__(self, host: str = "127.0.0.1", port: int = 0,
                 is_master: bool = False, world_size: int = 1,
                 timeout: float = _DEFAULT_TIMEOUT):
        self.host = host
        self.is_master = is_master
        self.world_size = world_size
        self.timeout = timeout
        self._server = None
        self._py_server = None
        lib = _lib()
        self._lib = lib
        if lib is not None:
            if is_master:
                got = ctypes.c_int(0)
                self._server = lib.ts_server_start(port, ctypes.byref(got))
                if not self._server:
                    raise RuntimeError(f"TCPStore: cannot bind port {port}")
                port = got.value
            self.port = port
            self._client = _connect_with_retry(
                lambda t: lib.ts_client_connect(
                    host.encode(), port, int(t * 1000)) or None,
                host, port, timeout)
        else:
            from . import _py_store

            if is_master:
                self._py_server = _py_store.PyStoreServer(port)
                port = self._py_server.port
            self.port = port
            self._client = _connect_with_retry(
                lambda t: _py_store.PyStoreClient(host, port, t),
                host, port, timeout)

    # ---- API (reference tcp_store.h: set/get/wait/add) ----
    def set(self, key: str, value) -> None:
        data = value if isinstance(value, bytes) else str(value).encode()
        if self._lib is not None:
            rc = self._lib.ts_set(self._client, key.encode(), data, len(data))
            if rc != 0:
                raise RuntimeError(f"TCPStore.set({key!r}) failed rc={rc}")
        else:
            self._client.set(key, data)

    def get(self, key: str, wait: bool = True) -> bytes:
        if self._lib is None:
            return self._client.get(key, wait,
                                    timeout=self.timeout if wait else 0.0)
        if wait:
            # wait+get (rather than the server's blocking kGet) so the store's
            # timeout applies — a never-set key raises instead of hanging the job
            self.wait([key])
        cap = 1 << 16
        while True:
            buf = ctypes.create_string_buffer(cap)
            needed = ctypes.c_int(0)
            rc = self._lib.ts_get(self._client, key.encode(), buf, cap,
                                  ctypes.byref(needed), 1)
            if rc >= 0:
                return buf.raw[:rc]
            if rc == -28:  # -ENOSPC: grow the buffer and retry
                cap = max(cap * 2, needed.value)
                continue
            if rc == -2:  # -ENOENT (nowait miss)
                raise KeyError(key)
            raise RuntimeError(f"TCPStore.get({key!r}) failed rc={rc}")

    def add(self, key: str, amount: int = 1) -> int:
        if self._lib is None:
            return self._client.add(key, amount)
        v = self._lib.ts_add(self._client, key.encode(), amount)
        if v == -(2 ** 63):
            raise RuntimeError(f"TCPStore.add({key!r}) failed")
        return int(v)

    def wait(self, keys, timeout: Optional[float] = None) -> None:
        if isinstance(keys, str):
            keys = [keys]
        tmo = self.timeout if timeout is None else timeout
        deadline = time.monotonic() + tmo
        for key in keys:
            remaining_ms = int(max(0.0, deadline - time.monotonic()) * 1000)
            if self._lib is None:
                self._client.wait(key, remaining_ms / 1000.0)
                continue
            rc = self._lib.ts_wait(self._client, key.encode(), remaining_ms)
            if rc == -1:
                raise TimeoutError(f"TCPStore.wait({key!r}): timed out after {tmo}s")
            if rc < -1:
                raise RuntimeError(f"TCPStore.wait({key!r}) failed rc={rc}")

    def num_keys(self) -> int:
        if self._lib is None:
            return self._client.num_keys()
        return int(self._lib.ts_num_keys(self._client))

    def delete_key(self, key: str) -> bool:
        if self._lib is None:
            return self._client.delete(key)
        return self._lib.ts_delete(self._client, key.encode()) > 0

    def list_keys(self, prefix: str = "") -> List[str]:
        """Keys with the given prefix (used by the elastic membership registry)."""
        if self._lib is None:
            return self._client.list_prefix(prefix)
        cap = 1 << 16
        while True:
            buf = ctypes.create_string_buffer(cap)
            needed = ctypes.c_int(0)
            rc = self._lib.ts_list_prefix(self._client, prefix.encode(), buf, cap,
                                          ctypes.byref(needed))
            if rc >= 0:
                raw = buf.raw[:rc].decode()
                return [k for k in raw.split("\n") if k]
            if rc == -28:
                cap = max(cap * 2, needed.value)
                continue
            raise RuntimeError(f"TCPStore.list_keys({prefix!r}) failed rc={rc}")

    def __del__(self):
        try:
            if getattr(self, "_lib", None) is not None:
                if getattr(self, "_client", None):
                    self._lib.ts_client_free(self._client)
                    self._client = None
                if getattr(self, "_server", None):
                    self._lib.ts_server_stop(self._server)
                    self._server = None
            elif getattr(self, "_py_server", None) is not None:
                self._py_server.stop()
                self._py_server = None
        except Exception:
            pass


class FileStore(_StoreOps):
    """Single-host fallback store over a shared directory (reference has a
    libuv-free file store for tests). Full TCPStore API parity — bounded
    ``wait``/``get`` timeouts, ``delete_key``/``list_keys``/``num_keys``,
    the generation-scoped ``barrier``/``gc_generation`` helpers — so the
    elastic membership coordinator runs on either backend, and multi-agent
    tests can rendezvous through a tmpdir instead of a socket."""

    def __init__(self, path: str, world_size: int = 1,
                 timeout: float = _DEFAULT_TIMEOUT):
        self.path = path
        self.world_size = world_size
        self.timeout = timeout
        os.makedirs(path, exist_ok=True)

    _LOCK = ".lock"

    def _p(self, key: str) -> str:
        return os.path.join(self.path, key.replace("/", "%2F"))

    def set(self, key: str, value) -> None:
        data = value if isinstance(value, bytes) else str(value).encode()
        tmp = self._p(key) + f".tmp.{os.getpid()}"
        with open(tmp, "wb") as f:
            f.write(data)
        os.replace(tmp, self._p(key))

    def get(self, key: str, wait: bool = True,
            timeout: Optional[float] = None) -> bytes:
        tmo = self.timeout if timeout is None else timeout
        deadline = time.monotonic() + tmo
        while True:
            try:
                with open(self._p(key), "rb") as f:
                    return f.read()
            except FileNotFoundError:
                if not wait:
                    raise KeyError(key) from None
                if time.monotonic() > deadline:
                    raise TimeoutError(
                        f"FileStore.get({key!r}): not set within {tmo}s"
                    ) from None
                time.sleep(0.02)

    def add(self, key: str, amount: int = 1) -> int:
        import fcntl

        lockp = os.path.join(self.path, self._LOCK)
        with open(lockp, "w") as lf:
            fcntl.flock(lf, fcntl.LOCK_EX)
            try:
                cur = int(self.get(key, wait=False))
            except KeyError:
                cur = 0
            new = cur + amount
            self.set(key, str(new))
            return new

    def wait(self, keys, timeout: Optional[float] = None) -> None:
        """Block until every key exists; raises TimeoutError past the bound
        (the store timeout by default) instead of hanging the caller — the
        same contract as TCPStore.wait."""
        if isinstance(keys, str):
            keys = [keys]
        tmo = self.timeout if timeout is None else timeout
        deadline = time.monotonic() + tmo
        for k in keys:
            self.get(k, wait=True,
                     timeout=max(0.0, deadline - time.monotonic()))

    def delete_key(self, key: str) -> bool:
        try:
            os.remove(self._p(key))
            return True
        except FileNotFoundError:
            return False

    def list_keys(self, prefix: str = "") -> List[str]:
        """Keys with the given prefix (used by the elastic membership
        registry). Internal lock/tmp files are invisible by construction."""
        out = []
        try:
            names = os.listdir(self.path)
        except OSError:
            return out
        for name in names:
            if name == self._LOCK or ".tmp." in name:
                continue
            key = name.replace("%2F", "/")
            if key.startswith(prefix):
                out.append(key)
        return sorted(out)

    def num_keys(self) -> int:
        return len(self.list_keys())
