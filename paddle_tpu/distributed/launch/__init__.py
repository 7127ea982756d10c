"""`python -m paddle_tpu.distributed.launch` — the distributed job launcher.

Reference: python/paddle/distributed/launch/ (collective controller at
controllers/collective.py:23, env contract PADDLE_TRAINER_ID /
PADDLE_TRAINERS_NUM / PADDLE_TRAINER_ENDPOINTS / PADDLE_CURRENT_ENDPOINT, master
rendezvous at controllers/master.py). TPU-native: one process per host
(multi-controller JAX) instead of one per GPU — `--nproc_per_node > 1` is
refused on a host with TPU chips and exists for CPU-platform jobs
(JAX_PLATFORMS=cpu: CPU-mesh simulation and tests); multi-node rendezvous goes
through the C++ TCPStore instead of HTTP/ETCD.
"""
from .main import launch, main  # noqa: F401
