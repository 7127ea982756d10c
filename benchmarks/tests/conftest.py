"""Bootstrap for the benchmark's own tests, before jax is imported: CPU
platform, four virtual devices, persistent compile cache off (cache-served
multi-device CPU programs are not deterministic on this jax).

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
if "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4"
                               ).strip()
os.environ["FLAGS_compile_cache_dir"] = ""
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _fresh_topology():
    """No test inherits another's fleet topology."""
    from paddle_tpu.distributed import fleet as fleet_mod
    from paddle_tpu.distributed.mesh import set_hybrid_communicate_group

    set_hybrid_communicate_group(None)
    fleet_mod.fleet.__init__()
    yield
