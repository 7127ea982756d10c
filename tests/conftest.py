"""Test bootstrap, before the first jax import: the suite runs on the CPU
platform over an 8-device virtual mesh, so multi-chip sharding tests need no
TPU hardware (SURVEY.md §4 test pyramid, level 2)."""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
if "--xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=8").strip()
# The persistent compile cache is on by default (core/compile_cache.py); the
# suite keeps it OFF, child processes included: cache-served multi-device CPU
# executables are nondeterministic on this jax, and bit-equality is what
# many tests pin. Tests of the cache itself turn it on in their own scope.
os.environ["FLAGS_compile_cache_dir"] = ""
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)


import sys

import pytest

# jax tracing is deeply recursive (export -> grad of custom_vjp -> pallas
# index-map traces nest hundreds of frames) and pytest adds its own stack on
# top; the lm_loss Mosaic-export gate sat within ~100 frames of CPython's
# default 1000 and tipped over. Match the reference's posture of configuring
# interpreter limits for the test run (its dy2static tests raise the limit
# for AST recursion the same way).
if sys.getrecursionlimit() < 3000:
    sys.setrecursionlimit(3000)


@pytest.fixture(autouse=True)
def _isolate_global_state():
    """Reset process-wide state before every test (VERDICT r2 #6).

    Tests previously leaked HCG topology, FLAGS values, the global RNG, and
    the default float dtype into later tests, making the suite
    order-dependent (test_engine_fit_with_mp_annotations failed only in the
    full run). Mirrors the reference's per-test scope guard
    (`test/legacy_test/op_test.py` fresh-scope-per-test discipline).
    """
    import paddle_tpu as paddle
    from paddle_tpu.core import dtype as _dtype, flags as _flags
    from paddle_tpu.distributed import fleet as _fleet_mod
    from paddle_tpu.distributed.mesh import set_hybrid_communicate_group

    set_hybrid_communicate_group(None)
    # the Fleet singleton caches _hcg/_strategy/_is_initialized independently
    # of the global HCG — reset it too or fleet-lazy-init tests inherit the
    # previous test's topology
    _fleet_mod.fleet.__init__()
    # restore flags to their bootstrap values through set_flags so value-keyed
    # caches (dispatch rule cache) are invalidated, never silently stale
    snap = dict(_FLAG_SNAPSHOT)
    changed = {k: v for k, v in snap.items() if _flags._REGISTRY.get(k) != v}
    if changed:
        _flags.set_flags(changed)
    # the restore itself must not count as "explicitly set" (flags.was_set)
    _flags._explicitly_set.clear()
    _flags._explicitly_set.update(_EXPLICIT_SNAPSHOT)
    _dtype._default_float_dtype = _dtype.float32
    paddle.seed(0)
    yield


def pytest_collection_modifyitems(config, items):
    # PADDLE_TPU_TEST_SHUFFLE=<seed> runs the suite in a seeded random order
    # to prove order-independence (VERDICT r2 #6 acceptance).
    shuf = os.environ.get("PADDLE_TPU_TEST_SHUFFLE")
    if shuf:
        import random

        random.Random(int(shuf)).shuffle(items)


def pytest_configure(config):
    from paddle_tpu.core import flags as _flags

    global _FLAG_SNAPSHOT, _EXPLICIT_SNAPSHOT
    _FLAG_SNAPSHOT = dict(_flags._REGISTRY)
    _EXPLICIT_SNAPSHOT = frozenset(_flags._explicitly_set)
    # fast subset for 1-core bench boxes (README "Testing"):
    #   python -m pytest tests -m "not slow" -q     (~ minutes)
    # full suite spawns subprocess clusters and e2e training runs (~20 min).
    config.addinivalue_line(
        "markers", "slow: subprocess-cluster / end-to-end tests; deselect "
        "with -m 'not slow' on constrained machines")
