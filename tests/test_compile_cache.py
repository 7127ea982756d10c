"""Persistent compilation cache (core/compile_cache.py): a second process
must NOT pay XLA compile cost for a step program the first process already
compiled.

The cross-process claim is the whole point, so the core test runs two real
subprocesses against one cache dir — placed from outside through
JAX_COMPILATION_CACHE_DIR — and compares the engine's measured compile wall
time: process 2's step compile must be classified WARM (served from the
store) and take a small fraction of process 1's COLD compile. Where the
cache goes by default, and that a directory given from outside is never set
in code, is pinned in tests/test_chip_rules.py; off is asserted in-process
here: the suite runs with FLAGS_compile_cache_dir="" (tests/conftest.py).
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROG = r"""
import json, os
import numpy as np
import paddle_tpu as paddle
from paddle_tpu.core import compile_cache, monitor
from paddle_tpu.distributed.engine import TrainStepEngine

paddle.seed(0)
net = paddle.nn.Sequential(paddle.nn.Linear(32, 64), paddle.nn.ReLU(),
                           paddle.nn.Linear(64, 8))
opt = paddle.optimizer.AdamW(learning_rate=0.01,
                             parameters=net.parameters())
eng = TrainStepEngine(net, opt, loss_fn=paddle.nn.CrossEntropyLoss())
rng = np.random.RandomState(0)
x = paddle.to_tensor(rng.randn(16, 32).astype(np.float32))
y = paddle.to_tensor(rng.randint(0, 8, (16,)).astype(np.int64))
loss = eng.step(x, y)
rep = monitor.registry().report()
print(json.dumps({
    "enabled": compile_cache.enabled(),
    "entries": compile_cache.entries(),
    "loss": repr(float(loss.item())),
    "compile_ms": rep["engine.jit_compile_ms"]["value"],
    "cold": rep.get("engine.compile_cold", {}).get("value", 0),
    "cold_ms": rep.get("engine.compile_cold_ms", {}).get("value", 0),
    "warm": rep.get("engine.compile_warm", {}).get("value", 0),
    "warm_ms": rep.get("engine.compile_warm_ms", {}).get("value", 0),
}))
"""


def _run(extra_env):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": REPO + os.pathsep + os.environ.get("PYTHONPATH", "")}
    env.pop("FLAGS_compile_cache_dir", None)
    env.update(extra_env)
    res = subprocess.run([sys.executable, "-c", _PROG], capture_output=True,
                         text=True, timeout=300, env=env, cwd=REPO)
    assert res.returncode == 0, (res.stdout[-2000:], res.stderr[-2000:])
    return json.loads(res.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_second_process_compiles_warm_and_fast(tmp_path):
    cache = str(tmp_path / "xla_cache")
    first = _run({"JAX_COMPILATION_CACHE_DIR": cache})
    assert first["enabled"] and first["entries"] > 0
    assert first["cold"] >= 1 and first["warm_ms"] == 0
    assert first["compile_ms"] > 0

    second = _run({"JAX_COMPILATION_CACHE_DIR": cache})
    assert second["warm"] >= 1 and second["cold"] == 0, second
    assert second["entries"] == first["entries"]  # nothing recompiled
    # "~0 ms": deserialization only. Generous bound for CI noise — the
    # real ratio is ~10x even for this toy program.
    assert second["compile_ms"] <= max(50, 0.5 * first["compile_ms"]), (
        f"second-process compile not served from the persistent cache: "
        f"{second['compile_ms']}ms vs cold {first['compile_ms']}ms")

    # cache on vs off is bit-identical
    plain = _run({"FLAGS_compile_cache_dir": ""})
    assert plain["loss"] == first["loss"] == second["loss"]
    assert not plain["enabled"] and plain["entries"] == -1
    assert plain["cold"] == 0 and plain["warm"] == 0  # unclassified when off


def test_off_touches_nothing(tmp_path, monkeypatch):
    import paddle_tpu  # noqa: F401  (import-time configure already ran)
    from paddle_tpu.core import compile_cache

    if compile_cache.enabled():
        pytest.skip("suite launched with a compile cache configured")
    import jax

    assert jax.config.jax_compilation_cache_dir in (None, "")
    assert compile_cache.entries() == -1
    assert compile_cache.note_compile(5, -1, -1) is None


def test_set_flags_configures_cache_in_process(tmp_path):
    """paddle.set_flags({'compile_cache_dir': d}) wires jax.config without a
    restart (the flag is also env-bootstrapped for new processes)."""
    import paddle_tpu as paddle
    from paddle_tpu.core import compile_cache

    if compile_cache.enabled():
        pytest.skip("suite launched with a compile cache configured")
    d = str(tmp_path / "cc")
    import jax

    try:
        paddle.set_flags({"compile_cache_dir": d})
        assert compile_cache.enabled()
        assert compile_cache.cache_dir() == d
        assert os.path.isdir(d)
        assert jax.config.jax_compilation_cache_dir == d
        f = jax.jit(lambda a: a * 2 + 1)
        f(jax.numpy.ones((8, 8))).block_until_ready()
        assert compile_cache.entries() >= 1
    finally:
        # disable through the real path: configure() unsets jax.config AND
        # drops jax's latched cache singleton (reset_cache). Anything less
        # leaks the cache into every later compile — cache-served
        # multi-device CPU executables are nondeterministic on this jax,
        # which is how this test once made test_dist_checkpoint flaky.
        paddle.set_flags({"compile_cache_dir": ""})
        assert not compile_cache.enabled()
        assert jax.config.jax_compilation_cache_dir in (None, "")
