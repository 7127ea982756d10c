"""Kernel autotune cache (core/autotune.py + incubate.autotune surface).

Reference analogue: phi AlgorithmsCache / switch_autotune step-window tests.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core import autotune


def setup_function(_):
    # isolate: fresh cache + disabled config per test
    autotune._cache = autotune.AlgorithmsCache()
    autotune._config["kernel"] = {"enable": False, "tuning_range": [1, 10]}
    autotune._config["cache_path"] = None
    autotune._step = 0


def test_cache_hit_miss_stats():
    c = autotune.AlgorithmsCache()
    assert c.get("k", (1, 2)) is None
    c.put("k", (1, 2), (512, 256))
    assert c.get("k", (1, 2)) == (512, 256)
    assert c.hits == 1 and c.misses == 1
    assert 0.0 < c.cache_hit_rate() < 1.0
    assert c.size() == 1


def test_pick_measures_and_caches():
    autotune.set_config({"kernel": {"enable": True}})
    calls = []

    def run(c):
        calls.append(c)
        if c == "slow":
            import time
            time.sleep(0.02)

    best = autotune.pick("dummy", ("shape",), ["slow", "fast"], run)
    assert best == "fast"
    assert calls.count("slow") == 2 and calls.count("fast") == 2  # warmup+timed
    # second call: cache hit, no re-measurement
    calls.clear()
    assert autotune.pick("dummy", ("shape",), ["slow", "fast"], run) == "fast"
    assert not calls


def test_pick_disabled_returns_default():
    out = autotune.pick("dummy", ("k",), [1, 2, 3], lambda c: None, default=2)
    assert out == 2
    assert autotune.cache().size() == 0  # nothing cached when off


def test_tuning_window_closes():
    autotune.set_config({"kernel": {"enable": True, "tuning_range": [1, 3]}})
    autotune.set_step(5)  # outside [1, 3)
    out = autotune.pick("dummy", ("k",), [1, 2], lambda c: None, default=2)
    assert out == 2 and autotune.cache().size() == 0
    autotune.set_step(2)  # inside window
    out = autotune.pick("dummy", ("k",), [1, 2], lambda c: None)
    assert autotune.cache().size() == 1


def test_failing_candidate_skipped():
    autotune.set_config({"kernel": {"enable": True}})

    def run(c):
        if c == "broken":
            raise RuntimeError("compile failed")

    assert autotune.pick("dummy", ("k",), ["broken", "ok"], run) == "ok"


def test_persistence_roundtrip(tmp_path):
    path = str(tmp_path / "autotune.json")
    c = autotune.AlgorithmsCache()
    c.put("flash_attention", (96, 1024, 1024), (512, 512))
    c.save(path)
    c2 = autotune.AlgorithmsCache()
    c2.load(path)
    assert c2.get("flash_attention", (96, 1024, 1024)) == (512, 512)


@pytest.mark.parametrize("head_dim", [32, 64])   # legacy, packed kernels
def test_flash_attention_uses_tuned_blocks(head_dim):
    """End-to-end: tuning picks a block pair and the kernel still matches the
    dense reference (CPU interpret mode; timing is meaningless there but the
    mechanism must produce a valid, cached choice), forward and backward —
    the tuned pair binds every kernel of the path."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    autotune.set_config({"kernel": {"enable": True}})
    rng = np.random.RandomState(0)
    q, k, v = [jnp.asarray(rng.randn(1, 256, 2, head_dim).astype(np.float32))
               for _ in range(3)]
    out = flash_attention(q, k, v, causal=True)
    assert autotune.cache().size() == 1
    (choice,) = [vv for sub in autotune.cache()._map.values() for vv in sub.values()]
    assert tuple(choice)[0] in (128, 256) and tuple(choice)[1] in (128, 256)

    def dense(q, k, v):
        qt, kt, vt = [jnp.swapaxes(x, 1, 2) for x in (q, k, v)]
        s = jnp.einsum("bhqd,bhkd->bhqk", qt, kt) / np.sqrt(head_dim)
        m = jnp.tril(jnp.ones(s.shape[-2:], bool))
        p = jax.nn.softmax(jnp.where(m, s, -1e30), axis=-1)
        return jnp.swapaxes(jnp.einsum("bhqk,bhkd->bhqd", p, vt), 1, 2)

    np.testing.assert_allclose(out, dense(q, k, v), atol=2e-5)
    # a second call reads the cached pair, and the backward takes it too
    got = jax.grad(lambda q: flash_attention(q, k, v, causal=True).sum())(q)
    want = jax.grad(lambda q: dense(q, k, v).sum())(q)
    np.testing.assert_allclose(got, want, atol=5e-5)
    assert autotune.cache().size() == 1


def test_incubate_surface():
    paddle.incubate.autotune.set_config({"kernel": {"enable": True}})
    assert autotune.enabled()
    stats = paddle.incubate.autotune.kernel_cache()
    assert hasattr(stats, "cache_hit_rate")
