"""Unified executable registry (core/exec_registry.py) + AOT warm start.

Four claims (ISSUE 18):
1. Keys are honest: any shape/dtype/mesh/flag variation is a distinct
   entry; the same key is a hit that rebuilds nothing.
2. LRU eviction never touches pinned entries — the serving engine pins
   every active executable, so FLAGS_decode_jit_cache_size=1 yields
   eviction REFUSALS, not a recompile storm (the latent hazard the
   registry migration fixed).
3. A precompiled engine serves token-identical output with ZERO dispatch
   compiles — the AOT fast path is the same executable the lazy path
   would have built.
4. The AOT bundle round-trips across processes: a fresh replica loading
   the bundle joins with engine.compile_cold == 0 while compile_warm
   grew (both-flat would just mean the cache was off) and serves
   bit-identical tokens. Multi-device CPU is probe-gated, not trusted.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from paddle_tpu.core.exec_registry import ExecutableRegistry  # noqa: E402


def _mk(tag, log=None):
    def build():
        if log is not None:
            log.append(tag)
        return lambda *a: tag
    return build


# ---- an entry's first call stands in one chunk of the frame stack -------


def _frames_above():
    names, f = [], sys._getframe(1)
    while f is not None:
        names.append((f.f_code.co_name, f.f_code.co_stacksize))
        f = f.f_back
    return names


def test_first_call_traces_and_lowers_in_one_stack_chunk():
    """CPython frees a 16 KiB chunk of a thread's frame stack when its first
    frame returns, so a loop on a chunk's edge maps memory with every call:
    jax's lowering loop did, or not, by the depth of whoever made an
    executable's first call (PERF.md, PR 32). The first call goes through a
    frame of 512 KiB, which opens one chunk for all frames below it; later
    calls, which only dispatch, do not pay for that mapping."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.core import exec_registry as X

    assert X._call_in_one_chunk.__code__.co_stacksize >= 1 << 16
    assert X._call_in_one_chunk(lambda a, b: a - b, (5, 3)) == 2

    seen = []

    def traced(x):
        seen.append(_frames_above())          # runs while jax traces
        return x + 1

    reg = ExecutableRegistry(name="t")
    entry = reg.get_or_build(("prog",), lambda: jax.jit(traced))
    assert int(entry(jnp.int32(1))) == 2
    assert reg.note_compiles(entry, wall_s=0.0) == 1
    assert any(name == "_call" and size >= 1 << 16 for name, size in seen[0])

    roomy = []
    entry.fn = lambda *a: roomy.append(_frames_above()) or 0
    entry(jnp.int32(1))
    assert not any(size >= 1 << 16 for _, size in roomy[0])


# ---- claim 1: key uniqueness -------------------------------------------


def test_key_uniqueness_across_shape_mesh_flag_variants():
    reg = ExecutableRegistry(name="t")
    built = []
    keys = [
        ("prog", (4, 8), "f32", ("dp", 2), False),
        ("prog", (4, 16), "f32", ("dp", 2), False),   # shape
        ("prog", (4, 8), "bf16", ("dp", 2), False),   # dtype
        ("prog", (4, 8), "f32", ("dp", 4), False),    # mesh degree
        ("prog", (4, 8), "f32", ("tp", 2), False),    # mesh axis
        ("prog", (4, 8), "f32", ("dp", 2), True),     # flag
        ("prog2", (4, 8), "f32", ("dp", 2), False),   # program id
    ]
    entries = [reg.get_or_build(k, _mk(i, built)) for i, k in enumerate(keys)]
    assert len(reg) == len(keys)
    assert len({id(e) for e in entries}) == len(keys)
    assert built == list(range(len(keys)))
    assert reg.misses == len(keys) and reg.hits == 0

    again = reg.get_or_build(keys[0], _mk("never", built))
    assert again is entries[0]
    assert reg.hits == 1 and built == list(range(len(keys)))  # no rebuild


def test_prefix_count_and_discard():
    reg = ExecutableRegistry(name="t")
    reg.get_or_build(("serve.prefill", 8), _mk(1))
    reg.get_or_build(("serve.prefill", 16), _mk(2))
    reg.get_or_build(("serve.decode", "greedy"), _mk(3))
    assert reg.count("serve.prefill") == 2
    assert reg.count("serve.decode") == 1
    reg.discard("serve.prefill")
    assert reg.count("serve.prefill") == 0 and len(reg) == 1
    assert reg.evictions == 0  # discard is invalidation, not LRU pressure


# ---- claim 2: LRU + pinned-entry semantics ------------------------------


def test_lru_evicts_oldest_unpinned_only():
    reg = ExecutableRegistry(name="t", capacity=2)
    reg.get_or_build(("a",), _mk(1), pin=True)
    reg.get_or_build(("b",), _mk(2))
    reg.get_or_build(("c",), _mk(3))   # over capacity: b goes, a is pinned
    assert ("a",) in reg and ("c",) in reg and ("b",) not in reg
    assert reg.evictions == 1

    reg.unpin(("a",))
    reg.get_or_build(("d",), _mk(4))   # now a is the oldest AND unpinned
    assert ("a",) not in reg and ("c",) in reg and ("d",) in reg
    assert reg.evictions == 2


def test_all_pinned_registry_refuses_eviction():
    reg = ExecutableRegistry(name="t", capacity=1)
    reg.get_or_build(("a",), _mk(1), pin=True)
    reg.get_or_build(("b",), _mk(2), pin=True)
    # over capacity but nothing evictable: refuse, never drop a pinned
    # executable out from under an active slot
    assert len(reg) == 2
    assert ("a",) in reg and ("b",) in reg
    assert reg.evictions == 0 and reg.evict_refusals >= 1


def test_pin_is_refcounted():
    reg = ExecutableRegistry(name="t", capacity=1)
    reg.get_or_build(("a",), _mk(1), pin=True)
    reg.pin(("a",))                    # second holder
    reg.unpin(("a",))                  # first releases: still pinned
    reg.get_or_build(("b",), _mk(2))
    assert ("a",) in reg
    reg.unpin(("a",))                  # last holder releases
    reg.get_or_build(("c",), _mk(3))
    assert ("a",) not in reg


def test_serving_cache_size_1_refuses_not_thrashes():
    """The eviction-hazard regression (ISSUE 18 satellite): with
    FLAGS_decode_jit_cache_size=1 the serving engine's 3+ pinned
    executables exceed capacity on every insert — the registry must
    refuse eviction (counters prove it) and the engine must keep serving
    correct tokens on the executables it already built."""
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTForPretraining, gpt_tiny
    from paddle_tpu.serving import ServingEngine

    paddle.seed(0)
    model = GPTForPretraining(gpt_tiny())
    model.eval()
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, model.config.vocab_size, (n,)).astype(np.int64)
               for n in (5, 12, 6)]

    def serve(eng):
        reqs = [eng.submit(p, max_new_tokens=3, temperature=0.0)
                for p in prompts]
        eng.run()
        return [list(r.tokens) for r in reqs]

    reference = serve(ServingEngine(model, slot_count=2, ladder=(8, 16),
                                    max_new_cap=4, max_seq_len=32,
                                    steps_per_dispatch=1))

    old = paddle.get_flags(["decode_jit_cache_size"])[
        "FLAGS_decode_jit_cache_size"]
    paddle.set_flags({"decode_jit_cache_size": 1})
    try:
        eng = ServingEngine(model, slot_count=2, ladder=(8, 16),
                            max_new_cap=4, max_seq_len=32,
                            steps_per_dispatch=1)
        tokens = serve(eng)
        reg = eng.exec_registry()
        # both prefill rungs + greedy decode live despite capacity 1
        assert len(reg) >= 3
        assert reg.evictions == 0, "evicted a pinned serving executable"
        assert reg.evict_refusals > 0
        assert tokens == reference
    finally:
        paddle.set_flags({"decode_jit_cache_size": old})


# ---- claim 3: precompile == lazy, token-identical, zero dispatch compiles


def _counter(name):
    from paddle_tpu.core import monitor

    return monitor.registry().report().get(name, {}).get("value", 0)


def _dispatch_compiles():
    return sum(_counter(f"serving.{k}_compiles")
               for k in ("prefill", "decode", "verify", "draft_prefill"))


def test_precompiled_engine_token_identical_zero_dispatch_compiles():
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTForPretraining, gpt_tiny
    from paddle_tpu.serving import ServingEngine

    paddle.seed(0)
    model = GPTForPretraining(gpt_tiny())
    model.eval()
    rng = np.random.RandomState(11)
    prompts = [rng.randint(0, model.config.vocab_size, (n,)).astype(np.int64)
               for n in (4, 7)]

    def serve(eng):
        reqs = [eng.submit(p, max_new_tokens=3, temperature=0.0)
                for p in prompts]
        eng.run()
        return [list(r.tokens) for r in reqs]

    kw = dict(slot_count=2, ladder=(8,), max_new_cap=4, max_seq_len=16,
              steps_per_dispatch=1)
    lazy_tokens = serve(ServingEngine(model, **kw))

    eng = ServingEngine(model, **kw)
    rep = eng.precompile(families=("greedy",))
    assert rep["skipped"] is None and rep["precompiled"] >= 2
    before = _dispatch_compiles()
    aot_tokens = serve(eng)
    assert _dispatch_compiles() == before, "precompiled dispatch compiled"
    assert aot_tokens == lazy_tokens
    assert eng.exec_registry().rollup()["aot_fallbacks"] == 0


def test_precompile_skips_on_probe_refusal(monkeypatch):
    import paddle_tpu as paddle
    from paddle_tpu.analysis import backend as _backend
    from paddle_tpu.models import GPTForPretraining, gpt_tiny
    from paddle_tpu.serving import ServingEngine

    paddle.seed(0)
    model = GPTForPretraining(gpt_tiny())
    model.eval()
    eng = ServingEngine(model, slot_count=1, ladder=(8,), max_new_cap=2,
                        max_seq_len=16, steps_per_dispatch=1)
    monkeypatch.setattr(_backend, "aot_serving_reason",
                        lambda device_count=None, platform=None:
                        "probe says no")
    rep = eng.precompile()
    assert rep == {"precompiled": 0, "skipped": "probe says no",
                   "cold": 0, "warm": 0, "wall_ms": 0.0}
    assert eng.aot_skip_reason == "probe says no"
    assert len(eng.exec_registry()) == 0  # nothing half-built

    rep2 = eng.precompile(families=("greedy",), force=True)
    assert rep2["skipped"] is None and rep2["precompiled"] >= 2
    assert eng.aot_skip_reason is None


# ---- claim 4: multi-device probe + cross-process bundle round trip ------


def test_aot_probe_gates_multi_device_cpu_only():
    from paddle_tpu.analysis.backend import (aot_serving_reason,
                                             backend_supports_aot_serving)

    assert aot_serving_reason(device_count=1, platform="cpu") is None
    assert aot_serving_reason(device_count=1, platform="tpu") is None
    assert aot_serving_reason(device_count=4, platform="tpu") is None
    reason = aot_serving_reason(device_count=4, platform="cpu")
    assert reason is not None and "multi-device" in reason
    assert not backend_supports_aot_serving(device_count=4, platform="cpu")
    assert backend_supports_aot_serving(device_count=1, platform="cpu")


_SERVE_PROG = r"""
import json, sys
import numpy as np
sys.path.insert(0, "__TOOLS__")
import aot_bundle
from paddle_tpu.core import monitor

mode, bundle = sys.argv[1], sys.argv[2]
if mode == "build":
    manifest = aot_bundle.build_bundle(
        bundle, slots=1, ladder=(8,), max_new_cap=3, max_seq_len=16,
        steps_per_dispatch=1, seed=0, families=("greedy",))
    assert manifest["report"]["skipped"] is None, manifest
eng, rep = aot_bundle.load_engine(bundle)

def counter(name):
    return monitor.registry().report().get(name, {}).get("value", 0)

before = sum(counter(f"serving.{k}_compiles")
             for k in ("prefill", "decode", "verify", "draft_prefill"))
rng = np.random.RandomState(7)
reqs = [eng.submit(rng.randint(0, 50304, (n,)).astype(np.int64),
                   max_new_tokens=3, temperature=0.0) for n in (4, 6)]
eng.run()
after = sum(counter(f"serving.{k}_compiles")
            for k in ("prefill", "decode", "verify", "draft_prefill"))
print(json.dumps({
    "tokens": [list(map(int, r.tokens)) for r in reqs],
    "cold": rep["cold"], "warm": rep["warm"], "skipped": rep["skipped"],
    "dispatch_compiles": after - before,
    "monitor_cold": counter("engine.compile_cold"),
}))
"""


@pytest.mark.slow
def test_aot_bundle_round_trip_fresh_process(tmp_path):
    """Process 1 builds the bundle and serves; process 2 is the joining
    replica — same bundle, fresh interpreter. It must precompile all-warm
    (compile_cold == 0 AND compile_warm > 0: both-flat would just mean
    the cache never engaged), dispatch with zero compiles, and emit
    bit-identical tokens."""
    bundle = str(tmp_path / "bundle")
    prog = _SERVE_PROG.replace("__TOOLS__",
                               os.path.join(REPO, "tools"))
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": REPO + os.pathsep + os.environ.get(
               "PYTHONPATH", "")}

    def run(mode):
        res = subprocess.run([sys.executable, "-c", prog, mode, bundle],
                             capture_output=True, text=True, timeout=600,
                             env=env, cwd=REPO)
        assert res.returncode == 0, (res.stdout[-2000:], res.stderr[-2000:])
        return json.loads(res.stdout.strip().splitlines()[-1])

    first = run("build")
    assert first["skipped"] is None
    assert first["cold"] == 0 and first["warm"] > 0  # build_bundle compiled
    assert first["dispatch_compiles"] == 0

    second = run("join")
    assert second["skipped"] is None
    assert second["cold"] == 0 and second["monitor_cold"] == 0
    assert second["warm"] > 0
    assert second["dispatch_compiles"] == 0
    assert second["tokens"] == first["tokens"]  # bit-identical replica
