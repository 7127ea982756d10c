"""Tracing-hazard source linter (paddle_tpu/analysis/source_lint.py).

Per-rule fixtures (each seeded hazard caught by exactly its rule, clean
twins stay clean), the scoped-tracedness regression (a public method
sharing a name with an inner jitted closure must NOT inherit its
tracedness — the false positive the first repo run surfaced), both
burn-down directions of the baseline comparison, the tier-1 repo-wide
gate against tools/lint_tracing_baseline.txt, and the
tools/lint_tracing.py CLI exit codes.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from paddle_tpu.analysis.source_lint import (compare_to_baseline,
                                             lint_source, lint_tree,
                                             load_baseline)

_REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
_BASELINE = os.path.join(_REPO, "tools", "lint_tracing_baseline.txt")


def _rules(src, relpath="paddle_tpu/x.py", **kw):
    return [(f.rule, f.token) for f in
            lint_source(textwrap.dedent(src), relpath, **kw)]


# ----------------------------------------------------------- rule fixtures

def test_host_sync_in_decorator_jitted_body():
    src = """
    import jax

    @jax.jit
    def step(x):
        lr = float(x.mean())
        return x * lr
    """
    assert _rules(src) == [("host-sync", "float")]


def test_host_sync_item_and_np_asarray_in_name_traced_body():
    """The name-passed-to-jit form: `jax.jit(step)` marks `step` traced."""
    src = """
    import jax
    import numpy as np

    def step(x):
        y = x.mean().item()
        z = np.asarray(x)
        return y, z

    fast = jax.jit(step)
    """
    assert _rules(src) == [("host-sync", ".item"),
                           ("host-sync", "np.asarray")]


def test_host_sync_via_scan_body_and_nested_fn():
    """lax.scan(body, ...) traces `body`, and functions nested inside a
    traced one are traced too."""
    src = """
    from jax import lax

    def body(carry, x):
        def inner(v):
            return int(v)
        return carry, inner(x)

    out = lax.scan(body, 0, xs)
    """
    assert _rules(src) == [("host-sync", "int")]


def test_float_of_literal_not_flagged():
    src = """
    import jax

    @jax.jit
    def step(x):
        return x * float(1e-3) + int("8")
    """
    assert _rules(src) == []


def test_untraced_code_may_sync_freely():
    src = """
    def report(x):
        return float(x.mean())
    """
    assert _rules(src) == []


def test_host_time_and_random_in_traced_body():
    src = """
    import time, random
    import numpy as np
    import jax

    @jax.jit
    def step(x):
        t = time.perf_counter()
        r = random.random()
        n = np.random.randn()
        return x + t + r + n
    """
    assert _rules(src) == [("host-time", "time.perf_counter"),
                           ("host-random", "random.random"),
                           ("host-random", "np.random.randn")]


def test_jax_random_is_not_host_random():
    src = """
    import jax

    @jax.jit
    def step(x, key):
        return x + jax.random.normal(key, x.shape)
    """
    assert _rules(src) == []


def test_mutable_default_in_public_api_only():
    src = """
    def submit(x, queue=[]):
        queue.append(x)
        return queue

    def _internal(x, acc={}):
        return acc
    """
    assert _rules(src) == [("mutable-default", "queue")]
    # non-library files (tests/, scripts) are exempt from the API rule
    assert _rules(src, relpath="tests/x.py") == []


def test_bare_lock_flagged_with_statement_clean():
    src = """
    import threading

    _lock = threading.Lock()

    def bad():
        _lock.acquire()
        try:
            pass
        finally:
            _lock.release()

    def good():
        with _lock:
            pass
    """
    assert _rules(src) == [("bare-lock", "_lock.acquire")]


def test_scoped_tracedness_regression():
    """THE false positive from the first repo-wide run: a class's public
    `step` method dispatches a jitted inner closure also named `step`.
    Only the closure is traced; the method may sync/time freely."""
    src = """
    import time
    import jax

    class Engine:
        def _build(self):
            def step(params, x):
                return params, x * 2
            return jax.jit(step)

        def step(self, x):
            t0 = time.perf_counter()
            out = self._build()(self.params, x)
            return float(out[1].mean()), time.perf_counter() - t0
    """
    assert _rules(src) == []


def test_parse_error_is_a_finding_not_a_crash():
    fs = lint_source("def broken(:\n", "paddle_tpu/x.py")
    assert [f.rule for f in fs] == ["parse-error"]


# --------------------------------------------------------------- baseline

def test_baseline_burns_down_both_directions(tmp_path):
    src = """
    import jax

    @jax.jit
    def step(x):
        return float(x)
    """
    findings = lint_source(textwrap.dedent(src), "paddle_tpu/x.py")
    key = findings[0].key
    assert key == "paddle_tpu/x.py:host-sync:step:float"

    # not baselined -> new
    new, stale = compare_to_baseline(findings, {})
    assert [f.key for f in new] == [key] and stale == []
    # baselined with justification -> accepted
    p = tmp_path / "baseline.txt"
    p.write_text(f"# comment\n\n{key}  # deliberate: startup probe\n")
    bl = load_baseline(str(p))
    assert bl == {key: "deliberate: startup probe"}
    new, stale = compare_to_baseline(findings, bl)
    assert new == [] and stale == []
    # finding fixed but line kept -> stale (paid-off debt must be deleted)
    new, stale = compare_to_baseline([], bl)
    assert new == [] and stale == [key]


def test_missing_baseline_file_is_empty():
    assert load_baseline("/nonexistent/baseline.txt") == {}


# ------------------------------------------------------- tier-1 repo gate

def test_repo_tree_lints_clean_against_baseline():
    """The satellite-2 acceptance, kept green forever: every hazard the
    linter finds across paddle_tpu/ + tools/ is either fixed or justified
    in tools/lint_tracing_baseline.txt — and nothing in the baseline is
    stale. On failure: fix the new finding (preferred) or add its key with
    a `# justification`, and delete any stale line."""
    findings = lint_tree(_REPO)
    baseline = load_baseline(_BASELINE)
    new, stale = compare_to_baseline(findings, baseline)
    msg = ["tracing-hazard lint drifted from tools/lint_tracing_baseline.txt:"]
    msg += [f"  NEW {f}" for f in new]
    msg += [f"  STALE (finding fixed — delete the line): {k}" for k in stale]
    assert not new and not stale, "\n".join(msg)


def test_lint_tracing_cli_exit_codes(tmp_path):
    """0 = clean vs baseline; 1 = drift (forced via an empty --root with a
    fabricated baseline, which makes every entry stale)."""
    tool = os.path.join(_REPO, "tools", "lint_tracing.py")
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}

    clean = subprocess.run([sys.executable, tool], capture_output=True,
                           text=True, env=env)
    assert clean.returncode == 0, clean.stdout + clean.stderr
    summary = json.loads(clean.stdout.strip().splitlines()[-1])["summary"]
    assert summary["kind"] == "lint_tracing" and summary["ok"]

    (tmp_path / "empty").mkdir()
    fake = tmp_path / "baseline.txt"
    fake.write_text("gone.py:host-sync:f:float\n")
    drift = subprocess.run(
        [sys.executable, tool, "--root", str(tmp_path / "empty"),
         "--baseline", str(fake)],
        capture_output=True, text=True, env=env)
    assert drift.returncode == 1, drift.stdout + drift.stderr
    summary = json.loads(drift.stdout.strip().splitlines()[-1])["summary"]
    assert not summary["ok"]
    assert summary["stale"] == ["gone.py:host-sync:f:float"]


# ------------------------------------------- the models' seam (PR 30)

_SEAM_CASES = {
    # what the parent's models/gpt.py and models/afmoe.py did
    "relative-import": ("""
        def kv_cache_spec(self, n):
            from ..serving.kv_state import KVLayerSpec
            return [KVLayerSpec("full", n, 4, 32)]
        """, [("model-serving", "serving.kv_state")]),
    "relative-import-of-the-package": ("""
        from .. import serving
        """, [("model-serving", "serving")]),
    "absolute-import": ("""
        import paddle_tpu.serving.kv_pages as kvp
        from paddle_tpu.serving import kv_pages
        """, [("model-serving", "paddle_tpu.serving.kv_pages"),
              ("model-serving", "paddle_tpu.serving")]),
    "at-set-on-a-cache": ("""
        def forward(self, k, kc, cache, rows, pos):
            kc = kc.at[rows, pos].set(k)
            cache.k_pool = cache.k_pool.at[rows, pos].set(k)
            return kc
        """, [("model-cache", "kc.at["), ("model-cache", "k_pool.at[")]),
    "dynamic-update-slice-on-a-cache": ("""
        import jax
        def forward(self, k, v_cache, off):
            return jax.lax.dynamic_update_slice(v_cache, k, (0, off, 0, 0))
        """, [("model-cache", "v_cache in dynamic_update_slice(")]),
    # the rule reads the construct, not the variable's name
    "a-cache-by-any-other-name": ("""
        import jax
        def forward(self, k, state, buf, off):
            buf = buf.at[:, off].set(k)
            self.rows().at[off].set(k)
            return jax.lax.dynamic_update_slice(state.rows, k, (0, off, 0, 0))
        """, [("model-cache", "buf.at["), ("model-cache", "<expr>.at["),
              ("model-cache", "rows in dynamic_update_slice(")]),
    # what a model may do
    "the-handle-and-other-packages": ("""
        import jax.numpy as jnp
        from ..nn.kv_cache import ChunkKV, KVLayerSpec
        from ..core.bucketing import resolve_bucket
        def step(toks, beam_idx, cache, k, v):
            toks = jnp.take_along_axis(toks, beam_idx[..., None], axis=1)
            return cache.update(k, v)
        """, []),
}


@pytest.mark.parametrize("case", sorted(_SEAM_CASES))
def test_models_neither_import_serving_nor_write_a_cache(case):
    src, want = _SEAM_CASES[case]
    assert _rules(src, "paddle_tpu/models/x.py") == want
    # the same text anywhere else is not the models' business
    assert _rules(src, "paddle_tpu/serving/x.py") == []
