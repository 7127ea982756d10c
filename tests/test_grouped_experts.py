"""The Pallas kernel of the routed experts' SwiGLU (ops/pallas/
grouped_experts.py) against the three `ragged_dot` calls it replaces, on the
CPU in interpret mode at small shapes; the rule that picks the path
(`takes_kernel`) at the three routed-expert cells' shapes; its two counters;
its backward; and a tiny SDAR `ServingEngine` with the kernel forced beside
the plain engine.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import SdarForCausalLM, sdar_tiny
from paddle_tpu.nn.layers.routed_experts import RoutedExperts
from paddle_tpu.observability import metrics
from paddle_tpu.ops.pallas import _common
from paddle_tpu.ops.pallas import grouped_experts as ge
from paddle_tpu.serving import ServingEngine

HIDDEN, WIDTH = 128, 256
CHUNK = 32          # rows a product here; the chip's is ge.CHUNK_ROWS


@pytest.fixture
def kernel(monkeypatch):
    """The kernel in interpret mode, in chunks of CHUNK rows."""
    monkeypatch.setattr(_common, "_target", lambda: "interpret")
    monkeypatch.setattr(ge, "CHUNK_ROWS", CHUNK)


def _count(form):
    return metrics.default_registry().counter("moe.calls." + form).value


def _inputs(sizes, rows, dtype, seed=0):
    count = len(sizes)
    k = jax.random.split(jax.random.key(seed), 4)

    def draw(key, shape, std):
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)

    return (draw(k[0], (rows, HIDDEN), 1.0),
            draw(k[1], (count, HIDDEN, WIDTH), 0.1),
            draw(k[2], (count, HIDDEN, WIDTH), 0.1),
            draw(k[3], (count, WIDTH, HIDDEN), 0.1),
            jnp.asarray(sizes, jnp.int32))


def _gap(a, b):
    a, b = (np.asarray(x, np.float32) for x in (a, b))
    return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-30))


# (sizes, rows, tiled): `tiled` streams the width in two tiles of 128
CASES = {
    "empty experts": ([5, 0, 20, 3, 0, 36], 64, False),
    "empty experts, tiled": ([0, 7, 0, 0, 25, 0, 32], 64, True),
    "one expert holds every row": ([0, 64, 0], 64, False),
    "sizes off the row tile": ([1, 15, 17, 2, 29], 64, False),
    "a group past one chunk": ([3, 70, 1, 22], 96, False),
    "a group past one chunk, tiled": ([41, 0, 50, 5], 96, True),
    "a held share, rows past the last group": ([3, 0, 9, 14], 64, False),
    "a held share, tiled": ([17, 2, 0], 64, True),
    "nothing held here": ([0, 0, 0], 32, False),
}


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_is_ragged_dot(kernel, monkeypatch, case, dtype, tol):
    """The kernel against `ragged_dot` and the SwiGLU between them. Rows
    past the last group (experts held elsewhere) are NaN in `xs`: they are
    never read into a held row. Nor are their output rows written, but for
    the tile the last group ends inside, whose rows past it are zeros (the
    interpreter fills what is never written with NaN; the chip's
    `ragged_dot` leaves those rows as it found them too). float32 to
    rounding; bf16 within a rounding (the plain path rounds `gate` and `up`
    before the SiLU, the kernel after)."""
    sizes, rows, tiled = CASES[case]
    if tiled:
        monkeypatch.setattr(ge, "STEP_BYTES", 3 * HIDDEN * 128 * 4)
        assert ge.tile_width(HIDDEN, WIDTH, 4) == 128
    xs, wg, wu, wd, sz = _inputs(sizes, rows, dtype)
    mine = sum(sizes)
    past = jnp.arange(rows)[:, None] >= mine
    want = ge.plain(xs, wg, wu, wd, sz)
    got = ge.swiglu(jnp.where(past, jnp.nan, xs), wg, wu, wd, sz)
    assert got.shape == want.shape == xs.shape
    assert got.dtype == want.dtype == dtype
    assert bool(jnp.isfinite(got[:mine]).all())
    assert _gap(got[:mine], want[:mine]) <= tol if mine else True
    tile_end = -(-mine // ge.ROW_TILE) * ge.ROW_TILE
    assert not bool(got[mine:tile_end].any())
    assert bool(jnp.isnan(got[tile_end:]).all())      # never written


def test_backward_is_the_plain_paths(kernel):
    xs, wg, wu, wd, sz = _inputs([9, 0, 30, 25], 64, jnp.float32)

    def loss(fn):
        return lambda *w: (fn(xs, *w, sz) ** 2).sum()

    got = jax.grad(loss(ge.swiglu), argnums=(0, 1, 2))(wg, wu, wd)
    want = jax.grad(loss(ge.plain), argnums=(0, 1, 2))(wg, wu, wd)
    for g, w in zip(got, want):
        assert _gap(g, w) <= 1e-4


# ------------------------------------------------- 2. who takes which path
# (rows x top_k, published experts, hidden, width) of each cell's program
SHAPES = {
    "sdar block step": ((256 * 8, 128, 2048, 768), True),
    "trinity decode": ((16 * 8, 128, 2048, 1024), False),
    "deepseek-v2 decode": ((64 * 6, 160, 5120, 1536), False),
}


@pytest.mark.parametrize("name", sorted(SHAPES))
def test_the_path_by_rows_an_expert(monkeypatch, name):
    shape, takes = SHAPES[name]
    monkeypatch.setattr(_common, "_target", lambda: "mosaic")
    assert ge.takes_kernel(*shape, jnp.bfloat16, jnp.bfloat16) is takes


def test_the_band_and_what_else_refuses(monkeypatch):
    monkeypatch.setattr(_common, "_target", lambda: "mosaic")
    lo, hi = ge.ROWS_AN_EXPERT
    ok = dict(hidden=2048, width=768, dtype=jnp.bfloat16,
              w_dtype=jnp.bfloat16)
    assert ge.takes_kernel(int(lo * 128), 128, **ok)
    assert ge.takes_kernel(int(hi * 128), 128, **ok)
    assert not ge.takes_kernel(int(lo * 128) - 16, 128, **ok)
    assert not ge.takes_kernel(int(hi * 128) + 16, 128, **ok)
    assert not ge.takes_kernel(2040, 128, **ok)                # rows % 16
    assert not ge.takes_kernel(2048, 128, **dict(ok, width=700))
    assert not ge.takes_kernel(2048, 128, **dict(ok, w_dtype=jnp.float32))
    devices = np.asarray(jax.devices()[:2])
    with _common.mesh_scope(jax.sharding.Mesh(devices, ("mp",))):
        assert not ge.takes_kernel(2048, 128, **ok)


def test_the_cpu_keeps_ragged_dot_unasked():
    assert _common._target() is None
    assert not ge.takes_kernel(2048, 128, 2048, 768, jnp.bfloat16,
                               jnp.bfloat16)


# ------------------------------------------------------ 3. the two counters
def _layer(first, count):
    paddle.seed(3)
    return RoutedExperts(HIDDEN, WIDTH, num_experts=8, top_k=2, first=first,
                         count=count, dtype="float32", init_std=0.1)


@pytest.mark.parametrize("share", [(0, 8), (2, 4)])
@pytest.mark.parametrize("forced", [False, True])
def test_counters_name_the_form_traced(kernel, monkeypatch, forced, share):
    """64 rows x 2 on 8 experts: 16 rows an expert, inside the band; the
    CPU (`_target` None) keeps `ragged_dot`, the kernel forced takes it, and
    both give the layer's result, a share held (experts 2 to 5) too: the
    combine never reads the rows the kernel leaves unwritten."""
    layer = _layer(*share)
    m = jax.random.normal(jax.random.key(1), (64, HIDDEN), jnp.float32)
    if not forced:
        monkeypatch.setattr(_common, "_target", lambda: None)
    before = _count("ragged_dot"), _count("grouped_kernel")
    out, load = layer.routed_load(m)
    after = _count("ragged_dot"), _count("grouped_kernel")
    assert (after[0] - before[0], after[1] - before[1]) == (
        (0, 1) if forced else (1, 0))
    monkeypatch.setattr(_common, "_target", lambda: None)
    want, _ = layer.routed_load(m)
    assert _gap(out, want) <= 1e-5
    assert int(load.sum()) == 128


# ------------------------------------------ 4. an engine, the kernel forced
def test_the_kernel_forced_gives_the_plain_engines_blocks(monkeypatch):
    """A tiny SDAR at widths of whole lanes, four slots: every block step and
    prefill of the forced engine takes the kernel (interpreted, the band
    opened to every shape), and its blocks are the plain engine's."""
    paddle.seed(6)
    model = SdarForCausalLM(sdar_tiny(hidden_size=128,
                                      moe_intermediate_size=128))
    model.eval()
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, 254, (n,)) for n in (5, 9, 12, 3)]

    def serve():
        eng = ServingEngine(model, slot_count=4, ladder=(8, 16),
                            max_seq_len=48, max_new_cap=8,
                            steps_per_dispatch=5)
        reqs = [eng.submit(p, max_new_tokens=8) for p in prompts]
        eng.run()
        assert all(r.done for r in reqs)
        return [r.tokens for r in reqs]

    plain = serve()
    monkeypatch.setattr(_common, "_target", lambda: "interpret")
    monkeypatch.setattr(ge, "CHUNK_ROWS", CHUNK)
    monkeypatch.setattr(ge, "ROWS_AN_EXPERT", (0.0, 1e9))
    before = _count("grouped_kernel")
    forced = serve()
    assert _count("grouped_kernel") - before >= 3     # a layer's calls
    assert forced == plain
