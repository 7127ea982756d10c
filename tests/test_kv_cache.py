"""Conformance of the cache handles (nn/kv_cache.py, serving/kv_state.py,
serving/kv_pages.py): whatever the layout, `update` returns exactly the rows
and positions a plain dict {(slot, position): row} holds.

One seeded sequence a kind, driven as the engine drives it (`cover`, `args`,
the traced half, `take`): requests prefilled into slots (one prompt longer
than the window), then single-token steps with an idle slot, a step a slot
sits out (its write must leave no trace), for the ring a context that wraps
twice, and for the pool a slot seated for replay on shared pages with another
slot's prefill dispatched before its first step (the shared row stays). After every `update` each query must see, under the one causal
test the models apply (`held <= position`, and the window's lower edge), the
positions the dict says it may see, each with the dict's row.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.nn.kv_cache import ChunkKV, KVLayerSpec
from paddle_tpu.serving.kv_pages import PagedSlotCache
from paddle_tpu.serving.kv_state import SlotCache

HEADS, DIM, SLOTS, T, WINDOW = 2, 4, 3, 32, 8


class Rows:
    """The reference: what each (slot, position) holds."""

    def __init__(self, window=None):
        self.held, self.window = {}, window

    def write(self, slot, first, k, v):
        for j in range(k.shape[0]):
            self.held[slot, first + j] = (k[j], v[j])

    def rewind(self, slot, n):
        self.held = {(s, p): r for (s, p), r in self.held.items()
                     if s != slot or p < n}

    def visible(self, slot, position):
        low = 0 if self.window is None else max(0, position - self.window + 1)
        return {p: self.held[slot, p] for p in range(low, position + 1)}


def chunk(rng, batch, s, exact):
    """New keys and values [batch, s, HEADS, DIM]; `exact` draws eighths,
    which bf16 and float32 both hold."""
    def draw():
        if exact:
            return rng.randint(-64, 65, (batch, s, HEADS, DIM)) / 8.0
        return rng.standard_normal((batch, s, HEADS, DIM))
    return draw().astype(np.float32), draw().astype(np.float32)


def check(rows, keys, values, held, positions, slots, tol):
    """Every query of `positions` [b, s] (row i of the batch is slot
    `slots[i]`, None: not checked) sees what `rows` says, no more."""
    keys, values = np.asarray(keys, np.float32), np.asarray(values, np.float32)
    held = np.broadcast_to(np.asarray(held),
                           positions.shape + (keys.shape[1],))
    for i, slot in enumerate(slots):
        if slot is None:
            continue
        for j, position in enumerate(positions[i]):
            want = rows.visible(slot, int(position))
            seen = held[i, j] <= position
            if rows.window is not None:
                seen &= held[i, j] > position - rows.window
            at = {int(held[i, j, t]): t for t in np.nonzero(seen)[0]}
            assert sorted(at) == sorted(want), (slot, position)
            for p, t in at.items():
                for got, ref in ((keys[i, t], want[p][0]),
                                 (values[i, t], want[p][1])):
                    bound = tol * np.abs(ref).max(-1, keepdims=True)
                    assert (np.abs(got - ref) <= bound).all(), (slot, p)


def test_chunk_cache_at_a_scalar_offset():
    """generate()'s kind: a prefill, steps, and a rewind to a shorter
    length (the bucketed prompt's pad rows go stale, then are rewritten)."""
    rng = np.random.RandomState(0)
    rows = Rows()
    cache = ChunkKV.zeros(2, 16, HEADS, DIM, jnp.float32)
    assert cache.fresh
    for s in (5, 1, 1):
        first = int(cache.offset)
        k, v = chunk(rng, 2, s, exact=True)
        positions = np.asarray(cache.positions(s))
        assert positions.tolist() == [list(range(first, first + s))]
        for b in range(2):
            rows.write(b, first, k[b], v[b])
        keys, values, held, cache = cache.update(jnp.asarray(k),
                                                 jnp.asarray(v))
        assert not cache.fresh
        check(rows, keys, values, held, np.repeat(positions, 2, 0), (0, 1), 0)
    cache = cache.rewound(jnp.int32(3))
    for b in range(2):
        rows.rewind(b, 3)
    k, v = chunk(rng, 2, 1, exact=True)
    for b in range(2):
        rows.write(b, 3, k[b], v[b])
    keys, values, held, cache = cache.update(jnp.asarray(k), jnp.asarray(v))
    check(rows, keys, values, held, np.full((2, 1), 3), (0, 1), 0)
    assert int(cache.offset) == 4


def _slot_cache(kind):
    if kind in ("full", "window"):
        rows = WINDOW if kind == "window" else T
        return SlotCache([KVLayerSpec(kind, rows, HEADS, DIM)], SLOTS, T,
                         jnp.float32)
    return PagedSlotCache([KVLayerSpec("full", T, HEADS, DIM)], SLOTS, T,
                          jnp.float32, 4, None, kind)


@pytest.mark.parametrize("kind", ["full", "window", "bf16", "int8"])
def test_slot_cache_holds_what_was_written(kind):
    rng = np.random.RandomState(1)
    kv = _slot_cache(kind)
    paged = isinstance(kv, PagedSlotCache)
    exact = kind != "int8"
    tol = 0 if exact else 0.51 / 127        # half a step of absmax / 127
    rows = Rows(WINDOW if kind == "window" else None)
    offsets = np.zeros(SLOTS, np.int32)

    def prefill(slot, bucket, length, base=0):
        at = (jnp.int32(base), jnp.int32(slot)) if paged \
            else (jnp.int32(slot),)
        assert len(at) == len(kv.prefill_at)
        kv.cover(np.arange(SLOTS) == slot, np.full(SLOTS, base),
                 np.full(SLOTS, base + length - 1))
        args = kv.args()
        (handle,) = kv.prefill_views(args, bucket, jnp.int32(length), *at)
        k, v = chunk(rng, 1, bucket, exact)        # the pad rows are junk
        rows.write(slot, base, k[0, :length], v[0, :length])
        positions = np.asarray(handle.positions(bucket))
        assert positions[0].tolist() == list(range(base, base + bucket))
        keys, values, held, handle = handle.update(jnp.asarray(k),
                                                   jnp.asarray(v))
        check(rows, keys, values, held, positions[:, :length], (slot,), tol)
        kv.take(kv.commit_prefill(args, [handle], jnp.int32(length), *at))
        offsets[slot] = base + length
        assert int(kv.first_position(length, *(int(a) for a in at))) \
            == base + length

    def step(active, replaying=()):
        active = np.asarray(active)
        kv.cover(active, offsets, offsets)
        args = kv.args()
        (handle,) = kv.views(args, kv.tip(jnp.asarray(offsets)),
                             jnp.asarray(active))
        k, v = chunk(rng, SLOTS, 1, exact)
        positions = np.asarray(handle.positions(1))
        assert positions[:, 0].tolist() == offsets.tolist()
        for slot in np.nonzero(active)[0]:
            if slot not in replaying:     # a replayed write leaves no trace
                rows.write(slot, int(offsets[slot]), k[slot], v[slot])
        keys, values, held, handle = handle.update(jnp.asarray(k),
                                                   jnp.asarray(v))
        check(rows, keys, values, held, positions,
              [s if active[s] else None for s in range(SLOTS)], tol)
        kv.take(kv.absorb(args, [handle], jnp.asarray(active)), active)
        offsets[active] += 1

    prefill(0, 8, 5)
    if paged:
        prefill(1, 8, 8)                  # the prefix, then its tail from a
        prefill(1, 8, 3, base=8)          # traced base: 11 in all
    else:
        prefill(1, 16, 11)                # longer than the window
    step([True, True, False])             # slot 2 idle throughout
    step([True, False, False])            # slot 1 sits one out: its write
    step([True, True, False])             # at 12 is overwritten here
    for _ in range(14):                   # slot 1 passes 24: the ring of 8
        step([True, True, False])         # has wrapped twice since 11
    assert offsets.tolist() == [22, 27, 0]
    step([False, True, False])            # slot 0 retired, slot 1 goes on
    kv.release(0)
    prefill(0, 8, 6)                      # and its slot is used again
    step([True, True, False])
    if paged:
        # a replay seat: slot 2 shares slot 1's first two pages (positions
        # 0..7) and re-derives position 7, and a prefill into another slot
        # is dispatched before its first step
        shared = [int(p) for p in kv.tables[1, :2]]
        for page in shared:
            kv.pool.incref(page)
        kv.tables[2, :2] = shared
        kv.slot_pages[2] = list(shared)
        kv.replay[2] = True
        offsets[2] = 7
        for p in range(8):
            rows.held[2, p] = rows.held[1, p]
        kv.release(0)
        prefill(0, 8, 4)
        assert kv.replay.tolist() == [False, False, True]
        step([False, True, True], replaying=(2,))  # both still see slot 1's
        assert not kv.replay.any()                 # row at 7; from 8 on slot
        step([False, True, True])                  # 2 writes pages of its own
        assert kv.tables[2, 2] not in (0, *shared)
    assert kv.nbytes() > 0
