"""Paged KV cache: fixed-size pages + a block allocator + a per-slot page
table traced into the serving executables as an integer gather index.

The PR 4 engine pins one slot-contiguous ``[slots, max_seq_len, nh, hd]``
cache row per slot, so every request reserves worst-case bytes and no two
requests can share anything. The paged layout (vLLM's PagedAttention block
table, arXiv 2309.06180) breaks each sequence into ``page_tokens``-sized
pages drawn from one shared pool:

- **device state** (per layer): a page pool ``[num_pages, page_tokens, nh,
  hd]`` plus, for all layers at once, ONE page table ``[slots, max_pages]``
  of int32 pool indices. Both shapes are static, so the two-executable
  (bucketed prefill + single decode) design and buffer donation survive
  unchanged — the page table is just another traced integer operand.
- **read** = gather: ``pool[table]`` reassembles each slot's logical
  ``[max_pages * page_tokens, nh, hd]`` K/V, and the existing causal mask
  (``col <= query_pos``) makes everything past a slot's offset inert.
- **write** = scatter: token position ``p`` lands in page ``table[slot,
  p // page_tokens]`` at row ``p % page_tokens``.

Two pool pages are reserved:

- page 0 is the **zero page**: every unallocated page-table entry points
  here and it is never written, so gathering an unallocated region reads
  exact zeros — the same values a freshly zero-initialized contiguous
  cache holds, which is what makes paged attention bit-identical to the
  contiguous engine (masked columns contribute exp(-1e9) == 0.0 either
  way).
- page 1 is the **scratch page**: rows that must not write (idle slots,
  prefix-replay steps re-deriving an already-cached position) have their
  scatter redirected here. It is never read through any table.

Quantized pages (``FLAGS_kv_cache_dtype``): 'bf16' casts the pool;
'int8' stores EQuARX-style chunk-scaled int8 (grad_comm's absmax/127
scheme, PAPERS.md 2506.17615) with one f32 scale per (page, token, head),
dequantized inside the attention read.

Host side, :class:`PagePool` is a refcounting block allocator (free list +
LRU-evictable set of refcount-zero pages still referenced by the radix
prefix cache — see prefix_cache.py).
"""
from __future__ import annotations

from collections import OrderedDict, deque
from typing import List, Optional

from jax.tree_util import register_pytree_node_class

from .kv_state import refuse_latent_layers, refuse_state_layers

ZERO_PAGE = 0
SCRATCH_PAGE = 1
RESERVED_PAGES = 2


class PoolExhausted(RuntimeError):
    """No free page and nothing evictable — the pool is undersized for the
    admitted load (raise kv_num_pages or lower slot_count/max_new_cap)."""


class PagePool:
    """Host-side page accounting: a free list plus per-page refcounts.

    The pool tracks *references held by live slots* only — the prefix
    cache holds pages weakly (a refcount-0 page with a trie node parks in
    the LRU ``evictable`` set, still allocated, content preserved, until
    either re-matched or evicted to satisfy an allocation).
    """

    def __init__(self, num_pages: int):
        import numpy as np

        if num_pages < RESERVED_PAGES + 1:
            raise ValueError(f"num_pages must be > {RESERVED_PAGES}, "
                             f"got {num_pages}")
        self.num_pages = int(num_pages)
        self.free: deque = deque(range(RESERVED_PAGES, self.num_pages))
        self.ref = np.zeros(self.num_pages, np.int32)
        # page -> monotonic clock at last release (LRU eviction order);
        # maintained by the prefix cache via park()/unpark()
        self.evictable: "OrderedDict[int, int]" = OrderedDict()
        self.allocs = 0
        self.evictions = 0

    # -- capacity -------------------------------------------------------
    @property
    def free_count(self) -> int:
        return len(self.free)

    @property
    def available(self) -> int:
        """Pages an allocation could obtain (free + evictable-cached)."""
        return len(self.free) + len(self.evictable)

    @property
    def in_use(self) -> int:
        """Pages referenced by at least one live slot."""
        return int((self.ref > 0).sum())

    @property
    def cached(self) -> int:
        """Refcount-zero pages parked for prefix reuse."""
        return len(self.evictable)

    # -- alloc / refs ---------------------------------------------------
    def alloc(self) -> int:
        """Pop a free page with refcount 1. Caller must have ensured a
        free page exists (evicting through the prefix cache if needed)."""
        if not self.free:
            raise PoolExhausted(
                f"KV page pool exhausted: {self.num_pages} pages, "
                f"{self.in_use} in use, {self.cached} cached (nothing "
                "evictable was freed) — raise kv_num_pages")
        p = self.free.popleft()
        self.ref[p] = 1
        self.allocs += 1
        return p

    def incref(self, page: int) -> int:
        self.ref[page] += 1
        if page in self.evictable:      # back in use: no longer evictable
            del self.evictable[page]
        return int(self.ref[page])

    def decref(self, page: int) -> int:
        if self.ref[page] <= 0:
            raise RuntimeError(f"decref of unreferenced page {page}")
        self.ref[page] -= 1
        return int(self.ref[page])

    def release(self, page: int) -> None:
        """Return a refcount-zero page to the free list."""
        if self.ref[page] != 0:
            raise RuntimeError(
                f"release of page {page} with refcount {self.ref[page]}")
        self.evictable.pop(page, None)
        self.free.append(page)

    def park(self, page: int, clock: int) -> None:
        """Park a refcount-zero page as evictable (prefix-cached)."""
        self.evictable[page] = clock
        self.evictable.move_to_end(page)


def resolve_store_dtype(mode: str, compute_dtype):
    """Map FLAGS_kv_cache_dtype to (storage dtype, quantized?)."""
    import jax.numpy as jnp

    if mode in (None, "", "auto"):
        return compute_dtype, False
    if mode == "bf16":
        return jnp.bfloat16, False
    if mode == "int8":
        return jnp.int8, True
    raise ValueError(f"kv_cache_dtype must be auto|bf16|int8, got {mode!r}")


def quantize_kv_int8(x):
    """[..., hd] -> (int8 [..., hd], f32 scale [...]) — grad_comm's
    EQuARX absmax/127 chunk scaling with the head_dim as the chunk."""
    import jax.numpy as jnp

    xf = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(xf), axis=-1) / 127.0
    safe = jnp.maximum(scale, 1e-30)
    q = jnp.clip(jnp.round(xf / safe[..., None]), -127, 127)
    return q.astype(jnp.int8), scale


@register_pytree_node_class
class PagedLayerCache:
    """One layer's handle onto the paged pool (the interface of
    nn/kv_cache.py: `positions`, `update`), built fresh inside each traced
    prefill/decode step from the donated pool-state operands.

    offset: int32 [b] — count of already-cached positions per row (the
    write position of this step's token), pre-clamped by the engine.
    write_mask: bool [b] or [b, s] — rows/positions whose scatter goes to
    a real page; everything else is redirected to the scratch page.
    """

    fresh = False

    def __init__(self, k_pool, v_pool, page_table, offset, write_mask,
                 page_tokens: int, compute_dtype, k_scale=None, v_scale=None):
        self.k_pool = k_pool            # [P, pt, nh, hd] storage dtype
        self.v_pool = v_pool
        self.page_table = page_table    # [b, max_pages] int32
        self.offset = offset            # [b] int32
        self.write_mask = write_mask    # [b] or [b, s] bool
        self.page_tokens = int(page_tokens)
        self.compute_dtype = compute_dtype
        self.k_scale = k_scale          # [P, pt, nh] f32 (int8 mode only)
        self.v_scale = v_scale

    def tree_flatten(self):
        return ((self.k_pool, self.v_pool, self.page_table, self.offset,
                 self.write_mask, self.k_scale, self.v_scale),
                (self.page_tokens, self.compute_dtype))

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves[:5], *aux, *leaves[5:])

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None

    def positions(self, s: int):
        import jax.numpy as jnp

        return self.offset[:, None] + jnp.arange(s)[None, :]

    def update(self, k, v):
        """Scatter this step's K/V into the pools through the page table,
        then gather the full logical cache back out in compute dtype.

        k, v: [b, s, nh, hd]. Returns (kc, vc, held, new_cache): kc/vc are
        the dense [b, max_pages * page_tokens, nh, hd] views attention
        consumes, row p of a slot holding position p (an unallocated table
        entry aliases the zero page, so what the causal test hides reads as
        a zero-initialized contiguous cache does, bit for bit), and
        new_cache carries the updated pools with offset advanced by s.
        """
        import jax.numpy as jnp

        b, s = k.shape[0], k.shape[1]
        pt = self.page_tokens
        table = self.page_table
        max_pages = table.shape[1]
        t_eff = max_pages * pt

        pos = self.offset[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
        pos_c = jnp.clip(pos, 0, t_eff - 1)                       # [b, s]
        pidx = pos_c // pt
        within = pos_c % pt
        gpage = jnp.take_along_axis(table, pidx, axis=1)          # [b, s]
        wm = self.write_mask
        if wm.ndim == 1:
            wm = wm[:, None]
        # out-of-range positions (idle slot at the cache tip) always redirect
        wm = wm & (pos < t_eff)
        target = jnp.where(wm, gpage, jnp.int32(SCRATCH_PAGE))    # [b, s]

        k_pool, v_pool = self.k_pool, self.v_pool
        k_scale, v_scale = self.k_scale, self.v_scale
        if self.quantized:
            qk, sk = quantize_kv_int8(k)                  # [b,s,nh,hd]/[b,s,nh]
            qv, sv = quantize_kv_int8(v)
            k_pool = k_pool.at[target, within].set(qk)
            v_pool = v_pool.at[target, within].set(qv)
            k_scale = k_scale.at[target, within].set(sk)
            v_scale = v_scale.at[target, within].set(sv)
        else:
            k_pool = k_pool.at[target, within].set(k.astype(k_pool.dtype))
            v_pool = v_pool.at[target, within].set(v.astype(v_pool.dtype))

        # gather: [b, max_pages, pt, nh, hd] -> [b, t_eff, nh, hd]
        def _gather(pool, scale):
            g = pool[table]
            if scale is not None:
                g = g.astype(jnp.float32) * scale[table][..., None]
            g = g.reshape((b, t_eff) + g.shape[3:])
            return g.astype(self.compute_dtype)

        kc = _gather(k_pool, k_scale)
        vc = _gather(v_pool, v_scale)
        new_cache = PagedLayerCache(
            k_pool, v_pool, table, self.offset + jnp.int32(s),
            self.write_mask, pt, self.compute_dtype, k_scale, v_scale)
        return kc, vc, jnp.arange(t_eff)[None, None, :], new_cache


class PagedSlotCache:
    """The slot cache over a page pool (the interface of kv_state.py): device
    side the per-layer K/V pools, optional per-layer scale pools, ONE
    [slots, max_pages] page table for all layers and the per-row `replay`
    flag, as one donated pytree; host side the page accounting (`pool`,
    `prefix`, each slot's `slot_pages`, the `tables` the next dispatch
    uploads).

    `replay`: a full-prefix-hit slot's first step re-derives a position whose
    K/V already sits in a shared page, so its write goes to the scratch page;
    the flag clears after the row's first active step."""

    n_args = 1
    masks_writes = True
    prefill_at = ("base", "slot")

    def __init__(self, spec, slots: int, max_seq_len: int, compute_dtype,
                 page_tokens: int, num_pages: Optional[int], mode):
        import jax.numpy as jnp
        import numpy as np

        from .prefix_cache import RadixPrefixCache

        if page_tokens < 1:
            raise ValueError(f"kv_page_tokens must be >= 1, got {page_tokens}")
        # this refusal is the prefix cache's too: only this class builds one
        refuse_state_layers(
            spec, "kv_layout='paged'",
            "kv_pages.py's pages hold rows addressed by position through "
            "one page table for all layers, and its prefix cache shares the "
            "pages up to a position, where a state cannot be cut; use "
            "'contiguous'")
        refuse_latent_layers(
            spec, "kv_layout='paged'",
            "kv_pages.py's pools hold a page of keys beside a page of values "
            "a head, which a latent row has not, so neither the pool nor "
            "its prefix cache can keep one; use 'contiguous'")
        self.spec = list(spec)
        self.compute_dtype = compute_dtype
        self.page_tokens = pt = int(page_tokens)
        self.max_seq_len = int(max_seq_len)
        self.max_pages = -(-self.max_seq_len // pt)       # ceil(T / pt)
        self.store_dtype, self.quantized = resolve_store_dtype(
            mode, compute_dtype)
        # default pool covers the contiguous worst case (every slot at
        # max_seq_len) so it can never exhaust; pass kv_num_pages to
        # trade bytes for admission-time eviction pressure
        self.num_pages = int(num_pages if num_pages is not None
                             else slots * self.max_pages + RESERVED_PAGES)
        self.pool = PagePool(self.num_pages)
        self.prefix = RadixPrefixCache(self.pool, pt)
        self.tables = np.zeros((slots, self.max_pages), np.int32)
        self.slot_pages: List[List[int]] = [[] for _ in range(slots)]
        self.replay = np.zeros(slots, bool)
        shape = (self.num_pages, pt, spec[0].kv_heads, spec[0].head_dim)

        def pools(shape, dtype, n):
            return [jnp.zeros(shape, dtype) for _ in range(n)]

        n, nq = len(spec), len(spec) if self.quantized else 0
        self.state = {"k": pools(shape, self.store_dtype, n),
                      "v": pools(shape, self.store_dtype, n),
                      "ks": pools(shape[:3], jnp.float32, nq),
                      "vs": pools(shape[:3], jnp.float32, nq)}

    # ---- between dispatches -------------------------------------------
    def args(self):
        import jax.numpy as jnp

        return (dict(self.state, tables=jnp.asarray(self.tables),
                     replay=jnp.asarray(self.replay)),)

    def take(self, results, stepped=None) -> None:
        """`stepped`: the rows a decode or verify dispatch ran as active. The
        device cleared their flags (`absorb`), so the host's copy follows
        without a fetch. A prefill steps no seated row: a slot seated for
        replay earlier in the same admission round keeps its flag."""
        (state,) = results
        self.state = {name: state[name] for name in ("k", "v", "ks", "vs")}
        if stepped is not None:
            self.replay &= ~stepped

    def nbytes(self) -> int:
        """Pools + scales + tables: the paged cache's footprint."""
        import jax

        return self.tables.nbytes + sum(
            a.size * a.dtype.itemsize
            for a in jax.tree_util.tree_leaves(self.state))

    def cover(self, active, offsets, last) -> None:
        """Make sure every active slot's table row covers the positions the
        next dispatch may write, its offset up to `last[slot]` (the table
        is static within a dispatch; a replaying slot's first write goes to
        the scratch page). Evicts LRU cached prefixes under pressure;
        admission reservations guarantee success."""
        import numpy as np

        pt = self.page_tokens
        for i in np.nonzero(active)[0]:
            first = int(offsets[i]) + (1 if self.replay[i] else 0)
            for pi in range(first // pt, int(last[i]) // pt + 1):
                if self.tables[i, pi] == 0:
                    if not self.prefix.ensure_free(1):
                        raise PoolExhausted(
                            f"slot {i} needs a page and none is free or "
                            "evictable (reservation accounting violated)")
                    page = self.pool.alloc()
                    self.tables[i, pi] = page
                    self.slot_pages[i].append(page)

    def release(self, slot: int) -> None:
        """Drop the slot's page references (shared pages decref; own pages
        free or park for prefix reuse) and reset its table row to the zero
        page."""
        for p in self.slot_pages[slot]:
            self.prefix.release(int(p))
        self.slot_pages[slot] = []
        self.tables[slot, :] = 0
        self.replay[slot] = False

    def truncate(self, slot: int, keep: int) -> None:
        """Speculative rollback: drop the slot's table entries past the page
        that holds position `keep` (the next to be written) and free their
        pages. After a verify window is partially rejected the slot's offset
        rewinds to the accepted frontier and those pages hold only rejected
        rows. They are always slot-private — shared prefix pages and
        trie-published prompt pages all sit below `keep // page_tokens`
        because generation positions start at the prompt length — so
        releasing them through the prefix cache frees them outright."""
        for pi in range(keep // self.page_tokens + 1, self.max_pages):
            page = int(self.tables[slot, pi])
            if page != ZERO_PAGE:
                self.tables[slot, pi] = ZERO_PAGE
                self.slot_pages[slot].remove(page)
                self.prefix.release(page)

    def gauges(self) -> dict:
        return {"pages_in_use": self.pool.in_use,
                "pages_cached": self.pool.cached,
                "prefix_hit_rate": self.prefix.hit_rate}

    # ---- inside a traced program --------------------------------------
    def tip(self, offsets):
        import jax.numpy as jnp

        return jnp.clip(offsets, 0,
                        jnp.int32(self.max_pages * self.page_tokens - 1))

    def _handles(self, state, table, offsets, write_mask):
        n = len(state["k"])
        ks = state["ks"] or [None] * n
        vs = state["vs"] or [None] * n
        return [PagedLayerCache(state["k"][i], state["v"][i], table, offsets,
                                write_mask, self.page_tokens,
                                self.compute_dtype, ks[i], vs[i])
                for i in range(n)]

    def views(self, args, offsets, write_mask):
        """write_mask: bool [b], or [b, s] for a window of s positions whose
        first is the row's offset; idle rows and a replaying row's first
        position write to the scratch page."""
        import jax.numpy as jnp

        (state,) = args
        replay = state["replay"]
        if write_mask.ndim == 2:
            first = jnp.arange(write_mask.shape[1], dtype=jnp.int32) == 0
            replay = replay[:, None] & first[None, :]
        return self._handles(state, state["tables"],
                             offsets.astype(jnp.int32), write_mask & ~replay)

    def absorb(self, args, handles, active):
        (state,) = args
        return (self._absorbed(state, handles,
                               state["replay"] & ~active),)

    def _absorbed(self, state, handles, replay):
        q = self.quantized
        return {"k": [c.k_pool for c in handles],
                "v": [c.v_pool for c in handles],
                "ks": [c.k_scale for c in handles] if q else [],
                "vs": [c.v_scale for c in handles] if q else [],
                "tables": state["tables"], "replay": replay}

    def prefill_views(self, args, bucket: int, length, base, slot):
        """The unshared tail of a prompt (`length` tokens from position
        `base`) writes through the slot's page-table row. Pad positions past
        the tail go to the scratch page: their table entries may be
        unallocated, and the zero page must never be written."""
        import jax
        import jax.numpy as jnp

        (state,) = args
        table_row = jax.lax.dynamic_slice_in_dim(
            state["tables"], slot, 1, 0)                     # [1, max_pages]
        wmask = (jnp.arange(bucket, dtype=jnp.int32)[None, :]
                 < length)                                   # [1, bucket]
        return self._handles(state, table_row, base[None], wmask)

    def commit_prefill(self, args, handles, length, base, slot):
        (state,) = args
        return (self._absorbed(state, handles, state["replay"]),)

    @staticmethod
    def first_position(length, base, slot):
        """The position of the request's first generated token."""
        return base + length
