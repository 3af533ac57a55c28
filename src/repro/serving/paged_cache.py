"""Paged KV-cache storage: a global page pool + per-sequence block tables.

Instead of one contiguous ``(B, max_len, ...)`` cache buffer per batch slot
(whose memory is ``max_len``-bound regardless of actual lengths), the cache is
a pool of fixed-size pages shared by every sequence:

    k_pages / v_pages : (n_layers, n_pages, n_kv_heads, page_size, head_dim)

The layout is head-major so that one (KV head, page) tile is a whole pair of
minor dimensions — the block the paged-decode kernel DMAs, and the only block
shape the TPU compiler accepts for every page size and head dim.

A sequence of length ``s`` holds exactly ``ceil(s / page_size)`` page ids (the
same ids index every layer's pool), so pool memory tracks the LIVE token count
— the memory term BiLLM (2402.04291) shows dominates ultra-low-bit serving.
Page ids are handed out by a free-list ``PageAllocator`` and returned when a
sequence finishes (or is preempted), which is what lets the continuous
batcher keep admitting new requests between decode steps.

Physical page 0 is reserved as the *null page*: idle batch slots point their
block tables at it, so the jitted decode step can scatter-write
unconditionally without corrupting a live sequence.

Since the prefix-caching refactor a page is a REFCOUNTED object rather than
the property of one sequence: ``retain`` adds an owner, ``release`` drops one
and returns the page to the free list only at zero, and ``free`` keeps its
historical name as an alias of ``release`` (including the double-free /
foreign-id guard). Two structures share pages:

  - ``PrefixCache``: a content-addressed index mapping the chained hash of
    each FULL page of prompt tokens to the physical page holding its K/V.
    The cache itself holds one reference per indexed page, so cached runs
    survive their producing sequence; unreferenced entries are retired in
    LRU order when the pool runs dry.
  - duplicate-admit aliasing: a queued request whose content is identical to
    a just-admitted one joins the batch by retaining the admitted slot's
    pages outright (zero prefill); the first decode write into a page still
    shared with another owner triggers a copy-on-write fork
    (``PagedKVCache.fork_page``) so owners never mutate shared state.

With ``cfg.kv_cache_dtype == "int8"`` pages store int8 codes plus per-(slot,
head) absmax scales, (n_layers, n_pages, n_kv_heads, page_size) — the same
quantization as the contiguous cache in
``repro.models.layers`` (scales per group of ``head_dim`` values, matching the
group-quant scales convention of one scale per contiguous value group).
"""
from __future__ import annotations

import collections
import hashlib
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import ModelConfig

__all__ = ["PageAllocator", "PagedKVCache", "PrefixCache", "NULL_PAGE",
           "chain_keys"]

NULL_PAGE = 0


@jax.jit
def _copy_page(pools, src, dst):
    """{leaf: (L, N, ...)} with row ``dst`` <- row ``src`` on every leaf."""
    return {k: v.at[:, dst].set(v[:, src]) for k, v in pools.items()}


class PageAllocator:
    """Refcounting LIFO free-list over page ids [reserved, n_pages).

    ``alloc`` is all-or-nothing (a partial grant would deadlock the batcher:
    a sequence cannot attend over half its prompt) and hands pages out with
    refcount 1. ``retain`` adds an owner; ``release`` (alias ``free``) drops
    one and returns the page to the free list only when the count reaches
    zero. Releasing a page that is not live raises — an id returned twice
    means two owners believe they dropped the same reference, which silently
    corrupts attention output once the page is re-issued.
    """

    def __init__(self, n_pages: int, reserved: int = 1):
        if n_pages <= reserved:
            raise ValueError(f"need more than {reserved} pages, got {n_pages}")
        self.n_pages = n_pages
        self.reserved = reserved
        self._free: List[int] = list(range(n_pages - 1, reserved - 1, -1))
        self._ref: Dict[int, int] = {}       # live page id -> owner count

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_live(self) -> int:
        return len(self._ref)

    def refcount(self, i: int) -> int:
        """Current owner count of page ``i`` (0 if the page is free)."""
        return self._ref.get(i, 0)

    def alloc(self, n: int) -> Optional[List[int]]:
        """n page ids at refcount 1, or None (no side effects) if fewer free."""
        if n < 0:
            raise ValueError(f"alloc({n})")
        if n > len(self._free):
            return None
        ids = [self._free.pop() for _ in range(n)]
        for i in ids:
            self._ref[i] = 1
        return ids

    def retain(self, ids: Sequence[int]) -> None:
        """Add one owner to each live page; retaining a free page raises."""
        for i in ids:
            if i not in self._ref:
                raise ValueError(f"retain of free / foreign page id {i}")
        for i in ids:
            self._ref[i] += 1

    def release(self, ids: Sequence[int]) -> List[int]:
        """Drop one owner per id; returns the ids that actually went free."""
        freed = []
        for i in ids:
            n = self._ref.get(i, 0)
            if n <= 0:
                raise ValueError(f"double free / foreign page id {i}")
            if n == 1:
                del self._ref[i]
                self._free.append(i)
                freed.append(i)
            else:
                self._ref[i] = n - 1
        return freed

    # historical name: single-owner callers (and the allocator tests) treat
    # "free" as "drop my reference", which is exactly what release does
    free = release


def chain_keys(tokens: np.ndarray, page_size: int) -> List[bytes]:
    """Content-addressed keys for every FULL page of ``tokens``.

    ``keys[i]`` commits to pages 0..i (the hash chains the previous key), so
    equal keys mean the whole prefix up to and including page ``i`` is
    token-identical — a page's K/V depends on every earlier position, so the
    prefix cache must never match on page content alone.
    """
    keys = []
    prev = b"paged-prefix-v1"
    toks = np.ascontiguousarray(np.asarray(tokens, np.int32))
    for i in range(len(toks) // page_size):
        h = hashlib.blake2b(digest_size=16)
        h.update(prev)
        h.update(toks[i * page_size: (i + 1) * page_size].tobytes())
        prev = h.digest()
        keys.append(prev)
    return keys


class PrefixCache:
    """Content-addressed prefix index over the page pool.

    Maps ``chain_keys`` entries (the chained hash of a full-page prompt run)
    to the physical page holding that run's K/V. The cache holds ONE
    reference on every indexed page, so cached runs outlive the sequence
    that produced them; ``evict_lru`` retires entries whose page has no other
    owner (refcount 1) in least-recently-matched order when the allocator
    runs dry, and ``clear`` drops every cache reference (pages still owned
    by live slots survive — they just stop being findable).
    """

    def __init__(self, allocator: PageAllocator,
                 max_entries: Optional[int] = None):
        self.allocator = allocator
        self.max_entries = max_entries
        self._runs: "collections.OrderedDict[bytes, int]" = \
            collections.OrderedDict()
        self.hits = 0
        self.misses = 0

    def __len__(self) -> int:
        return len(self._runs)

    def lookup(self, keys: Sequence[bytes]) -> List[int]:
        """Longest indexed prefix run of ``keys`` -> page ids, RETAINED for
        the caller (one new reference per returned page)."""
        run: List[int] = []
        for k in keys:
            pid = self._runs.get(k)
            if pid is None:
                self.misses += 1
                break
            self._runs.move_to_end(k)
            run.append(pid)
        self.hits += len(run)
        self.allocator.retain(run)
        return run

    def insert(self, key: bytes, page_id: int) -> bool:
        """Index ``page_id`` under ``key`` (cache takes its own reference).
        Returns False (no reference taken) if the key is already present."""
        if key in self._runs:
            self._runs.move_to_end(key)
            return False
        self.allocator.retain([page_id])
        self._runs[key] = page_id
        if self.max_entries is not None and len(self._runs) > self.max_entries:
            self.evict_lru(len(self._runs) - self.max_entries)
        return True

    def evict_lru(self, n_pages: int) -> int:
        """Retire up to ``n_pages`` unreferenced entries (LRU first).

        Only entries whose page the cache is the SOLE owner of (refcount 1)
        are retired — pages still aliased into live block tables must keep
        their index entry, releasing them would not free memory anyway.
        """
        freed = 0
        if n_pages <= 0:
            return 0
        for key in list(self._runs):
            pid = self._runs[key]
            if self.allocator.refcount(pid) == 1:
                del self._runs[key]
                self.allocator.release([pid])
                freed += 1
                if freed >= n_pages:
                    break
        return freed

    def clear(self) -> None:
        """Drop every cache reference (end-of-run drain)."""
        for pid in self._runs.values():
            self.allocator.release([pid])
        self._runs.clear()


def refuse_unpaged(cfg: ModelConfig, what: str) -> None:
    """Raise where the paged serving path cannot hold ``cfg``'s cache: a
    block pattern with no attention cache, or latent attention (MLA), whose
    per-token cache is a latent (``kv_lora_rank`` + ``qk_rope_head_dim``)
    that no paged pool here stores (the pools hold per-head K and V)."""
    if cfg.block_pattern not in ("dense", "moe"):
        raise ValueError(f"{what} requires attention blocks, "
                         f"got {cfg.block_pattern!r}")
    if cfg.mla is not None:
        raise ValueError(
            f"{what}: latent attention (MLA) needs a latent paged cache "
            f"({cfg.mla.kv_lora_rank} + {cfg.mla.qk_rope_head_dim} values a "
            f"token a layer), which the paged pools do not have; they hold "
            f"per-head K/V pages")


class PagedKVCache:
    """Device page pools for every layer plus the page allocator.

    The pools are plain jnp arrays handed in and out of the jitted decode
    step (functional updates); this object owns their *identity* between
    steps and the host-side allocator state.
    """

    def __init__(self, cfg: ModelConfig, n_pages: int, page_size: int,
                 max_pages_per_seq: int):
        if cfg.block_pattern not in ("dense", "moe"):
            raise ValueError(
                f"paged KV cache requires an attention cache; "
                f"block_pattern={cfg.block_pattern!r} keeps O(1) state")
        refuse_unpaged(cfg, "paged KV cache")
        self.cfg = cfg
        self.page_size = page_size
        self.n_pages = n_pages
        self.max_pages_per_seq = max_pages_per_seq
        self.allocator = PageAllocator(n_pages)
        L, Hkv, hd = cfg.n_layers, cfg.n_kv_heads, cfg.resolved_head_dim
        self.quantized = cfg.kv_cache_dtype == "int8"
        dt = jnp.dtype(cfg.compute_dtype)
        kv_dt = jnp.int8 if self.quantized else dt
        self.pools = {
            "k": jnp.zeros((L, n_pages, Hkv, page_size, hd), kv_dt),
            "v": jnp.zeros((L, n_pages, Hkv, page_size, hd), kv_dt),
        }
        if self.quantized:
            self.pools["k_scale"] = jnp.zeros((L, n_pages, Hkv, page_size), dt)
            self.pools["v_scale"] = jnp.zeros((L, n_pages, Hkv, page_size), dt)

    # -- geometry ----------------------------------------------------------

    def pages_for(self, n_tokens: int) -> int:
        return max(1, -(-n_tokens // self.page_size))

    def pool_bytes(self) -> int:
        return sum(int(a.size * a.dtype.itemsize) for a in self.pools.values())

    def dense_equiv_bytes(self, batch: int, max_len: int) -> int:
        """What a contiguous (B, max_len) cache would cost at the same dtype."""
        slots = self.n_pages * self.page_size
        per_tok = sum(a.size // slots * a.dtype.itemsize
                      for a in self.pools.values())
        return batch * max_len * per_tok

    # -- block tables ------------------------------------------------------

    def block_table_row(self, page_ids: Sequence[int]) -> np.ndarray:
        """(max_pages_per_seq,) int32 row, padded with the null page."""
        if len(page_ids) > self.max_pages_per_seq:
            raise ValueError(
                f"sequence needs {len(page_ids)} pages > "
                f"max_pages_per_seq={self.max_pages_per_seq}")
        row = np.full((self.max_pages_per_seq,), NULL_PAGE, np.int32)
        row[: len(page_ids)] = page_ids
        return row

    def gather_tokens(self, page_ids: Sequence[int], length: int) -> dict:
        """Read the first ``length`` token rows of a sequence back out of the
        pool: {key: (L, length, ...)} in token order. Test/debug helper — the
        serving path never materialises this contiguous view."""
        ids = jnp.asarray(page_ids, jnp.int32)
        out = {}
        for key, pool in self.pools.items():
            rows = jnp.swapaxes(pool[:, ids], 2, 3)      # (L, n, psz, Hkv, ...)
            rows = rows.reshape((rows.shape[0], -1) + rows.shape[3:])
            out[key] = rows[:, :length]
        return out

    # -- copy-on-write fork ------------------------------------------------

    def fork_page(self, src: int, dst: int) -> None:
        """Copy page ``src``'s rows (every layer, every pool leaf) into
        ``dst`` — the copy-on-write fork run by the batcher before a decode
        write would mutate a page that still has other owners. One jitted
        program regardless of page ids (ids are traced scalars)."""
        self.pools = _copy_page(self.pools, jnp.int32(src), jnp.int32(dst))

    # -- prefill write (legacy contiguous path) ----------------------------

    def write_prefill(self, page_ids: Sequence[int], cache: dict,
                      length: int) -> None:
        """Scatter a freshly prefilled contiguous cache into the pool.

        ``cache`` is ``model.prefill``'s per-layer cache for ONE sequence
        (leaves (L, 1, S_pad, ...)) with ``S_pad >= len(page_ids) *
        page_size`` covering the ``length``-token prompt. Rows past
        ``length`` inside the last page carry garbage — masked at read time
        by the per-sequence length.

        Since serving v2 the batcher admits through the CHUNKED paged
        prefill (``serving/prefill.py``) and never calls this; it remains as
        the reference path the equivalence tests compare against.
        """
        n = len(page_ids)
        need = self.pages_for(length)
        if n < need:
            raise ValueError(f"{n} pages cannot hold {length} tokens")
        ids = jnp.asarray(page_ids, jnp.int32)
        for key in self.pools:
            src = cache[key][:, 0]                       # (L, S_pad, ...)
            if src.shape[1] < n * self.page_size:
                raise ValueError(
                    f"prefill cache depth {src.shape[1]} < {n} pages")
            src = src[:, : n * self.page_size]
            src = src.reshape((src.shape[0], n, self.page_size) + src.shape[2:])
            src = jnp.swapaxes(src, 2, 3)                # head-major pages
            self.pools[key] = self.pools[key].at[:, ids].set(
                src.astype(self.pools[key].dtype))
