"""Paged KV-cache attention for serving.

Reference: ABSENT from the reference repo (it serves models via user
code in replicas — SURVEY P15); this is the vLLM-style PagedAttention
scheme rebuilt TPU-first: the KV cache is a pool of fixed-size pages,
each sequence owns a page table, and ``paged_attention`` here gathers a
sequence's pages with static shapes (gather + mask — XLA-friendly; the
unjitted helper of the tests and of small callers). The serving
engine's decode step does not gather: its Pallas kernel reads the pages
where they lie (``ops/paged_decode_attention.py``, same layout below).

Why paging: the slot-based cache (ray_tpu/models/decoding.py KVCache)
reserves max_len per slot — a 2048-token cache for an 80-token chat
wastes 96% of its HBM. Pages allocate on demand, so max_batch scales
with TOKENS in flight, not worst-case sequence length.

Layout:
    k_pages, v_pages: [L, n_pages, page_size, n_kv, head_dim]
    page_table:       [B, max_pages_per_seq] int32 (−1 = unused)
    lengths:          [B] int32 tokens currently cached per sequence
"""

from __future__ import annotations

import hashlib
from collections import OrderedDict
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np


@dataclass
class PagedKVCache:
    """K/V pages live on device; the page table and lengths are HOST
    numpy — they're scheduler bookkeeping mutated per request per step,
    and keeping them host-side avoids a device round-trip + sync on
    every allocation (they ship to the device per attention call, a few
    hundred bytes)."""

    k_pages: jax.Array       # [L, P, page, nkv, hd]
    v_pages: jax.Array
    page_table: np.ndarray   # [B, max_pages] int32, -1 = hole
    lengths: np.ndarray      # [B] int32


def init_paged_cache(cfg, *, num_pages: int, page_size: int,
                     max_batch: int, max_pages_per_seq: int,
                     dtype=jnp.bfloat16) -> PagedKVCache:
    nkv = getattr(cfg, "n_kv_heads", None) or cfg.n_heads
    hd = cfg.head_dim
    shape = (cfg.n_layers, num_pages, page_size, nkv, hd)
    return PagedKVCache(
        k_pages=jnp.zeros(shape, dtype),
        v_pages=jnp.zeros(shape, dtype),
        page_table=np.full((max_batch, max_pages_per_seq), -1, np.int32),
        lengths=np.zeros((max_batch,), np.int32),
    )


class PageAllocator:
    """Host-side free-list of page ids (the serving engine's bookkeeping;
    device tensors never see allocation logic)."""

    def __init__(self, num_pages: int):
        self.free = list(range(num_pages - 1, -1, -1))
        self.owned: dict[int, list[int]] = {}  # seq slot -> page ids

    def alloc(self, slot: int, n: int) -> list[int]:
        if len(self.free) < n:
            raise MemoryError(
                f"paged KV cache exhausted: need {n} pages, "
                f"{len(self.free)} free")
        pages = [self.free.pop() for _ in range(n)]
        self.owned.setdefault(slot, []).extend(pages)
        return pages

    def free_slot(self, slot: int):
        for p in self.owned.pop(slot, []):
            self.free.append(p)

    def pages_needed(self, cur_len: int, new_tokens: int,
                     page_size: int) -> int:
        have = (cur_len + page_size - 1) // page_size
        need = (cur_len + new_tokens + page_size - 1) // page_size
        return need - have


def paged_write(cache: PagedKVCache, layer: int, slot, k_new, v_new,
                start) -> PagedKVCache:
    """Append k_new/v_new [T, nkv, hd] for one sequence at position
    `start` (its current length). Positions map to
    (page_table[slot][pos // page], pos % page). A position landing on
    an unassigned table hole (-1) is DROPPED, never written: -1 would
    wrap to the last page and silently corrupt another sequence's KV.

    PERF: each functional .at[].set copies the whole multi-layer page
    pool when run eagerly — call this inside jit with the cache arrays
    donated (XLA then updates in place), or write every layer at once
    with paged_write_all."""
    page_size = cache.k_pages.shape[2]
    num_pages = cache.k_pages.shape[1]
    t = k_new.shape[0]
    pos = start + np.arange(t)
    page_idx = cache.page_table[slot][pos // page_size]  # [T] host
    # holes -> out-of-bounds index + mode="drop" (loud alternative:
    # callers should assign_pages first; see assign_pages' guard)
    page_idx = np.where(page_idx >= 0, page_idx, num_pages)
    in_page = pos % page_size

    k_pages = cache.k_pages.at[layer, jnp.asarray(page_idx),
                               jnp.asarray(in_page)].set(
        k_new.astype(cache.k_pages.dtype), mode="drop")
    v_pages = cache.v_pages.at[layer, jnp.asarray(page_idx),
                               jnp.asarray(in_page)].set(
        v_new.astype(cache.v_pages.dtype), mode="drop")
    return PagedKVCache(k_pages, v_pages, cache.page_table, cache.lengths)


def paged_write_all(cache: PagedKVCache, slot, k_new, v_new,
                    start) -> PagedKVCache:
    """Append k_new/v_new [L, T, nkv, hd] for ALL layers in one indexed
    update per tensor (one pool copy eagerly, in-place under jit) —
    the per-decode-step entry point."""
    page_size = cache.k_pages.shape[2]
    num_pages = cache.k_pages.shape[1]
    t = k_new.shape[1]
    pos = start + np.arange(t)
    page_idx = cache.page_table[slot][pos // page_size]
    page_idx = np.where(page_idx >= 0, page_idx, num_pages)
    in_page = pos % page_size
    k_pages = cache.k_pages.at[:, jnp.asarray(page_idx),
                               jnp.asarray(in_page)].set(
        k_new.astype(cache.k_pages.dtype), mode="drop")
    v_pages = cache.v_pages.at[:, jnp.asarray(page_idx),
                               jnp.asarray(in_page)].set(
        v_new.astype(cache.v_pages.dtype), mode="drop")
    return PagedKVCache(k_pages, v_pages, cache.page_table, cache.lengths)


def paged_attention(q, cache: PagedKVCache, layer: int, *,
                    scale: float | None = None):
    """Decode-step attention: q [B, n_heads, hd] against each sequence's
    paged KV. Gathers each sequence's pages into a contiguous
    [max_pages*page, nkv, hd] view (static shape) and masks beyond
    `lengths`. Supports GQA (n_heads a multiple of n_kv)."""
    b, nh, hd = q.shape
    page_size = cache.k_pages.shape[2]
    nkv = cache.k_pages.shape[3]
    max_pages = cache.page_table.shape[1]
    if scale is None:
        scale = hd ** -0.5
    n_rep = nh // nkv

    # gather pages: [B, max_pages, page, nkv, hd]; holes (-1) clamp to
    # page 0 and are masked out by `lengths`
    table = jnp.maximum(jnp.asarray(cache.page_table), 0)
    k = cache.k_pages[layer][table]
    v = cache.v_pages[layer][table]
    s = max_pages * page_size
    k = k.reshape(b, s, nkv, hd)
    v = v.reshape(b, s, nkv, hd)

    qg = q.reshape(b, nkv, n_rep, hd)
    logits = jnp.einsum("bgrd,bkgd->bgrk", qg, k,
                        preferred_element_type=jnp.float32) * scale
    kpos = jnp.arange(s)
    lengths = jnp.asarray(cache.lengths)
    mask = kpos[None, :] < lengths[:, None]                # [B, S]
    logits = jnp.where(mask[:, None, None, :], logits,
                       jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bgrk,bkgd->bgrd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, nh, hd).astype(q.dtype)


def quantize_kv(x):
    """Per-token-per-head symmetric int8 quantization of a K or V tensor
    over its trailing head_dim axis: returns (int8 values, f32 scales
    with the trailing axis dropped). Halves KV HBM (the pool holds 2x
    the tokens) at <1% relative error — the standard serving-engine KV
    compression (w8 KV in vLLM/TGI terms)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def dequantize_kv(q, scale, dtype=jnp.bfloat16):
    """Inverse of quantize_kv (scale broadcast over head_dim)."""
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def page_hashes(tokens: np.ndarray, page_size: int) -> list[bytes]:
    """Chained content hashes of the FULL pages of a token sequence —
    hash i covers tokens[0 : (i+1)*page_size], so equal hash means equal
    whole prefix (the prefix-cache key; vLLM's automatic prefix caching
    uses the same chained-block-hash scheme). Partial trailing pages are
    never hashed: only fully-written pages are shareable."""
    out: list[bytes] = []
    h = b""
    toks = np.ascontiguousarray(np.asarray(tokens, np.int32))
    for i in range(len(toks) // page_size):
        h = hashlib.blake2b(
            h + toks[i * page_size:(i + 1) * page_size].tobytes(),
            digest_size=16).digest()
        out.append(h)
    return out


class PrefixCache:
    """Host-side prefix-page registry: chained page hash -> page id, with
    per-page refcounts and LRU eviction of unreferenced pages.

    A page is in exactly one of three states: SHARED (refs > 0 — mapped
    by at least one live slot's table; never evictable, never written),
    CACHED-IDLE (refs == 0, still holds valid KV; evictable), or gone
    (evicted — the id returned to the allocator's free list and its hash
    mapping dropped, so no future lookup can see stale contents)."""

    def __init__(self):
        self._by_hash: dict[bytes, int] = {}
        self._hash_of: dict[int, bytes] = {}
        self._refs: dict[int, int] = {}
        self._idle: OrderedDict[int, None] = OrderedDict()  # LRU order
        self.hit_pages = 0
        self.miss_pages = 0

    def acquire(self, hashes: list[bytes]) -> list[int]:
        """Longest contiguous run of cached pages for a hash chain; each
        returned page's refcount is bumped (caller owns one release)."""
        pages: list[int] = []
        for hsh in hashes:
            page = self._by_hash.get(hsh)
            if page is None:
                break
            pages.append(page)
            self.hit_pages += 1
            self._refs[page] = self._refs.get(page, 0) + 1
            self._idle.pop(page, None)
        # every page from the first miss on has to be computed: then
        # hit / (hit + miss) is the share of looked-up pages reused
        self.miss_pages += len(hashes) - len(pages)
        return pages

    def release(self, pages: list[int]):
        """Drop one reference per page; unreferenced pages stay cached
        but become evictable (most recently released = evicted last)."""
        for page in pages:
            n = self._refs.get(page, 0) - 1
            if n > 0:
                self._refs[page] = n
            else:
                self._refs.pop(page, None)
                if page in self._hash_of:
                    self._idle[page] = None
                    self._idle.move_to_end(page)

    def ref(self, page: int):
        self._refs[page] = self._refs.get(page, 0) + 1
        self._idle.pop(page, None)

    def insert(self, hsh: bytes, page: int) -> bool:
        """Register a freshly prefilled full page. False when the hash is
        already cached (a concurrent identical prompt won registration;
        the caller keeps its copy exclusive)."""
        if hsh in self._by_hash:
            return False
        self._by_hash[hsh] = page
        self._hash_of[page] = hsh
        return True

    def evictable(self) -> int:
        return len(self._idle)

    def evict(self, n: int) -> list[int]:
        """Drop up to n least-recently-released idle pages from the
        cache; the returned ids are free for reallocation (their hash
        mappings are gone, so no lookup can alias the recycled page)."""
        out: list[int] = []
        while self._idle and len(out) < n:
            page, _ = self._idle.popitem(last=False)
            hsh = self._hash_of.pop(page)
            self._by_hash.pop(hsh, None)
            out.append(page)
        return out


# ---------------------------------------------------------------------------
# host-side helpers for the serving engine
# ---------------------------------------------------------------------------

def assign_pages(cache: PagedKVCache, allocator: PageAllocator, slot: int,
                 new_tokens: int) -> PagedKVCache:
    """Grow `slot`'s page table to cover new_tokens more positions.
    Raises MemoryError (the allocator's exhaustion contract) when the
    sequence would outgrow max_pages_per_seq — not an opaque numpy
    broadcast error."""
    page_size = cache.k_pages.shape[2]
    max_pages = cache.page_table.shape[1]
    cur = int(cache.lengths[slot])
    n_new = allocator.pages_needed(cur, new_tokens, page_size)
    if n_new == 0:
        return cache
    have = (cur + page_size - 1) // page_size
    if have + n_new > max_pages:
        raise MemoryError(
            f"sequence in slot {slot} needs {have + n_new} pages, over "
            f"max_pages_per_seq={max_pages}")
    pages = allocator.alloc(slot, n_new)
    cache.page_table[slot, have:have + n_new] = pages  # host, in place
    return cache


def release_slot(cache: PagedKVCache, allocator: PageAllocator,
                 slot: int) -> PagedKVCache:
    allocator.free_slot(slot)
    cache.page_table[slot, :] = -1
    cache.lengths[slot] = 0
    return cache
