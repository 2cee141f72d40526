"""The paged-KV format: where a token's K and V rows live, how they are
written and how they are read back.

Reference: ABSENT from the reference repo (it serves models via user
code in replicas — SURVEY P15); this is the vLLM-style PagedAttention
scheme rebuilt TPU-first. The serving engine (``serve/paged_llm.py``)
holds the pools and calls this module from its two device programs; the
decode kernel (``ops/paged_decode_attention.py``) reads the same layout
in place. Nothing else states the format.

Why paging: a cache of contiguous rows reserves max_len per sequence — a
2048-token row for an 80-token chat wastes 96% of its HBM. Pages are
reserved per request, so max_batch scales with TOKENS in flight, not
worst-case sequence length.

Layout:
    k_pages, v_pages: [L, n_pages, page_size, n_kv, head_dim], bf16, or
                      int8 with
    k_scale, v_scale: [L, n_pages, page_size, n_kv] float32 (one scale a
                      token and head: ``quantize_kv``)
    page_table:       [B, max_pages_per_seq] int32 (-1 = unused)

KV heads of HALF a lane tile (a head of 64) lie TWO A ROW:
``heads_per_row(nkv, hd)`` consecutive KV heads side by side in one
row of ``ROW_LANES``, the pool [L, n_pages, page_size, n_kv / per, 128]
(``pool_heads`` says the shape, ``rows_of_heads`` lays q, k and v in it,
``own_parts`` takes a head's result back out). The chip tiles an array's
last axis in lanes of 128 whatever it holds, so a pool whose rows are 64
wide keeps 12,288 B a token where the model keeps 6,144, the decode
kernel copies the padding and the prefill kernel's rule (whole lanes)
sends every prompt down the gather formulation. Side by side, K's
write is a reshape of [.., nkv, hd], which is how it lies; a QUERY head
is laid in the part of the row its KV head has, zeros in the others, so
the one matmul that scores a head against a row scores it against its
own part, the kernels' mask of "the rows of its KV head" keeps the rows
of its KV head's ROW, and of the weighted sum [.., 128] the part that is
its own is sliced out. No entry below changes by a line: each sees a
pool of ``n_kv / per`` heads of 128 and queries in groups ``per`` times
as large (the trick ``ops/index_select.py`` plays for a 64-wide index
key, "by padding the queries"). The price is ``per`` times the
attention's multiply-adds, which a decode step bound by its bytes does
not see and a prefill does. The entries scale a score by
``page_attention_scale`` of the width THEY see, so ``rows_of_heads``
folds the ratio of the two scales into the queries it lays out.

A layer that keeps ONE ROW a token and no K/V twins (latent attention:
the compressed KV with its rotary key; an indexer's key) has pools of
    rows:             [L, n_pages, page_size, lanes], bf16
one a kind of row, over the layers that keep that kind, addressed by the
same page table: a page id names the same ``page_size`` tokens in every
pool, so one allocator and one prefix cache serve them all
(``row_pool``, ``write_rows``, ``gather_rows``). ``lanes`` is the row's
width rounded up to whole lanes of ``ROW_LANES``, the rest zero: the
chip tiles an array's last axis in lanes of 128 anyway, and a pool whose
last axis is not whole lanes is copied whole at the entry of every
program that scatters into it (a v5e compile of the decode program over
576- and 1,088-wide pools: 3.2 GB of temporaries, none at 640 and 1,152).

Token ``pos`` of a sequence lives at ``[layer, table[pos // page_size],
pos % page_size]``. ``write_kv`` scatters new rows there (an index
outside the pool, which is how a caller names a dead slot or a hole,
writes nothing); ``gather_kv_window`` copies a table's pages back out,
dequantised (``visible_pages`` first cuts the table to what a windowed
layer can see). On the host, ``PageAllocator`` hands out page ids,
``page_hashes`` and ``PrefixCache`` make full prompt pages shareable.
"""

from __future__ import annotations

import hashlib
from collections import OrderedDict
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.ops import scopes


ROW_LANES = 128


def page_attention_scale(head_dim: int) -> float:
    """What BOTH entries of attention over K/V pages multiply a score by
    (``ops/paged_decode_attention.py``, ``ops/paged_prefill_attention.py``,
    kernel and plain alike), and so what the serving engine's programs
    scale by whatever the model: ``head_dim ** -0.5``, the entries' own
    choice and the one place it is stated. A model's plain ``forward``
    hands ``cached_attention`` this number where it means the same
    attention as the engine's. A block whose published scale is another
    folds the ratio of the two into q, in float32 before q's one rounding
    (``models/granite_moe_hybrid.py:attention_projections``), as a block
    with a multiplier on its keys folds it into k
    (``models/falcon_h1.py``)."""
    return head_dim ** -0.5


def heads_per_row(n_kv: int, head_dim: int) -> int:
    """How many consecutive KV heads of ``head_dim`` one row of a K or V
    pool holds (module docstring): two, where the head is half of
    ``ROW_LANES`` (the one narrow head a published model has brought: 64)
    and the heads pair up; else one, the head as it is. (The layout and
    the two functions below hold for any whole fraction of a row; a head
    of 32 or 16 is a test's toy, and stays as the parent had it.)"""
    return 2 if 2 * head_dim == ROW_LANES and n_kv % 2 == 0 else 1


def pool_heads(n_kv: int, head_dim: int) -> tuple:
    """The trailing axes of a K or V pool of ``n_kv`` heads of
    ``head_dim``: (rows a token, their width)."""
    per = heads_per_row(n_kv, head_dim)
    return n_kv // per, head_dim * per


def _own_part(per: int):
    """[KV head of a row, 1, part of the row, 1]: true where the part is
    the KV head's own."""
    return jnp.eye(per, dtype=bool)[:, None, :, None]


def rows_of_heads(q, k, v):
    """q [.., heads, hd], k and v [.., n_kv, hd] as a block's module
    states them -> as the pools and the entries of attention over them
    take them: themselves, where a head fills its row (nothing is traced:
    a program of such a model lowers to the text it had). Else k and v
    reshaped ``per`` heads a row, and each query head laid in its KV
    head's part of a row, zeros in the others, times the ratio of the
    model's scale (``head_dim ** -0.5``) to the one the entries apply to
    a row of that width: one more rounding of q."""
    heads, hd = q.shape[-2:]
    n_kv = k.shape[-2]
    per = heads_per_row(n_kv, hd)
    if per == 1:
        return q, k, v
    lead = q.shape[:-2]
    fold = page_attention_scale(hd) / page_attention_scale(hd * per)
    with jax.named_scope(scopes.ATTN_QKV):
        # [.., row, KV head of the row, query head of the group, part, hd]
        scaled = (q.astype(jnp.float32) * fold).astype(q.dtype)
        laid = jnp.where(_own_part(per), scaled.reshape(
            *lead, n_kv // per, per, heads // n_kv, 1, hd), 0)
        return (laid.reshape(*lead, heads, per * hd),
                k.reshape(*k.shape[:-2], n_kv // per, per * hd),
                v.reshape(*v.shape[:-2], n_kv // per, per * hd))


def own_parts(attn, n_kv: int, head_dim: int):
    """What attention over pools of several heads a row gave for queries
    ``rows_of_heads`` laid out, [.., heads, per x hd] -> each head's own
    part of its row's weighted sum [.., heads, hd]; itself where a head
    fills its row."""
    per = heads_per_row(n_kv, head_dim)
    if per == 1:
        return attn
    *lead, heads, _ = attn.shape
    with jax.named_scope(scopes.ATTN_OUT):
        parts = attn.reshape(*lead, n_kv // per, per, heads // n_kv, per,
                             head_dim)
        # one part a head is kept and the others are zeros: the sum is it
        return jnp.where(_own_part(per), parts, 0).sum(axis=-2).reshape(
            *lead, heads, head_dim)


class PageRow(NamedTuple):
    """One row a token keeps in a page of a layer that keeps no K/V twins
    (a layer plan's run states its rows: ``LayerStack.rows``)."""
    name: str
    width: int
    dtype: str


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


def write_kv(kp, vp, ks, vs, layer, k_new, v_new, pidx, ip, quantized):
    """THE KV write, shared by decode and prefill (shape-generic: decode
    writes one token per slot with [B] indices, prefill a padded suffix
    with [n, T] indices), on the STACKED pools [L, P, page, nkv, hd]
    (+ scale pools in int8 mode) at layer ``layer``: k/v land at
    (layer, pidx, ip), out-of-bounds indices dropping.

    The pools come in whole and go out whole: all that is written is
    the new rows (a scatter, in place on the buffer the layer loop
    carries). Write before any read of the layer's pages, so the reader
    sees the rows just written."""
    with jax.named_scope(scopes.KV_WRITE):
        if quantized:
            kq, ksc = quantize_kv(k_new)
            vq, vsc = quantize_kv(v_new)
            kp = kp.at[layer, pidx, ip].set(kq, mode="drop")
            vp = vp.at[layer, pidx, ip].set(vq, mode="drop")
            ks = ks.at[layer, pidx, ip].set(ksc, mode="drop")
            vs = vs.at[layer, pidx, ip].set(vsc, mode="drop")
        else:
            kp = kp.at[layer, pidx, ip].set(k_new.astype(kp.dtype),
                                            mode="drop")
            vp = vp.at[layer, pidx, ip].set(v_new.astype(vp.dtype),
                                            mode="drop")
    return kp, vp, ks, vs


def gather_kv_window(k_pages, v_pages, k_scale, v_scale, layer, table):
    """Layer ``layer``'s ``table`` page window of every row [B, PB, page,
    nkv, hd], copied out of the stacked pools (holes read page 0; the
    caller's causal limit masks them) and dequantised to bf16 if the pages
    are int8: what the gather formulation and the engine's PREFILL attend
    over."""
    table_c = jnp.maximum(table, 0)
    kg, vg = k_pages[layer, table_c], v_pages[layer, table_c]
    if k_pages.dtype == jnp.int8:
        kg = dequantize_kv(kg, k_scale[layer, table_c])
        vg = dequantize_kv(vg, v_scale[layer, table_c])
    return kg, vg


def row_pool(layers: int, num_pages: int, page_size: int, row: PageRow):
    """An empty pool of ``row`` for ``layers`` layers: [L, P, page,
    lanes], the row's width rounded up to whole lanes."""
    lanes = -(-row.width // ROW_LANES) * ROW_LANES
    return jnp.zeros((layers, num_pages, page_size, lanes), row.dtype)


def write_rows(pool, layer, rows, pidx, ip):
    """``write_kv`` for a pool of one row a token [L, P, page, lanes]:
    ``rows`` ([B, width] with [B] indices, or [n, T, width] with [n, T])
    land at (layer, pidx, ip), zeros in the lanes past their width,
    out-of-bounds indices dropping."""
    spare = pool.shape[-1] - rows.shape[-1]
    with jax.named_scope(scopes.KV_WRITE):
        if spare:
            rows = jnp.pad(rows, [(0, 0)] * (rows.ndim - 1) + [(0, spare)])
        return pool.at[layer, pidx, ip].set(rows.astype(pool.dtype),
                                            mode="drop")


def gather_rows(pool, layer, table):
    """Layer ``layer``'s ``table`` page window of every sequence, as rows
    in key order [B, PB x page, lanes], copied out of a pool of one row a
    token (holes read page 0; the caller's causal limit masks them).
    The lanes past the row's width hold zeros."""
    rows = pool[layer, jnp.maximum(table, 0)]
    return rows.reshape(table.shape[0], -1, pool.shape[-1])


def visible_pages(table, first_key, n_pages: int, page_size: int):
    """The part of each row's page table that a WINDOWED layer's queries
    can see: ``n_pages`` consecutive entries of ``table`` [B, PB] from
    the page that holds key position ``first_key`` [B] (the oldest key of
    the row's first query's window), and the position of their first row
    [B], which ``cached_attention`` takes as ``key_start``. Entries past
    the table's end repeat its last: their positions lie past every query
    and the causal limit masks them. The whole table, from position 0,
    where it is no wider than ``n_pages``."""
    b, pb = table.shape
    if n_pages >= pb:
        return table, jnp.zeros((b,), jnp.int32)
    first_page = jnp.maximum(first_key, 0) // page_size
    cols = first_page[:, None] + jnp.arange(n_pages, dtype=jnp.int32)
    rows = jnp.take_along_axis(table, jnp.minimum(cols, pb - 1), axis=1)
    return rows, (first_page * page_size).astype(jnp.int32)


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
