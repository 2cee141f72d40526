"""Latent attention over paged rows, with a learned selection of keys.

A layer of this kind keeps ONE ROW a token and no K/V twins
(``ops/paged_attention.py`` states the pools): the compressed KV
``ckv`` (``r`` numbers, after its norm) beside one rotary key ``kr``
(``dr`` numbers, already rotated) that every head shares. A head's key
and value are an expansion of the row, ``kn | v = ckv @ wkv_b`` ([r, H,
dn + dv]), and its score on a key is ``(qn . kn + qr . kr) x scale``
(DeepSeek-V2's multi-head latent attention, arXiv:2405.04434). Two forms
give the same numbers:

- EXPANDED (``latent_prefill_attention``): the rows a block of queries
  can see are gathered and expanded into every head's key and value, and
  plain causal softmax attention runs over them. Right for a prefill's
  many queries: the expansion is paid once a block of queries;
- ABSORBED (``latent_decode_attention``): the query takes the expansion
  instead, ``q~ = qn @ wkv_b[K]^T`` in R^r, scores run against the rows
  as they lie, ``(q~ . ckv + qr . kr) x scale``, the probabilities weigh
  the rows themselves and the value half of the expansion comes last,
  ``o = (sum p ckv) @ wkv_b[V]``. Right for a decode step's one query a
  slot: nothing of size keys x heads x width exists.

A layer with an INDEXER (``IndexInputs``; DeepSeek-V3.2's sparse
attention) keeps a second, narrow row a token, the index key. A query
scores every key it may see, ``I(t, s) = sum_j w_j relu(qI_j(t) .
kI(s))`` over the indexer's heads, in float32 (the rows are bf16, so
their products are exact and the sums float32: a choice must not turn on
a rounding), and its softmax runs over the ``topk`` keys of largest
``I`` alone, ties to the lower position; a query that sees no more than
``topk`` keys attends over them all. The decode form gathers just the
chosen rows (``select_keys``); the prefill form masks.

A WINDOWED layer's queries see the ``window`` newest keys, their own
among them; both forms gather only the pages that hold them
(``visible_pages``).

Plain ``jax.numpy`` and ``lax``: no Pallas kernel. A prefill's float32
scores, the layer's and its indexer's, go over blocks of queries under
``SCORES_MAX_BYTES``. The serving engine (``serve/paged_llm.py``) calls
the three functions at the bottom from its two programs; what they take
of a block is ``LatentInputs``, which the model's module builds."""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from ray_tpu.ops.paged_attention import (gather_rows, visible_pages,
                                         write_rows)

# the most a prefill's float32 scores of one block of queries may take,
# the layer's [n, H, block, keys] and its indexer's [n, HI, block, keys]
# together (the plain KV prefill's limit: ops/paged_prefill_attention.py)
SCORES_MAX_BYTES = 1 << 30
# a full layer's blocks of queries go in this many groups at most, each
# over the keys its last block can see and no further
KEY_GROUPS = 4
_MASKED = float(jnp.finfo(jnp.float32).min)


class IndexInputs(NamedTuple):
    """What a layer's indexer takes of its tokens."""
    q: object         # [b, s, HI, dI]: the tokens' index queries
    weights: object   # [b, s, HI] float32: the indexer heads' weights
    key: object       # [b, s, dI]: what each token keeps, the index key
    topk: int         # keys a query attends over, at most


class LatentInputs(NamedTuple):
    """What a latent-attention layer takes of its tokens (built by the
    model's ``latent_projections``)."""
    q: object         # [b, s, H, dn + dr]: qn | qr, qr rotated
    row: object       # [b, s, r + dr]: what each token keeps, ckv | kr
    wkv_b: object     # [r, H, dn + dv]: a latent's keys | values, by head
    scale: float      # on the scores
    index: IndexInputs | None = None


def write_latent(inputs: LatentInputs, pools: tuple, layer, pidx, ip):
    """The tokens' rows into the run's pools (the latent rows' and, for a
    layer with an indexer, the index keys') at (layer, pidx, ip):
    ``inputs`` over one token a slot ([B, 1, ...], indices [B]) or a
    padded suffix ([n, T, ...], indices [n, T])."""
    squeeze = (lambda a: a[:, 0]) if pidx.ndim == 1 else (lambda a: a)
    out = (write_rows(pools[0], layer, squeeze(inputs.row), pidx, ip),)
    if inputs.index is not None:
        out += (write_rows(pools[1], layer, squeeze(inputs.index.key),
                           pidx, ip),)
    return out


def index_scores(q, weights, keys):
    """``I = sum_j w_j relu(q_j . k)``: q [B, T, HI, dI], weights [B, T,
    HI], keys [B, S, dI or its whole lanes] -> [B, T, S] float32."""
    dots = jnp.einsum("bthd,bsd->bhts", q, keys[..., :q.shape[-1]],
                      preferred_element_type=jnp.float32)
    w = jnp.moveaxis(weights.astype(jnp.float32), -1, 1)[..., None]
    return jnp.sum(w * jax.nn.relu(dots), axis=1)


def select_keys(index: IndexInputs, index_pool, layer, table, count):
    """The positions of the ``topk`` keys a decode step's query attends
    over, for every slot: (positions [B, topk] int32, chosen [B, topk]
    bool: false where the slot has fewer keys than ``topk``), from the
    index keys of the slot's pages. ``count`` [B]: the keys the query
    sees (its own, written already, among them). None where the table
    holds no more than ``topk`` keys: then nothing is dropped."""
    keys = gather_rows(index_pool, layer, table)               # [B, S, dI]
    if keys.shape[1] <= index.topk:
        return None
    scores = index_scores(index.q, index.weights, keys)[:, 0]    # [B, S]
    seen = jnp.arange(keys.shape[1], dtype=jnp.int32) < count[:, None]
    values, positions = jax.lax.top_k(
        jnp.where(seen, scores, _MASKED), index.topk)
    # a key past the slot's count comes out with the mask's own value (a
    # gather of ``seen`` at the positions says the same, a scalar at a
    # time: 1.3 ms a layer on a v5e at 64 x 2,048)
    return positions, values > _MASKED


def _absorbed(inputs: LatentInputs, rows, mask):
    """One query a slot over ``rows`` [B, S, lanes] (``r + dr`` numbers,
    then zeros) where ``mask`` [B, S] lets it: the absorbed form.
    Returns [B, H, dv]."""
    r = inputs.wkv_b.shape[0]
    dn = inputs.q.shape[-1] - (inputs.row.shape[-1] - r)
    q = inputs.q[:, 0]                                   # [B, H, dn + dr]
    wk, wv = inputs.wkv_b[..., :dn], inputs.wkv_b[..., dn:]
    q_latent = jnp.einsum("bhn,rhn->bhr", q[..., :dn], wk,
                          preferred_element_type=jnp.float32)
    # the query in the row's own layout, zeros against its spare lanes:
    # the rows are contracted as they lie, never sliced
    q_row = jnp.concatenate(
        [q_latent.astype(q.dtype), q[..., dn:],
         jnp.zeros((*q.shape[:2], rows.shape[-1] - inputs.row.shape[-1]),
                   q.dtype)], -1)
    scores = jnp.einsum("bhw,bsw->bhs", q_row, rows,
                        preferred_element_type=jnp.float32) * inputs.scale
    scores = jnp.where(mask[:, None, :], scores, _MASKED)
    probs = jax.nn.softmax(scores, axis=-1)
    o_latent = jnp.einsum("bhs,bsr->bhr", probs.astype(rows.dtype),
                          rows[..., :r], preferred_element_type=jnp.float32)
    return jnp.einsum("bhr,rhv->bhv", o_latent.astype(q.dtype), wv,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def latent_decode_attention(inputs: LatentInputs, pools: tuple, layer,
                            table, pos, *, window=None):
    """A decode step's attention, one query a slot at position ``pos``
    [B], over the slot's rows where they lie in ``pools`` (the run's:
    latent rows, then index keys where the layer has an indexer) through
    the page table [B, PB], the step's own row written already. A layer
    with an indexer reads its slots' index keys, then the chosen latent
    rows alone; a windowed layer the pages of its window. Returns [B, H,
    dv]. A dead slot (the caller gives it position 0) reads one row of
    whatever page its table names and its result is dropped."""
    pool = pools[0]
    page = pool.shape[2]
    chosen = None
    if inputs.index is not None:
        chosen = select_keys(inputs.index, pools[1], layer, table, pos + 1)
    if chosen is not None:
        positions, mask = chosen
        # each position's page id: the table's entry at its page, picked
        # by comparison (a gather of 2,048 scalars a slot out of the
        # table takes 1.0 ms a layer on a v5e; this a few microseconds)
        at = (positions // page)[..., None] == jnp.arange(
            table.shape[1], dtype=jnp.int32)
        pages = jnp.sum(jnp.where(at, jnp.maximum(table, 0)[:, None, :], 0),
                        axis=-1)
        rows = pool[layer, pages, positions % page]       # [B, topk, w]
    else:
        key_start = jnp.zeros_like(pos)
        if window is not None:
            table, key_start = visible_pages(
                table, pos - window + 1, -(-(page + window - 1) // page),
                page)
        rows = gather_rows(pool, layer, table)               # [B, S, w]
        kpos = key_start[:, None] + jnp.arange(rows.shape[1],
                                               dtype=jnp.int32)
        mask = kpos <= pos[:, None]
        if window is not None:
            mask = mask & (kpos > pos[:, None] - window)
    return _absorbed(inputs, rows, mask)


def query_block(n: int, t: int, heads: int, keys: int, window) -> int:
    """How many of a prefill's ``t`` queries a row attend at once: a
    windowed layer's in blocks of about its window (a block then gathers
    two windows' pages, not the table), a full layer's all where its
    float32 scores over ``heads`` (its own and its indexer's) fit
    ``SCORES_MAX_BYTES``, else in blocks that do: ``t`` halved as often
    as that takes (a power-of-two bucket halves evenly)."""
    block = t
    if window is not None:
        while block > 16 and block >= 2 * window and block % 2 == 0:
            block //= 2
        keys = block + window
    while (block > 16 and block % 2 == 0
           and 4 * n * heads * block * keys > SCORES_MAX_BYTES):
        block //= 2
    return block


def latent_prefill_attention(inputs: LatentInputs, pools: tuple, layer,
                             table_rows, starts, *, window=None):
    """A prefill's attention: queries ``inputs.q`` [n, T, H, dn + dr],
    row i's first at position ``starts[i]``, over the rows' pages
    (``table_rows`` [n, PB]) in the expanded form, the suffixes' own
    rows written already, so a suffix's queries see a reused prefix's
    rows exactly as the prompt that wrote them left them. A layer with
    an indexer scores a block's queries against the rows' index keys and
    masks every key outside a query's ``topk`` (a block that sees no more
    than ``topk`` keys drops none). Returns [n, T, H, dv]."""
    pool = pools[0]
    n, t, heads, _ = inputs.q.shape
    page, r = pool.shape[2], inputs.wkv_b.shape[0]
    dr = inputs.row.shape[-1] - r
    dn = inputs.q.shape[-1] - dr
    index = inputs.index
    keys = table_rows.shape[1] * page
    if index is not None and keys <= index.topk:
        index = None                    # no query can see more than topk
    block = query_block(
        n, t, heads + (index.q.shape[2] if index is not None else 0), keys,
        window)
    # the pages that hold the keys of ``block`` queries' windows
    seen = (table_rows.shape[1] if window is None
            else -(-(block + window - 2) // page) + 1)

    def expand(table):
        """What ``table``'s pages hold, by key: the rotary keys [n, S, dr],
        every head's no-position keys [n, H, S, dn] and values [n, H, S,
        dv] (heads before keys: the layout the two products below
        contract in) and, where the layer selects, the index keys."""
        rows = gather_rows(pool, layer, table)

        def heads_of(w):
            return jnp.einsum("nsr,rhe->nhse", rows[..., :r], w,
                              preferred_element_type=jnp.float32
                              ).astype(inputs.q.dtype)

        return (rows[..., r:r + dr], heads_of(inputs.wkv_b[..., :dn]),
                heads_of(inputs.wkv_b[..., dn:]),
                gather_rows(pools[1], layer, table) if index is not None
                else None)

    def attend(q, iq, iw, first, seen_keys=None):
        """``q`` [n, block, H, dn + dr], the first of them at ``first``
        [n]; ``iq``, ``iw``: their index queries and weights, or None;
        ``seen_keys``: what ``expand`` gave for the keys from position 0
        that they can see, or None for a windowed layer, whose block
        expands the pages of its own windows."""
        key_start = jnp.zeros_like(first)
        if seen_keys is None:
            table, key_start = visible_pages(
                table_rows, first - window + 1, seen, page)
            seen_keys = expand(table)
        kr, kn, v, index_keys = seen_keys
        scores = (jnp.einsum("nthd,nhsd->nhts", q[..., :dn], kn,
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("nthd,nsd->nhts", q[..., dn:], kr,
                               preferred_element_type=jnp.float32)
                  ) * inputs.scale
        qpos = first[:, None] + jnp.arange(q.shape[1], dtype=jnp.int32)
        kpos = key_start[:, None] + jnp.arange(kr.shape[1], dtype=jnp.int32)
        mask = kpos[:, None, :] <= qpos[:, :, None]          # [n, block, S]
        if window is not None:
            mask = mask & (kpos[:, None, :] > qpos[:, :, None] - window)
        if iq is not None and kr.shape[1] > index.topk:
            chosen = jnp.where(mask, index_scores(iq, iw, index_keys),
                               _MASKED)
            # the topk-th largest score; every key above it, and of
            # those AT it the lowest positions that fill the count
            kth = jax.lax.top_k(chosen, index.topk)[0][..., -1:]
            above, ties = chosen > kth, chosen == kth
            room = index.topk - jnp.sum(above, axis=-1, keepdims=True)
            mask = mask & (above | (ties & (
                jnp.cumsum(ties, axis=-1, dtype=jnp.int32) <= room)))
        scores = jnp.where(mask[:, None], scores, _MASKED)
        probs = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("nhts,nhsv->nthv", probs.astype(q.dtype), v,
                          preferred_element_type=jnp.float32).astype(q.dtype)

    iq, iw = (index.q, index.weights) if index is not None else (None, None)
    # a full layer's queries all see the same rows: expanded once, outside
    # the blocks; a windowed layer's blocks each expand what they can see
    whole = expand(table_rows) if window is None else None
    if block == t:
        return attend(inputs.q, iq, iw, starts, whole)
    count = t // block
    firsts = starts[None, :] + block * jnp.arange(
        count, dtype=jnp.int32)[:, None]                     # [blocks, n]

    def blocks(a):
        return jnp.moveaxis(a.reshape(n, count, block, *a.shape[2:]), 1, 0)

    xs = (blocks(inputs.q),) + (
        (blocks(iq), blocks(iw)) if index is not None else ()) + (firsts,)

    def some(lo, hi, seen_keys):
        """Blocks ``lo`` to ``hi``, one after another."""
        return jax.lax.map(
            lambda xs: attend(xs[0], *(xs[1:-1] or (None, None)), xs[-1],
                              seen_keys),
            jax.tree.map(lambda a: a[lo:hi], xs))

    def grouped():
        # where the table reaches past the last (padded) query, the
        # queries of block i see no key past ``keys - (count - 1 - i) x
        # block``: the blocks go in up to ``KEY_GROUPS`` groups, each
        # over the keys its last block can see (a cold prompt's first
        # quarter attends over a quarter of the keys, not all of them)
        groups, out = min(KEY_GROUPS, count), []
        for g in range(groups):
            lo, hi = g * count // groups, (g + 1) * count // groups
            extent = keys - (count - hi) * block
            out.append(some(lo, hi, jax.tree.map(
                lambda a: a[..., :extent, :], whole)))
        return jnp.concatenate(out)

    if window is not None:
        out = some(0, count, None)
    else:
        # (a suffix whose padding runs past its table, ``starts + t >
        # keys``, gives no such bound: every block over every key)
        out = jax.lax.cond(jnp.all(starts + t <= keys), grouped,
                           lambda: some(0, count, whole))
    return jnp.moveaxis(out, 0, 1).reshape(n, t, heads, -1)
