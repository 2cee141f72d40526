"""A learned selection of keys: the indexer that a sparse-attention layer
keeps beside its cache rows (DeepSeek-V3.2's sparse attention), for
whatever those rows are: a latent row (``ops/latent_attention.py``) or
the K/V twins (``ops/paged_decode_attention.py``,
``ops/paged_prefill_attention.py``). Both kinds of layer import what is
here; nothing of it knows which one calls.

A layer with an INDEXER (``IndexInputs``) keeps one narrow row a token
more, the index key, in a pool of its own (``ops/paged_attention.py:
row_pool``: the key's width in whole lanes, the rest zero). A query
scores every key it may see, ``I(t, s) = sum_j w_j relu(qI_j(t) .
kI(s))`` over the indexer's heads, in float32 (the rows are bf16, so
their products are exact and the sums float32: a choice must not turn on
a rounding), and its softmax runs over the ``topk`` keys of largest
``I`` alone, ties to the lower position; a query that sees no more than
``topk`` keys attends over them all (``kept``: one rule, every form).

``kept`` makes the set ``lax.top_k`` would return and sorts no row for
it (on a v5e ``lax.top_k`` is a whole ``sort`` of every row, 91 stages
over 8,192 scores and their positions to learn one value and one
position): the ``topk``-th largest score is found by an exact THRESHOLD
SEARCH over the scores' float32 bit patterns, a bit a pass, and the
ties at it are filled by position (``_searched``): plain ``jax.numpy``
on every platform, each pass one fused compare and count over the rows.

A DECODE step's index scores (``decode_index_scores``) have two
formulations, chosen by the platform a program is lowered for
(``jax.lax.platform_dependent``) and by static shapes
(``index_kernel_engages``: wherever the layer selects, pages in whole
lanes), nothing else:

- IN PLACE, a Pallas kernel (``index_decode_scores``): the index keys'
  pool stays in HBM and each live slot's pages are walked (``walk``,
  which the latent rows' kernel shares), a group of keys ``[g x page,
  lanes]`` against the slot's index queries ``[HI, lanes]`` (zeros
  against a narrow key's spare lanes: the rows are contracted as they
  lie) on the MXU in float32, ``relu``, the heads' weighted sum:
  ``index_scores``' arithmetic (exact products, float32 sums), written
  as ``[slots, keys]`` float32 with the mask's value from the slot's
  count on, in the pages it never fetched too;
- GATHERED (``_scored_gathered``: what the kernel is held to): every
  page of the table copied out of the pool, for every slot, and
  ``index_scores`` over the copy (at ``serve-note-gen``'s shapes 134 MB
  written and read again a layer where the slots hold 65 MB of keys:
  0.644 ms against the kernel's 0.165 on a v5e, PR 53).

A PREFILL's selection (``selection_flags``) is flags a query and key
([n, T, S], one byte a pair): ``index_scores`` in ``jax.numpy``, in
float32, and ``kept`` over them, over blocks of queries whose index
scores fit ``SCORES_MAX_BYTES`` (``query_block``, ``over_blocks``). A
prefill kernel takes the flags and computes no index score, no top-k
and no tie; a plain formulation takes them as a mask."""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import scopes
from ray_tpu.ops.paged_attention import ROW_LANES, gather_rows

# the most a prefill's float32 scores of one block of queries may take,
# the layer's [n, H, block, keys] and its indexer's [n, HI, block, keys]
# together (the plain KV prefill's limit: ops/paged_prefill_attention.py)
SCORES_MAX_BYTES = 1 << 30
# a full layer's blocks of queries go in this many groups at most, each
# over the keys its last block can see and no further
KEY_GROUPS = 4
# an index score of a key the query may not see
MASKED = float(jnp.finfo(jnp.float32).min)


class IndexInputs(NamedTuple):
    """What a layer's indexer takes of its tokens."""
    q: object         # [b, s, HI, dI]: the tokens' index queries
    weights: object   # [b, s, HI] float32: the indexer heads' weights
    key: object       # [b, s, dI]: what each token keeps, the index key
    topk: int         # keys a query attends over, at most


def index_scores(q, weights, keys):
    """``I = sum_j w_j relu(q_j . k)``: q [B, T, HI, dI], weights [B, T,
    HI], keys [B, S, dI or its whole lanes] -> [B, T, S] float32."""
    with jax.named_scope(scopes.INDEX_SELECT):
        dots = jnp.einsum("bthd,bsd->bhts", q, keys[..., :q.shape[-1]],
                          preferred_element_type=jnp.float32)
        w = jnp.moveaxis(weights.astype(jnp.float32), -1, 1)[..., None]
        return jnp.sum(w * jax.nn.relu(dots), axis=1)


def _ordered(scores):
    """float32 -> int32 keys whose signed order is the order ``lax.top_k``
    sorts by: the floats' total order (``-0.0`` below ``+0.0``), the bit
    pattern with its low 31 bits flipped where the sign is set."""
    bits = lax.bitcast_convert_type(scores, jnp.int32)
    return bits ^ ((bits >> 31) & 0x7FFFFFFF)


def _searched(keys, topk: int):
    """The search: of ``keys`` [R, S] int32, each row's ``topk``-th
    largest [R, 1] and the position [R, 1] of the last key AT it that a
    stable descending sort's first ``topk`` hold. No row is sorted: the
    threshold is the largest ``t`` with ``topk`` keys at or above it,
    found a bit a pass from the sign down (32 passes, each a compare and
    a count along the row), and the position the ``need``-th of the keys
    equal to ``t`` (``need``: what the keys above ``t`` leave of the
    count), found the same way over the positions' bits, in the rows
    that have a tie to spare (where none has, which is what scores that
    differ give, the passes are not made: every tie is taken, up to the
    row's end). On a v5e, ``scripts/sweep_kept.py``, PR 61: a prefill's
    block of 2,048 queries over 8,192 keys 0.87-0.97 ms (XLA keeps the
    block on the core across the passes) where the sort took 3.7 in the
    serving cell, a decode step's 32 rows of 8,192 0.063 against 0.19;
    the same passes as a Pallas kernel over blocks of rows took 0.59 and
    0.05 alone and, in the cell, served tokens that were not the
    sort's on the same seed (PERF.md, Findings, PR 61): this form's
    are."""
    width = keys.shape[-1]

    def count(which):
        return jnp.sum(which.astype(jnp.int32), axis=-1, keepdims=True)

    def raised(held, bit, holds):
        """``held`` with ``bit`` set in the rows where the higher value
        still ``holds``."""
        higher = held | bit
        return jnp.where(holds(higher), higher, held)

    def at_least(t):
        return count(keys >= t) >= topk

    lowest = jnp.full((keys.shape[0], 1), jnp.iinfo(jnp.int32).min)
    # the sign first: clearing it is the one step up that sets no bit
    t = jnp.where(at_least(jnp.zeros_like(lowest)), 0, lowest)
    t = lax.fori_loop(
        0, 31, lambda i, t: raised(t, jnp.int32(1) << (30 - i), at_least), t)
    need = topk - count(keys > t)
    tied = keys == t
    spare = count(tied) > need

    def nth():
        """The largest position with fewer than ``need`` ties below it:
        the ``need``-th tie's own."""
        ties = jnp.where(
            tied, lax.broadcasted_iota(jnp.int32, keys.shape, 1), width)
        bits = max(width - 1, 1).bit_length()
        return lax.fori_loop(
            0, bits, lambda i, last: raised(
                last, jnp.int32(1) << (bits - 1 - i),
                lambda p: count(ties < p) < need), jnp.zeros_like(lowest))

    # (scores that differ leave no row a tie to spare, and no pass to make)
    end = jnp.full_like(lowest, width - 1)
    last = lax.cond(jnp.max(spare.astype(jnp.int32)) > 0, nth, lambda: end)
    return t, jnp.where(spare, last, end)


def kept(chosen, topk: int):
    """Which keys a query's softmax runs over, from its index scores
    ``chosen`` [..., S] float32 (``MASKED`` where it may not see the
    key): every key above the ``topk``-th largest score and, of those AT
    it, the lowest positions that fill the count: the set ``lax.top_k``
    returns, key for key, as a mask, and no row sorted for it
    (``_searched``: a threshold search over the scores' bit patterns in
    ``lax.top_k``'s own order, the ties at the threshold filled by
    position). The caller ANDs it with what the query may see: where
    that is no more than ``topk`` keys the ``topk``-th score is the
    mask's own and none is dropped."""
    with jax.named_scope(scopes.INDEX_SELECT):
        keys = _ordered(chosen)
        kth, last = _searched(keys.reshape(-1, keys.shape[-1]), topk)
        lead = (*chosen.shape[:-1], 1)
        at = jnp.arange(keys.shape[-1], dtype=jnp.int32)
        kth, last = kth.reshape(lead), last.reshape(lead)
        return (keys > kth) | ((keys == kth) & (at <= last))


# ---------------------------------------------------------------------------
# Decode: a step's index scores, in place or gathered
# ---------------------------------------------------------------------------

INDEX_KERNEL_NAME = "index_decode_scores"
BUFFERS = 2     # a walk's page buffers: one computed on, one in flight
# pages a step of the index kernel's walk: 32 KB each at the serving
# cell's 128 keys of 128 bf16 numbers, a fifth of a latent page (a full
# layer at 64 slots of 3.0-5.1k keys, 64 index heads, on a v5e, the
# kernel alone, ``scripts/sweep_index_kernel.py``, PR 53: 0.251 ms by
# fours, 0.187 by eights, 0.165 by sixteens, 0.160 by thirty-twos, 0.166
# the whole 64-page table at once; the keys' bytes over the bandwidth are
# 0.081 and the gathered formulation takes 0.644)
_INDEX_GROUP = 16


def index_kernel_engages(page: int, table_pages: int, topk,
                         width: int) -> bool:
    """The rule, from static shapes alone: whether a decode step's index
    scores of a layer that keeps ``topk`` keys (None: it has no indexer),
    over a table of ``table_pages`` pages of ``page`` index keys of
    ``width`` numbers, are the index kernel's on a program lowered for a
    TPU: wherever the layer selects (the table holds more than ``topk``
    keys), pages and keys in whole lanes (a key is then its pool's whole
    row)."""
    return (topk is not None and page % ROW_LANES == 0
            and width % ROW_LANES == 0 and topk < page * table_pages)


def walk(layer_ref, table_ref, count_ref, next_ref,          # SMEM
          pool_hbm, buf, sem, step_ref, *, pages_per_slot, group, body,
          carry=()):
    """This grid step's slot's live pages of ``pool_hbm``'s layer, through
    the page table, ``group`` at a step of the walk: ONE async copy a
    page into ``buf`` [2, group x page, lanes] under ``sem`` [2, group],
    double buffered, the next group's copies (of this slot or, behind
    its last, of the next live one's first) in flight while ``body(g,
    rows, *carry)`` computes on group ``g``'s rows [group x page, lanes]
    (of its last group, the pages past the slot's last are not fetched:
    what the buffer holds there is the caller's to ignore) and returns
    the next ``carry``. ``step_ref``: the groups walked so far (which
    buffer is next), kept across the grid's steps as the buffers are.
    Returns the last carry."""
    slot, slots = pl.program_id(0), pl.num_programs(0)
    page = pool_hbm.shape[2]
    layer = layer_ref[0]

    def pages_of(slot):
        # never past the table's row (the gathered formulation ends there
        # too; the engine's reservations keep counts inside; the chain
        # ends at ``slots``, which is no slot: read the last)
        count = count_ref[jnp.minimum(slot, slots - 1)]
        return jnp.minimum((count + page - 1) // page, pages_per_slot)

    def copies(slot, g, b, do):
        """``do`` to the copy of each page of the slot's group ``g`` that
        the slot holds, into (or in) buffer ``b``."""
        first = g * group

        def one(j, _):
            p = table_ref[slot * pages_per_slot + first + j]
            do(pltpu.make_async_copy(
                pool_hbm.at[layer, p],
                buf.at[b, pl.ds(pl.multiple_of(j * page, page), page)],
                sem.at[b, j]))

        lax.fori_loop(0, jnp.minimum(group, pages_of(slot) - first), one,
                      None)

    @pl.when(slot == 0)
    def _():
        step_ref[0] = 0
        first = next_ref[0]

        @pl.when(first < slots)
        def _():
            copies(first, 0, 0, lambda c: c.start())

    n_groups = (pages_of(slot) + group - 1) // group

    def group_body(g, carry):
        *carry, step = carry
        b = step % BUFFERS
        more = g + 1 < n_groups
        nslot = jnp.where(more, slot, next_ref[slot + 1])

        @pl.when(nslot < slots)
        def _():
            copies(nslot, jnp.where(more, g + 1, 0), (step + 1) % BUFFERS,
                   lambda c: c.start())

        copies(slot, g, b, lambda c: c.wait())
        return (*body(g, buf[b], *carry), step + 1)

    *carry, step = lax.fori_loop(0, n_groups, group_body,
                                 (*carry, step_ref[0]))
    step_ref[0] = step
    return carry


def of_slot(*block):
    """A grid step's block of an array whose first axis is the slots."""
    return pl.BlockSpec((1, *block), lambda s, *_: (s, 0, 0))


def walked(table, count):
    """What ``walk`` takes through scalar prefetch beside the layer: the
    page table [B, PB] in one row, holes as page 0 (what ``gather_rows``
    reads there); the slots' key counts [B]; and the next-live-slot chain
    [B + 1]: its first entry the first slot with keys, entry s + 1 the
    first after s (``B`` when there is none)."""
    slots = count.shape[0]
    live_at = jnp.where(count > 0, jnp.arange(slots, dtype=jnp.int32),
                        slots)
    next_live = jnp.concatenate([lax.cummin(live_at, reverse=True),
                                 jnp.full((1,), slots, jnp.int32)])
    return (jnp.maximum(table, 0).astype(jnp.int32).reshape(-1),
            count.astype(jnp.int32), next_live)


def _index_kernel(layer_ref, table_ref, count_ref, next_ref,      # SMEM
                  q_ref, w_ref, pool_hbm, o_ref, buf, sem, step_ref, *,
                  pages_per_slot, group):
    """One grid step a slot: its index queries ``q_ref`` [1, HI, dI],
    their weights ``w_ref`` [1, HI, 1] float32, its scores ``o_ref`` [1,
    PB / group, group x page] float32, a row a group of the walk; the
    page buffers, their semaphores and ``step_ref``: ``walk``'s."""
    q, w = q_ref[0], w_ref[0]
    count = count_ref[pl.program_id(0)]
    keys = o_ref.shape[2]
    # the groups the walk never reaches (and a dead slot's all)
    o_ref[...] = jnp.full_like(o_ref, MASKED)

    def group_body(g, rows):
        # ``index_scores``' arithmetic: exact products, float32 sums
        dots = lax.dot_general(q, rows[:, :q.shape[1]],
                               (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)
        scores = jnp.sum(w * jnp.maximum(dots, 0.0), axis=0, keepdims=True)
        at = g * keys + lax.broadcasted_iota(jnp.int32, (1, keys), 1)
        # (past the count: pages not fetched, whatever the buffer held)
        o_ref[0, pl.ds(g, 1), :] = jnp.where(at < count, scores, MASKED)
        return ()

    walk(layer_ref, table_ref, count_ref, next_ref, pool_hbm, buf, sem,
          step_ref, pages_per_slot=pages_per_slot, group=group,
          body=group_body)


@functools.partial(jax.jit, static_argnames=("interpret",))
def index_decode_scores_kernel(q, weights, pool, layer, table, count, *,
                               interpret=False):
    """The index kernel's launch: ``q`` [B, HI, dI], a step's index
    queries; ``weights`` [B, HI] float32; ``pool`` [L, P, page, dI or
    its whole lanes]: the run's index keys; ``table`` [B, PB] page ids
    (-1 = hole); ``count`` [B]: the keys a slot's query sees, 0 for a
    dead slot. Returns ``I`` [B, PB x page] float32, ``MASKED`` from the
    slot's count on."""
    slots, heads, width = q.shape
    page, pb = pool.shape[2], table.shape[1]
    group = math.gcd(pb, _INDEX_GROUP)

    out = pl.pallas_call(
        functools.partial(_index_kernel, pages_per_slot=pb, group=group),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(slots,),
            in_specs=[of_slot(heads, width), of_slot(heads, 1),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=of_slot(pb // group, group * page),
            scratch_shapes=[
                pltpu.VMEM((BUFFERS, group * page, pool.shape[3]),
                           pool.dtype),
                pltpu.SemaphoreType.DMA((BUFFERS, group)),
                pltpu.SMEM((1,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((slots, pb // group, group * page),
                                       jnp.float32),
        # the slots in order on one core, as the latent kernel's
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret, name=INDEX_KERNEL_NAME,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), *walked(table, count),
      q, weights.astype(jnp.float32)[..., None], pool)
    return out.reshape(slots, pb * page)


def _scored_in_place(q, weights, pool, layer, table, count):
    """The index kernel's formulation; arguments as ``_scored_gathered``'s.
    A key narrower than its pool's lanes (64 numbers in a row of 128)
    meets queries with zeros against the row's spare lanes, which hold
    zeros too: the rows are contracted as they lie, never sliced."""
    with jax.named_scope(scopes.INDEX_SELECT):
        q = q[:, 0]
        spare = pool.shape[-1] - q.shape[-1]
        if spare:
            q = jnp.pad(q, ((0, 0), (0, 0), (0, spare)))
        return index_decode_scores_kernel(q, weights[:, 0], pool, layer,
                                          table, count)


def _scored_gathered(q, weights, pool, layer, table, count):
    """The plain formulation of a step's index scores (what the index
    kernel is held to): ``q`` [B, 1, HI, dI] and ``weights`` [B, 1, HI]
    against every key of the table's pages, copied out of ``pool``; [B,
    PB x page] float32, ``MASKED`` from the slot's ``count`` on."""
    with jax.named_scope(scopes.INDEX_SELECT):
        keys = gather_rows(pool, layer, table)                    # [B, S, dI]
        scores = index_scores(q, weights, keys)[:, 0]
        seen = jnp.arange(keys.shape[1], dtype=jnp.int32) < count[:, None]
        return jnp.where(seen, scores, MASKED)


def decode_index_scores(index: IndexInputs, pool, layer, table, count):
    """A decode step's index scores: ``index`` of one token a slot (``q``
    [B, 1, HI, dI], ``weights`` [B, 1, HI]) against the slots' index keys
    where they lie in ``pool`` [L, P, page, lanes] through the page table
    [B, PB]; ``count`` [B]: the keys a slot's query sees, 0 for a dead
    slot. Returns ``I`` [B, PB x page] float32, ``MASKED`` from the
    slot's count on: what ``kept`` takes. The index kernel on a program
    lowered for a TPU where ``index_kernel_engages``, the gather
    everywhere else (module docstring)."""
    scored = (index.q, index.weights, pool, layer, table, count)
    if index_kernel_engages(pool.shape[2], table.shape[1], index.topk,
                            pool.shape[-1]):
        return lax.platform_dependent(*scored, tpu=_scored_in_place,
                                      default=_scored_gathered)
    return _scored_gathered(*scored)


def decode_selection(index: IndexInputs, pool, layer, table, count):
    """[B, PB x page] bool: the keys each slot's query attends over, of
    the ``count`` [B] it sees (``decode_index_scores``, then ``kept``);
    None where the table holds no more than ``topk`` keys: nothing is
    dropped, and nothing is scored."""
    if table.shape[1] * pool.shape[2] <= index.topk:
        return None
    chosen = decode_index_scores(index, pool, layer, table, count)
    with jax.named_scope(scopes.INDEX_SELECT):
        return (chosen > MASKED) & kept(chosen, index.topk)


# ---------------------------------------------------------------------------
# Prefill: the selection as flags, over blocks of queries
# ---------------------------------------------------------------------------

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


def over_blocks(fn, xs, starts, by_key, *, block, grouped):
    """``fn(*x, first, seen)`` for every block of ``block`` queries of
    ``xs`` (arrays [n, t, ...]; ``first`` [n]: the block's first position,
    from ``starts``), one block after another, their results [n, block,
    ...] side by side as [n, t, ...]. ``seen``: ``by_key`` (arrays whose
    last axis but one is the table's key positions from 0), whole unless
    ``grouped``: where the table reaches past the last (padded) query,
    the queries of block i see no key past ``keys - (count - 1 - i) x
    block``, and the blocks go in up to ``KEY_GROUPS`` groups, each over
    the keys its last block can see and no further (a cold prompt's first
    quarter attends over a quarter of the keys, not all of them)."""
    n, t = xs[0].shape[:2]
    if block == t:
        return fn(*xs, starts, by_key)
    count = t // block
    firsts = starts[None, :] + block * jnp.arange(
        count, dtype=jnp.int32)[:, None]                     # [blocks, n]
    xs = tuple(jnp.moveaxis(a.reshape(n, count, block, *a.shape[2:]), 1, 0)
               for a in xs) + (firsts,)

    def some(lo, hi, seen):
        """Blocks ``lo`` to ``hi``, one after another."""
        return jax.lax.map(lambda xs: fn(*xs, seen),
                           jax.tree.map(lambda a: a[lo:hi], xs))

    def in_groups():
        groups, out = min(KEY_GROUPS, count), []
        for g in range(groups):
            lo, hi = g * count // groups, (g + 1) * count // groups
            extent = keys - (count - hi) * block
            out.append(some(lo, hi, jax.tree.map(
                lambda a: a[..., :extent, :], by_key)))
        return jnp.concatenate(out)

    if not grouped:
        out = some(0, count, by_key)
    else:
        # (a suffix whose padding runs past its table, ``starts + t >
        # keys``, gives no such bound: every block over every key)
        keys = jax.tree.leaves(by_key)[0].shape[-2]
        out = jax.lax.cond(jnp.all(starts + t <= keys), in_groups,
                           lambda: some(0, count, by_key))
    return jnp.moveaxis(out, 0, 1).reshape(n, t, *out.shape[3:])


def causal(first, block: int, key_start, extent: int, window):
    """[n, block, extent] bool: which of ``extent`` keys from position
    ``key_start`` [n] each of a block's queries from ``first`` [n] may
    see: those up to its own, under a ``window`` the newest alone."""
    qpos = first[:, None] + jnp.arange(block, dtype=jnp.int32)
    kpos = key_start[:, None] + jnp.arange(extent, dtype=jnp.int32)
    mask = kpos[:, None, :] <= qpos[:, :, None]
    if window is not None:
        mask = mask & (kpos[:, None, :] > qpos[:, :, None] - window)
    return mask


def selection_flags(indexed, index_keys, starts, *, topk):
    """[n, T, S] int8: 1 where ``kept`` keeps the key for the query, of
    the keys up to its own: ``index_scores`` and ``kept`` as the plain
    formulation runs them, in float32, over blocks of queries whose
    index scores [n, HI, block, keys] fit ``SCORES_MAX_BYTES``, in the
    plain formulation's groups of keys. A group of no more than ``topk``
    keys drops none: ones."""
    iq, iw = indexed
    n, t, index_heads, _ = iq.shape
    keys = index_keys.shape[1]

    def flags(iq, iw, first, seen_keys):
        extent = seen_keys.shape[1]
        if extent <= topk:
            return jnp.ones((n, iq.shape[1], keys), jnp.int8)
        mask = causal(first, iq.shape[1], jnp.zeros_like(first), extent,
                       None)
        chosen = jnp.where(mask, index_scores(iq, iw, seen_keys), MASKED)
        return jnp.pad((mask & kept(chosen, topk)).astype(jnp.int8),
                       ((0, 0), (0, 0), (0, keys - extent)))

    return over_blocks(
        flags, (iq, iw), starts, index_keys, grouped=True,
        block=query_block(n, t, index_heads, keys, None))



def prefill_selection(index: IndexInputs, pool, layer, table_rows, starts):
    """``selection_flags`` [n, T, PB x page] int8 of a prefill's queries
    (``index`` of its tokens [n, T, ...], row i's first at position
    ``starts[i]``) against the rows' index keys in ``pool`` through
    ``table_rows`` [n, PB], the suffixes' own written already; None where
    the table holds no more than ``topk`` keys: nothing is dropped."""
    if table_rows.shape[1] * pool.shape[2] <= index.topk:
        return None
    with jax.named_scope(scopes.INDEX_SELECT):
        return selection_flags(
            (index.q, index.weights), gather_rows(pool, layer, table_rows),
            starts, topk=index.topk)
