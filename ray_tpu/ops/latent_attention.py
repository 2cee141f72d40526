"""Latent attention over paged rows, with a learned selection of keys.

A layer of this kind keeps ONE ROW a token and no K/V twins
(``ops/paged_attention.py`` states the pools): the compressed KV
``ckv`` (``r`` numbers, after its norm) beside one rotary key ``kr``
(``dr`` numbers, already rotated) that every head shares. A head's key
and value are an expansion of the row, ``kn | v = ckv @ wkv_b`` ([r, H,
dn + dv]), and its score on a key is ``(qn . kn + qr . kr) x scale``
(DeepSeek-V2's multi-head latent attention, arXiv:2405.04434). Two forms
give the same numbers:

- EXPANDED (``latent_prefill_attention``): the rows the queries can see
  are gathered and expanded into every head's key and value, and causal
  softmax attention runs over them. Right for a prefill's many queries:
  the expansion is paid once for all of them (once a block of queries in
  a windowed layer's plain formulation);
- ABSORBED (``latent_decode_attention``): the query takes the expansion
  instead, ``q~ = qn @ wkv_b[K]^T`` in R^r, scores run against the rows
  as they lie, ``(q~ . ckv + qr . kr) x scale``, the probabilities weigh
  the rows themselves and the value half of the expansion comes last,
  ``o = (sum p ckv) @ wkv_b[V]``. Right for a decode step's one query a
  slot: nothing of size keys x heads x width exists.

A layer with an INDEXER (``ops/index_select.py``: ``IndexInputs``;
DeepSeek-V3.2's sparse attention) keeps a second, narrow row a token,
the index key; a query's softmax runs over the ``topk`` keys of largest
index score alone (``kept``: one rule, both forms; the set ``lax.top_k``
would return, found by a threshold search and not a sort).

A WINDOWED layer's queries see the ``window`` newest keys, their own
among them.

WHAT RUNS WHERE. The prefill form has two formulations of the same
softmax over the same keys, and ``latent_prefill_attention`` chooses by
the platform a program is lowered for (``jax.lax.platform_dependent``)
and by static shapes (``latent_prefill_kernel_engages``), nothing else:

- IN THE KERNEL, one Pallas kernel (``latent_prefill_attn``; full and
  sliding layers are one kernel at different static arguments): the
  table's rows are gathered and expanded ONCE a layer in HBM, a head's
  key as one array (its no-position part beside the rotary key all heads
  share: 128 + 64 or 192 + 64 wide) and its value, and the grid walks
  (row, four heads, a block of 512 queries, a chunk of 512 keys): scores
  on the MXU into VMEM, masked by position (causal from ``starts``; under
  a static ``window`` the second side too), online softmax in float32,
  the probabilities cast to the rows' type before the weighted sum, the
  sum divided by the float32 denominator at a block's last chunk. A
  block's walk starts at the chunk of its oldest visible key and ends at
  its last valid query's: the chunks outside it are neither fetched (the
  index maps hold them at the walk's ends) nor computed, and a block of
  padding alone is zeros. The SELECTION reaches it as flags a query and
  key ([n, T, S], one byte a pair): ``index_scores`` runs in
  ``jax.numpy`` as the plain formulation runs it, in float32, in blocks
  of queries whose index scores fit ``SCORES_MAX_BYTES`` (they stay in
  HBM), ``kept`` searches each query's row of them for its ``topk``-th
  score (``ops/index_select.py``: no row is sorted), and the kernel
  computes no index score, no top-k and no tie.
  Nothing of size heads x queries x keys is written to HBM (a full
  layer of one cold 4,096-token prompt, 3,600 of them valid, on a v5e,
  PR 58: 14.9 ms, of which the kernel 10.4, the flags 3.4 and the
  expansion 2.1, against the plain formulation's 51.7; a sliding layer
  4.3 against 14.9);
- PLAIN ``jax.numpy`` and ``lax`` (``_prefill_plain``: what the kernel
  is held to, and what every other platform and every shape under the
  rule runs): it gathers the pages a block of queries can see
  (``visible_pages`` for a window), the selection is a mask, and its
  float32 scores, the layer's and its indexer's, go over blocks of
  queries under ``SCORES_MAX_BYTES``: written, masked, read for the max,
  for the sum, written as probabilities and read again.

The rule between them is a line in bytes on the plain formulation's
float32 scores for the layer: see ``PREFILL_KERNEL_SCORES_BYTES``.

The decode form has two formulations of the same softmax over the same
keys, and ``latent_decode_attention`` chooses by the platform a program
is lowered for (``jax.lax.platform_dependent``) and by static shapes
(``latent_kernel_engages``), nothing else:

- IN PLACE, one Pallas kernel (``latent_decode_attn``, a sibling of
  ``ops/paged_decode_attention.py``'s), for a layer with an indexer: the
  rows' pool [L, P, page, lanes] stays in HBM; the layer, the page table,
  each slot's key count and the next-live-slot chain come through scalar
  prefetch; the grid walks the slots and, per live slot, the pages up to
  ``ceil(keys / page)``, ``_GROUP`` at a step, ONE async copy a page,
  double buffered, the next pages' copies (of this slot or of the next
  live one) in flight while these are computed (``_walk``). The pages are
  contracted as they lie: ``q_row [H, lanes] x rows^T -> [H, keys]``
  float32, online softmax in float32, the probabilities cast to the rows'
  type, ``p x rows[:, :r] -> [H, r]`` accumulated in float32. The
  SELECTION reaches it as flags a key ([slots, keys], a page's 128 riding
  with the page): ``kept``'s set among the keys the slot sees, so every
  page a slot holds is read whole and the keys outside the set are
  masked. A dead slot and the pages past a slot's count cost nothing;
- GATHERED, plain ``jax.numpy`` (``_gathered``: what the kernel is held
  to, and what every other platform, every shape outside the rule and
  every windowed layer runs): the chosen rows, or a window's pages
  (``visible_pages``), copied out of the pool and attended over as one
  array. (A windowed layer's gather is whole pages already: through the
  kernel its five pages a slot took 0.287 ms a layer against 0.281
  gathered, on a v5e at the serving cell's shapes, PR 41, so the kernel
  has no window.)

The rule between them for a layer with an indexer is a ratio the program
sees as static shapes, the table's keys over ``topk``: see
``GATHER_PAST``.

The INDEXER's own arithmetic (``index_scores``, ``kept``), a decode
step's index scores in place or gathered (``decode_index_scores``: the
index kernel, whose walk over a slot's pages the latent kernel shares)
and a prefill's selection as flags (``selection_flags``) are
``ops/index_select.py``'s, which the layers that select over K/V twins
import too.

The serving engine (``serve/paged_llm.py``) calls the
three functions at the bottom from its two programs; what they take of a
block is ``LatentInputs``, which the model's module builds."""

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
# the indexer's pieces, which the K/V layers that select share
from ray_tpu.ops.index_select import (  # noqa: F401 - IndexInputs: what a
    BUFFERS, MASKED, IndexInputs,       # model's ``latent_projections``
    causal, decode_index_scores,        # builds its ``LatentInputs`` with
    index_scores, kept, of_slot, over_blocks, query_block, selection_flags,
    walk, walked)
from ray_tpu.ops.paged_attention import (ROW_LANES, gather_rows,
                                         visible_pages, write_rows)

# inside the kernel a masked score must stay finite under ``s - m``
_KERNEL_MASKED = -0.7 * float(jnp.finfo(jnp.float32).max)


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



# ---------------------------------------------------------------------------
# Decode: the absorbed form, in place or gathered
# ---------------------------------------------------------------------------

KERNEL_NAME = "latent_decode_attn"
BUFFERS = 2
# pages a step of the walk: one max, one rescale of the accumulator and one
# weighted sum for all of them (a full layer at 64 slots of 3.0-5.1k keys
# on a v5e, the kernel alone, PR 41: 1.36 ms a page at a time, 0.89 by
# twos, 0.72 by fours, 0.65 by eights and by sixteens)
_GROUP = 8
# A layer with an indexer reads its rows IN PLACE (every page the slot
# holds, the selection a mask) while its table holds no more than this
# many times ``topk`` keys, and GATHERED (the chosen rows alone, by
# position) past it. The measurement (a v5e, rows of 1,280 B, traces of
# PR 40 and PR 41): XLA's gather takes 17 ns a chosen row whatever its
# width (2.28 ms for 64 x 2,048 rows, 74 GB/s; 2.78 with the scores and
# values that read the copy back), and a row read in place costs 2.2-2.5
# ns (0.58-0.65 ms the kernel for the 260 thousand rows 64 slots of
# 3.0-5.1k keys hold; its bytes over the bandwidth would be 1.6); so the
# gather wins only where a slot holds more than about 8 x ``topk`` keys.
# The serving cell's programs (64-page tables of 8,192 keys over a
# ``topk`` of 2,048) stand at 4 x.
GATHER_PAST = 8

def latent_kernel_engages(page: int, table_pages: int, topk) -> bool:
    """The rule, from static shapes alone: whether a decode step's
    attention of a layer that keeps ``topk`` keys (None: it has no
    indexer), over a table of ``table_pages`` pages of ``page`` rows, is
    the kernel on a program lowered for a TPU: where the table holds more
    than ``topk`` keys (else nothing is selected) and no more than
    ``GATHER_PAST`` times as many, in pages of whole lanes (a page's
    flags are then whole lanes too)."""
    return (topk is not None and page % ROW_LANES == 0
            and topk < page * table_pages <= GATHER_PAST * topk)






def _kernel(layer_ref, table_ref, count_ref, next_ref,       # SMEM
            q_ref, pool_hbm, flags_ref, o_ref, buf, sem, step_ref, *,
            pages_per_slot, group, width, scale):
    """One grid step a slot (module docstring): its queries ``q_ref`` [1,
    H, lanes], its flags [1, PB / group, group x page], its output [1, H,
    width]; the page buffers, their semaphores and ``step_ref``:
    ``walk``'s."""
    heads = q_ref.shape[1]
    q = q_ref[0]                                            # [H, lanes]

    @pl.when(pl.program_id(0) == 0)
    def _():
        # a group's pages past the slot's last are not fetched: what the
        # buffer holds there weighs nothing, and must be a number
        buf[...] = jnp.zeros_like(buf)

    def group_body(g, rows, m, l, acc):
        s = lax.dot_general(q, rows, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        # the flags hold the slot's count and its selection both
        seen = flags_ref[0, pl.ds(g, 1), :] > 0      # [1, group x page]
        s = jnp.where(seen, s, _KERNEL_MASKED)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        # (a group with no key of the set, met before any that has one,
        # must leave nothing behind: its rows weigh nothing, not one)
        p = jnp.where(seen, jnp.exp(s - m_new), 0.0)
        l = alpha * l + p.sum(axis=-1, keepdims=True)
        acc = alpha * acc + lax.dot_general(
            p.astype(rows.dtype), rows[:, :width], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    m, l, acc = walk(
        layer_ref, table_ref, count_ref, next_ref, pool_hbm, buf, sem,
        step_ref, pages_per_slot=pages_per_slot, group=group,
        body=group_body,
        carry=(jnp.full((heads, 1), -jnp.inf, jnp.float32),
               jnp.zeros((heads, 1), jnp.float32),
               jnp.zeros((heads, width), jnp.float32)))
    # a slot with no keys: zeros (l == 0), never a NaN
    o_ref[0] = (acc / jnp.where(l > 0, l, 1.0)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("rank", "scale", "interpret"))
def latent_decode_attention_kernel(q_row, pool, layer, table, count, flags,
                                   *, rank, scale, interpret=False):
    """The kernel's launch. ``q_row`` [B, H, lanes]: the queries in the
    rows' own layout (``_query_rows``); ``pool`` [L, P, page, lanes];
    ``table`` [B, PB] page ids (-1 = hole); ``count`` [B]: the keys a
    slot's query sees, 0 for a dead slot; ``flags`` [B, PB x page] bool:
    the keys its softmax runs over (none past ``count``). Returns the
    probability-weighted rows' first ``rank`` numbers [B, H, rank] in
    ``q_row``'s type."""
    slots, heads, lanes = q_row.shape
    page, pb = pool.shape[2], table.shape[1]
    group = math.gcd(pb, _GROUP)
    # what a page's rows are sliced to: whole lanes (``rank``'s, or all)
    width = min(lanes, -(-rank // ROW_LANES) * ROW_LANES)

    out = pl.pallas_call(
        functools.partial(_kernel, pages_per_slot=pb, group=group,
                          width=width, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(slots,),
            in_specs=[of_slot(heads, lanes),
                      pl.BlockSpec(memory_space=pl.ANY),
                      of_slot(pb // group, group * page)],
            out_specs=of_slot(heads, width),
            scratch_shapes=[
                pltpu.VMEM((BUFFERS, group * page, lanes), pool.dtype),
                pltpu.SemaphoreType.DMA((BUFFERS, group)),
                pltpu.SMEM((1,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((slots, heads, width), q_row.dtype),
        # the slots in order on one core: a slot's last group fetches the
        # next live slot's first
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret, name=KERNEL_NAME,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      *walked(table, count), q_row, pool,
      flags.astype(jnp.int32).reshape(slots, pb // group, group * page))
    return out[..., :rank]



def _query_rows(inputs: LatentInputs, lanes: int):
    """A step's queries in the rows' own layout [B, H, lanes]: the
    no-position part through the keys' half of the expansion (``q~``),
    the rotary part beside it, zeros against the rows' spare lanes: the
    rows are contracted as they lie, never sliced."""
    r = inputs.wkv_b.shape[0]
    dn = inputs.q.shape[-1] - (inputs.row.shape[-1] - r)
    q = inputs.q[:, 0]                                   # [B, H, dn + dr]
    q_latent = jnp.einsum("bhn,rhn->bhr", q[..., :dn],
                          inputs.wkv_b[..., :dn],
                          preferred_element_type=jnp.float32)
    return jnp.concatenate(
        [q_latent.astype(q.dtype), q[..., dn:],
         jnp.zeros((*q.shape[:2], lanes - inputs.row.shape[-1]), q.dtype)],
        -1)



def _in_place(q_row, pool, layer, table, count, chosen, *, rank, scale,
              topk):
    """The kernel's formulation of a layer that selects; arguments as
    ``_gathered``'s."""
    return latent_decode_attention_kernel(
        q_row, pool, layer, table, count,
        (chosen > MASKED) & kept(chosen, topk), rank=rank, scale=scale)


def _gathered(q_row, pool, layer, table, count, chosen=None, *, rank, scale,
              topk, window):
    """The plain formulation: ``q_row`` [B, H, lanes] over the rows that
    slot b's query attends over, copied out of ``pool`` through ``table``
    [B, PB]: of the ``count`` [B] keys it sees, the ``topk`` of largest
    ``chosen`` ([B, PB x page] float32 index scores, ``MASKED`` past the
    count) by position, else its ``window``'s pages, else all. Returns
    the probability-weighted rows' first ``rank`` numbers [B, H, rank] in
    ``q_row``'s type."""
    page = pool.shape[2]
    if chosen is not None:
        with jax.named_scope(scopes.INDEX_SELECT):
            values, positions = lax.top_k(chosen, topk)
            # a key past the slot's count comes out with the mask's own value
            # (a gather of the seen keys at the positions says the same, a
            # scalar at a time: 1.3 ms a layer on a v5e at 64 x 2,048)
            mask = values > MASKED
            # each position's page id: the table's entry at its page, picked
            # by comparison (a gather of 2,048 scalars a slot out of the
            # table takes 1.0 ms a layer on a v5e; this a few microseconds)
            at = (positions // page)[..., None] == jnp.arange(
                table.shape[1], dtype=jnp.int32)
            pages = jnp.sum(
                jnp.where(at, jnp.maximum(table, 0)[:, None, :], 0), axis=-1)
        rows = pool[layer, pages, positions % page]       # [B, topk, w]
    else:
        key_start = jnp.zeros_like(count)
        if window is not None:
            table, key_start = visible_pages(
                table, count - window, -(-(page + window - 1) // page), page)
        rows = gather_rows(pool, layer, table)               # [B, S, w]
        kpos = key_start[:, None] + jnp.arange(rows.shape[1],
                                               dtype=jnp.int32)
        mask = kpos < count[:, None]
        if window is not None:
            mask = mask & (kpos >= count[:, None] - window)
    scores = jnp.einsum("bhw,bsw->bhs", q_row, rows,
                        preferred_element_type=jnp.float32) * scale
    scores = jnp.where(mask[:, None, :], scores, MASKED)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhs,bsr->bhr", probs.astype(rows.dtype),
                      rows[..., :rank], preferred_element_type=jnp.float32
                      ).astype(q_row.dtype)


@functools.cache
def _formulations(rank, scale, topk, window):
    """(in place, gathered) for a layer of these statics, made once: the
    branches ``lax.platform_dependent`` takes are then the same functions
    from call to call, and an engine's decode programs that differ in
    their chunk alone trace them once
    (``ops/paged_decode_attention.py:paged_decode_attention``)."""
    statics = dict(rank=rank, scale=scale, topk=topk)
    return (functools.partial(_in_place, **statics),
            functools.partial(_gathered, window=window, **statics))


def latent_decode_attention(inputs: LatentInputs, pools: tuple, layer,
                            table, pos, *, window=None, active=None):
    """A decode step's attention, one query a slot at position ``pos``
    [B], over the slot's rows where they lie in ``pools`` (the run's:
    latent rows, then index keys where the layer has an indexer) through
    the page table [B, PB], the step's own row written already; a slot
    that is not ``active`` ([B] bool; none: all are) sees no key and its
    result, unspecified, is dropped. A layer with an indexer scores its
    slots' index keys and attends over the ``topk`` best, a windowed
    layer over its window. Returns [B, H, dv].

    On a program lowered for a TPU, where ``index_kernel_engages``, the
    index keys are scored in place by the index kernel and, where
    ``latent_kernel_engages``, the rows are read in place by the latent
    kernel; everywhere else they are gathered (module docstring). A
    windowed layer's are gathered on every platform: its gather is whole
    pages already, and through the kernel it took as long (module
    docstring)."""
    with jax.named_scope(scopes.LATENT_ATTN):
        pool = pools[0]
        page, r = pool.shape[2], inputs.wkv_b.shape[0]
        index = inputs.index
        count = pos + 1 if active is None else jnp.where(active, pos + 1, 0)
        topk = None
        args = (_query_rows(inputs, pool.shape[-1]), pool, layer, table, count)
        if index is not None and table.shape[1] * page > index.topk:
            # (a table of no more than ``topk`` keys: nothing is dropped)
            topk = index.topk
            args += (decode_index_scores(index, pools[1], layer, table,
                                         count),)
        in_place, gathered = _formulations(r, inputs.scale, topk, window)
        if latent_kernel_engages(page, table.shape[1], topk):
            o_latent = lax.platform_dependent(*args, tpu=in_place,
                                              default=gathered)
        else:
            o_latent = gathered(*args)
        dn = inputs.q.shape[-1] - (inputs.row.shape[-1] - r)
        return jnp.einsum("bhr,rhv->bhv", o_latent, inputs.wkv_b[..., dn:],
                          preferred_element_type=jnp.float32
                          ).astype(inputs.q.dtype)


# ---------------------------------------------------------------------------
# Prefill: the expanded form, plain or in the kernel
# ---------------------------------------------------------------------------

PREFILL_KERNEL_NAME = "latent_prefill_attn"
# The plain formulation's float32 scores for a layer (every block of its
# queries as ``query_block`` cuts them: [n, H, block, keys], the
# indexer's [n, HI, block, keys] beside them) past which a layer over
# bf16 rows takes the kernel on a TPU: the line of
# ``ops/paged_prefill_attention.py``'s ``KERNEL_SCORES_BYTES``, drawn as
# that one was, from what a kernel-holding program costs the host to
# trace and lower in every run against what it saves a dispatch (a v5e,
# one full layer behind a cached 3,700-token transcript, plain against
# the kernel's path, PR 58: 256 queries, 805 MB of scores, 5.9 ms against
# 3.2; 128 queries, 403 MB, 3.9 against 2.8; 64 queries, 201 MB, 2.9
# against 2.7, where both are the whole table's expansion, 2.1; a sliding
# layer's suffix, 9-38 MB, 0.3-0.6 ms against 1.8: its plain path expands
# two windows' pages and the kernel's the table).
PREFILL_KERNEL_SCORES_BYTES = 256 << 20
# queries a block and keys a chunk of the kernel's walk (a head's scores
# [512, 512] float32 are 1 MiB of VMEM), and the heads a grid step takes:
# they share the step's block of flags (a cold 4,096-token prompt, 3,600
# queries valid, on a v5e, the kernel alone, a full layer with flags / a
# sliding one, ``scripts/sweep_latent_prefill.py``, PR 58: 10.29 / 2.15
# ms at 512, 512, 4; 9.67 / 2.35 with chunks of 1,024; 9.50 / 2.88 at
# 1,024, 1,024, 2; 11.75 / 3.02 with blocks of 1,024; 10.43 / 2.13 at 8
# heads; 17.43 / 3.91 with chunks of 256)
_PREFILL_BLOCK_Q = 512
_PREFILL_CHUNK = 512
_PREFILL_HEADS = 4
# of the core's 128 MiB: a step's queries, keys, values, flags and
# outputs twice, the heads' accumulators, one head's scores and
# probabilities (about 14 MiB at 256 | 128 wide)
_PREFILL_VMEM_BYTES = 48 << 20



def _kernel_blocks(t: int, keys: int) -> tuple:
    """(queries a block, keys a chunk) of the kernel's walk over ``t``
    queries a row and ``keys`` keys: the largest powers of two up to 512
    that divide them."""
    return (math.gcd(t, _PREFILL_BLOCK_Q), math.gcd(keys, _PREFILL_CHUNK))


def latent_prefill_kernel_engages(q_shape, pool, table_pages: int, window,
                                  index_heads: int = 0) -> bool:
    """The rule, from shapes, the layer's kind and the pool's dtype:
    whether the attention of a prefill's queries ``q_shape`` [n, T, H,
    dn + dr] over ``table_pages`` pages a row of ``pool`` (the rows'
    stacked pool, or its shape and dtype), under ``window`` keys where
    the layer slides, beside ``index_heads`` indexer heads where it
    selects (0: no indexer, or a table of no more than ``topk`` keys), is
    the kernel's on a program lowered for a TPU: bf16 rows, and float32
    scores of the plain formulation for the layer over
    ``PREFILL_KERNEL_SCORES_BYTES``: a full layer's [n, H + HI, T, the
    table's keys]; a sliding layer's what its blocks write, [n, H, T,
    ``query_block`` + window] (or the table's keys where that is
    narrower). The kernel takes queries in blocks and keys in chunks of
    whole sublanes and lanes, and heads four at a time."""
    n, t, heads, _ = q_shape
    keys = table_pages * pool.shape[2]
    bq, ck = _kernel_blocks(t, keys)
    seen = keys
    if window is not None:
        seen = min(keys, query_block(n, t, heads, keys, window) + window)
    return (pool.dtype == jnp.bfloat16 and heads % _PREFILL_HEADS == 0
            and bq % 32 == 0 and ck % ROW_LANES == 0
            and 4 * n * (heads + index_heads) * t * seen
            > PREFILL_KERNEL_SCORES_BYTES)


def _walked_chunks(starts_ref, slens_ref, b, qi, *, bq, ck, keys, window):
    """(first, last): the chunks of keys that block ``qi`` of row ``b``'s
    queries walks, from the one that holds the oldest key its first query
    sees (0 without a window) to the one that holds its last valid
    query's own; ``last < first`` for a block of padding alone."""
    start, slen = starts_ref[b], slens_ref[b]
    oldest = start + qi * bq
    newest = start + jnp.minimum((qi + 1) * bq, slen) - 1
    first = (0 if window is None
             else jnp.clip(oldest - (window - 1), 0, keys - 1) // ck)
    return first, jnp.where(qi * bq < slen,
                            jnp.minimum(newest, keys - 1) // ck, first - 1)


def _prefill_kernel(starts_ref, slens_ref,                       # SMEM
                    q_ref, k_ref, v_ref, *refs, scale, window, keys):
    """One grid step (row, block of heads, block of queries, chunk of
    keys): ``q_ref`` [1, heads, bq, dk], ``k_ref`` [1, heads, ck, dk],
    ``v_ref`` [1, heads, ck, dv], then the flags of the block's queries
    on the chunk's keys [1, bq, ck] where the layer selects, the output
    [1, heads, bq, dv] and, kept from chunk to chunk of a block's walk,
    the heads' running max, denominator and weighted sum in float32. The
    chunks outside the block's walk (``_walked_chunks``) are neither
    fetched (the index maps hold them at the walk's ends) nor computed."""
    *flags_ref, o_ref, m_ref, l_ref, acc_ref = refs
    b, qi, kj = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    heads, bq, ck = q_ref.shape[1], q_ref.shape[2], k_ref.shape[2]
    first, last = _walked_chunks(starts_ref, slens_ref, b, qi, bq=bq, ck=ck,
                                 keys=keys, window=window)

    @pl.when(kj == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when((kj >= first) & (kj <= last))
    def _():
        qpos = starts_ref[b] + qi * bq + lax.broadcasted_iota(
            jnp.int32, (bq, 1), 0)
        kpos = kj * ck + lax.broadcasted_iota(jnp.int32, (1, ck), 1)
        seen = kpos <= qpos
        if window is not None:
            seen &= kpos > qpos - window
        if flags_ref:
            seen &= flags_ref[0][0] != 0
        for h in range(heads):
            s = lax.dot_general(q_ref[0, h], k_ref[0, h],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
            # (``_KERNEL_MASKED`` is finite: a row that has met no key of
            # its set yet sums ones, which the ``alpha`` of its first
            # such key zeroes)
            s = jnp.where(seen, s, _KERNEL_MASKED)
            m = m_ref[h]
            m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)
            l_ref[h] = alpha * l_ref[h] + p.sum(axis=-1, keepdims=True)
            acc_ref[h] = alpha * acc_ref[h] + lax.dot_general(
                p.astype(v_ref.dtype), v_ref[0, h], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    @pl.when(kj == pl.num_programs(3) - 1)
    def _():
        # a block of padding alone walked nothing: zeros, never a NaN
        l = l_ref[...]
        o_ref[0] = (acc_ref[...] / jnp.where(l > 0, l, 1.0)).astype(
            o_ref.dtype)


def latent_prefill_attention_kernel(q, k, v, starts, slens, flags=None, *,
                                    scale, window=None, interpret=False):
    """The prefill kernel's launch: ``q`` [n, H, T, dk], row i's first
    query at position ``starts[i]``, its first ``slens[i]`` valid;
    ``k`` [n, H, S, dk] and ``v`` [n, H, S, dv]: every head's key
    (no-position part | rotary part) and value at the table's key
    positions 0 to S; ``flags`` [n, T, S] int8, or None for a layer that
    selects nothing: nonzero where the query's softmax may run over the
    key (the causal side, and the ``window``'s, are the kernel's own).
    Returns [n, H, T, dv] in ``q``'s type; the rows of padding inside a
    block with valid queries come back finite and otherwise unspecified,
    a block of padding alone as zeros."""
    n, heads, t, dk = q.shape
    keys, dv = k.shape[2], v.shape[3]
    bq, ck = _kernel_blocks(t, keys)
    walk = dict(bq=bq, ck=ck, keys=keys, window=window)

    def chunk(b, qi, kj, starts_ref, slens_ref):
        first, last = _walked_chunks(starts_ref, slens_ref, b, qi, **walk)
        return jnp.clip(kj, first, jnp.maximum(last, first))

    def of_queries(width):
        return pl.BlockSpec((1, _PREFILL_HEADS, bq, width),
                            lambda b, h, qi, kj, *_: (b, h, qi, 0))

    def of_keys(width):
        return pl.BlockSpec((1, _PREFILL_HEADS, ck, width),
                            lambda b, h, qi, kj, *refs: (
                                b, h, chunk(b, qi, kj, *refs), 0))

    in_specs, operands = [of_queries(dk), of_keys(dk), of_keys(dv)], [q, k, v]
    if flags is not None:
        in_specs.append(pl.BlockSpec(
            (1, bq, ck),
            lambda b, h, qi, kj, *refs: (b, qi, chunk(b, qi, kj, *refs))))
        operands.append(flags)
    return pl.pallas_call(
        functools.partial(_prefill_kernel, scale=scale, window=window,
                          keys=keys),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n, heads // _PREFILL_HEADS, t // bq, keys // ck),
            in_specs=in_specs, out_specs=of_queries(dv),
            scratch_shapes=[
                pltpu.VMEM((_PREFILL_HEADS, bq, 1), jnp.float32),
                pltpu.VMEM((_PREFILL_HEADS, bq, 1), jnp.float32),
                pltpu.VMEM((_PREFILL_HEADS, bq, dv), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((n, heads, t, dv), q.dtype),
        # a block's chunks in order (the accumulators go from one to the
        # next); rows, heads and blocks of queries in any
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3 + ("arbitrary",),
            vmem_limit_bytes=_PREFILL_VMEM_BYTES),
        interpret=interpret, name=PREFILL_KERNEL_NAME,
    )(starts.astype(jnp.int32), slens.astype(jnp.int32), *operands)




def _expanded(q, wkv_b, rows, dr: int):
    """What ``rows`` [n, S, lanes] hold, by key: the rotary keys [n, S,
    dr], every head's no-position keys [n, H, S, dn] and values [n, H, S,
    dv] (heads before keys: the layout the products over them contract
    in), in ``q``'s type."""
    r, dn = wkv_b.shape[0], q.shape[-1] - dr

    def heads_of(w):
        return jnp.einsum("nsr,rhe->nhse", rows[..., :r], w,
                          preferred_element_type=jnp.float32
                          ).astype(q.dtype)

    return (rows[..., r:r + dr], heads_of(wkv_b[..., :dn]),
            heads_of(wkv_b[..., dn:]))


def _prefill_plain(q, wkv_b, indexed, pools, layer, table_rows, starts, *,
                   scale, window, topk, dr):
    """The plain formulation (what the kernel is held to, and what every
    other platform and every shape under the rule runs): the float32
    scores of a block of queries (``query_block``), the layer's and its
    indexer's, written, masked and read again. ``indexed``: the queries'
    index queries and weights where the layer selects ``topk`` keys, else
    None. The rows of padding are computed as any other."""
    pool = pools[0]
    n, t, heads, _ = q.shape
    page = pool.shape[2]
    dn = q.shape[-1] - dr
    keys = table_rows.shape[1] * page
    block = query_block(
        n, t, heads + (indexed[0].shape[2] if indexed else 0), keys, window)
    # the pages that hold the keys of ``block`` queries' windows
    seen = (table_rows.shape[1] if window is None
            else -(-(block + window - 2) // page) + 1)

    def expand(table):
        """``_expanded`` of ``table``'s pages and, where the layer
        selects, their index keys."""
        return (*_expanded(q, wkv_b, gather_rows(pool, layer, table), dr),
                gather_rows(pools[1], layer, table) if indexed else None)

    # a full layer's queries all see the same rows: expanded once, outside
    # the blocks; a windowed layer's blocks each expand what they can see
    whole = expand(table_rows) if window is None else None

    def attend(q, *rest):
        """``q`` [n, block, H, dn + dr], then their index queries and
        weights where the layer selects, the first's position [n] and
        what ``expand`` gave for the keys from position 0 that they can
        see, or None for a windowed layer, whose block expands the pages
        of its own windows."""
        *iq, first, seen_keys = rest
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
                  ) * scale
        mask = causal(first, q.shape[1], key_start, kr.shape[1], window)
        if iq and kr.shape[1] > topk:
            chosen = jnp.where(mask, index_scores(*iq, index_keys), MASKED)
            mask = mask & kept(chosen, topk)
        scores = jnp.where(mask[:, None], scores, MASKED)
        probs = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum(
            "nhts,nhsv->nthv", probs.astype(q.dtype), v,
            preferred_element_type=jnp.float32).astype(q.dtype)

    return over_blocks(attend, (q, *(indexed or ())), starts, whole,
                        block=block, grouped=window is None)



def _prefill_in_kernel(q, wkv_b, indexed, pools, layer, table_rows, starts,
                       slens, *, scale, window, topk, dr):
    """The kernel's formulation; arguments as ``_prefill_plain``'s, and
    the rows' valid queries ``slens`` [n]. The table's rows are expanded
    ONCE a layer in HBM, a head's key as one
    array (its no-position part beside the rotary key all heads share),
    and a layer that selects hands the kernel ``kept``'s set as flags."""
    n, heads = q.shape[0], q.shape[2]
    kr, kn, v = _expanded(
        q, wkv_b, gather_rows(pools[0], layer, table_rows), dr)
    k = jnp.concatenate(
        [kn, jnp.broadcast_to(kr[:, None], (n, heads, *kr.shape[1:]))], -1)
    flags = None
    if indexed:
        flags = selection_flags(
            indexed, gather_rows(pools[1], layer, table_rows), starts,
            topk=topk)
    out = latent_prefill_attention_kernel(
        jnp.moveaxis(q, 1, 2), k, v, starts, slens, flags, scale=scale,
        window=window)
    return jnp.moveaxis(out, 1, 2)


@functools.cache
def _prefill_formulations(scale, window, topk, dr):
    """(in the kernel, plain beside it, plain) for a layer of these
    statics, made once: the branches ``lax.platform_dependent`` takes
    (the first two: one list of arguments, of which the plain formulation
    leaves the last, the valid lengths, unread) are then the same
    functions from call to call (``_formulations``)."""
    statics = dict(scale=scale, window=window, topk=topk, dr=dr)
    plain = functools.partial(_prefill_plain, **statics)
    return (functools.partial(_prefill_in_kernel, **statics),
            lambda *args: plain(*args[:-1]), plain)


def latent_prefill_attention(inputs: LatentInputs, pools: tuple, layer,
                             table_rows, starts, slens, *, window=None):
    """A prefill's attention: queries ``inputs.q`` [n, T, H, dn + dr],
    row i's first at position ``starts[i]``, over the rows' pages
    (``table_rows`` [n, PB]) in the expanded form, the suffixes' own
    rows written already, so a suffix's queries see a reused prefix's
    rows exactly as the prompt that wrote them left them. A layer with
    an indexer scores a block's queries against the rows' index keys and
    attends over each query's ``topk`` alone (a block that sees no more
    than ``topk`` keys drops none). ``slens`` [n]: the rows' valid
    queries; the rows of padding past them come back finite and
    otherwise unspecified. Returns [n, T, H, dv].

    Under the rule (``latent_prefill_kernel_engages``) this IS the plain
    formulation, called directly: the program's lowered text is what it
    was. Over it ``jax.lax.platform_dependent`` chooses where the program
    is LOWERED: the kernel for a TPU, the plain formulation for anything
    else (module docstring)."""
    with jax.named_scope(scopes.LATENT_ATTN):
        index, pages = inputs.index, table_rows.shape[1]
        if index is not None and pages * pools[0].shape[2] <= index.topk:
            index = None                    # no query can see more than topk
        in_kernel, beside, plain = _prefill_formulations(
            inputs.scale, window, index.topk if index is not None else None,
            inputs.row.shape[-1] - inputs.wkv_b.shape[0])
        args = (inputs.q, inputs.wkv_b,
                (index.q, index.weights) if index is not None else None,
                pools, layer, table_rows, starts)
        if not latent_prefill_kernel_engages(
                inputs.q.shape, pools[0], pages, window,
                index.q.shape[2] if index is not None else 0):
            return plain(*args)
        return lax.platform_dependent(*args, slens, tpu=in_kernel,
                                      default=beside)
