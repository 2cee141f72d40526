"""Decode attention over the KV pages where they lie: one Pallas kernel.

Reference: ABSENT from the reference repo (SURVEY P15). The paged
engine's decode step used to gather every slot's page window out of the
pool ([B x PB, page, nkv, hd], a copy as large as the window, per layer
per step) and attend over the copy. This kernel reads each slot's pages
from the STACKED pool [L, P, page, nkv, hd] in place, as the engine
holds it, and stops at each slot's length:

- the pools stay in HBM (``pl.ANY``); the layer index, the flattened page
  table, the per-slot key counts and the next-live-slot chain come
  through scalar prefetch (SMEM);
- one invocation walks the live slots in order and, per slot, the pages
  up to ``ceil(keys / page)``. A page [page, nkv, hd] is contiguous in
  the pool: ONE async copy brings every KV head of it into VMEM, double
  buffered, and the copies of the next step (of this slot or of the next
  live slot) are in flight while this one is computed. A slot with no
  keys (inactive) costs nothing; pages past a slot's length are neither
  fetched nor computed;
- a STEP of the walk takes 1,024 rows ``page * nkv`` (``step_pages``, a
  rule on the pool's shape and type alone): at a page of 128 tokens one
  page at 8 and 16 KV heads, two at 4, four at 2. A step's serial chain
  (wait K, scores, row maximum, exponentials, row sum, wait V, weighted
  sum, the update of the softmax state) with one step in flight behind
  it does not hide behind the step's copies when the step is small: a
  one-page step at 4 KV heads took 0.80 us where its copies alone take
  0.40 and its arithmetic alone 0.58, 40% of its 262 KB at 819 GB/s, and
  0.84 us at 2 KV heads, 19% of 131 KB; with 1,024 rows behind every
  wait a step takes 0.78 us beside copies of 0.71, 82% (chip, PR 52:
  ``PERF.md``, Findings). Where a step takes more
  than a page the pool is handed over as rows, [L, P, page * nkv, hd],
  which is how it lies (a bitcast where ``nkv`` fills a tile's sublanes:
  the rule takes more pages nowhere else), each page's copy lands in its
  rows of the step's buffer, and the matmuls read the buffer as it is:
  read as [page, nkv, hd] and reshaped, a page costs a sublane shuffle a
  token, 0.23 us of that arithmetic at 4 KV heads. A walk's last
  step may hold fewer pages than the buffer: the pages it lacks are not
  fetched, their rows are masked, and the V buffers are zeroed once a
  call so that what a masked probability multiplies is a fetched page's
  rows or zero, never what VMEM held. At one page a step the kernel is
  the one it was, instruction for instruction;
- the step is read as the rows [rows, hd]: ONE matmul scores every
  query head against every (token, kv head) row, and a mask keeps, for
  query head r, the rows of ITS kv head (r // group) at key positions
  under the slot's count. The online softmax then runs over exactly the
  keys ``cached_attention`` sees, and the masked probabilities are
  zero, so the second matmul (probabilities x the same rows of V) is the
  grouped weighted sum. Grouped (GQA, 4 query heads a KV head) and plain
  (MHA) attention are the same code at different shapes, and no head is
  ever sliced out of a page (a strided sublane read of packed bf16);
- scores and softmax state in float32, probabilities cast to the pages'
  dtype before the weighted sum, as ``cached_attention`` does;
- int8 pages: the per-(token, head) scales multiply the score COLUMNS
  (K) and the probability columns (V), which is the dequantisation done
  in VMEM after the matmul instead of on a window copy before it. The
  window's scales (1/32 of its bytes) are gathered by XLA into rows, a
  step's side by side;
- a SLIDING layer (``window``: a query sees the ``window`` newest keys,
  itself among them) starts each slot's walk at the page that holds key
  ``count - window`` and masks the rows before it: pages that lie wholly
  before the window are neither fetched nor computed, so a slot costs
  ``window / page`` pages, one more where the window straddles a page's
  edge, however long its context: the walk's first step begins at that
  page, not at a multiple of a step.

- a layer that PICKS ITS KEYS (``selected``: a learned selection,
  ``ops/index_select.py``) hands the kernel ``kept``'s set (the ``topk``
  keys of largest index score, found by a threshold search over a
  slot's scores and not a sort of them) as flags a key, laid out as a
  step's score columns are (a flag a (token, kv head) row, a page's
  side by side: [B, PB, page * nkv] int32 in VMEM, 4 MB
  at 32 slots of 8,192 keys over 4 KV heads, which XLA writes a layer):
  every page a slot holds is still fetched whole and the rows outside
  the set are masked, so the selection saves the step no byte (reading
  the chosen rows alone is ROADMAP Queue 2's). A step none of whose rows
  is in the set, met before any that has one, sums ones under the
  finite ``_MASKED``, and the first row of the set zeroes them
  (``alpha``). Without a selection none of this is traced.

``paged_decode_attention`` is the entry: on a program LOWERED for a TPU
it is the kernel, on any other platform the plain gather formulation
(``paged_decode_attention_reference``), chosen by
``jax.lax.platform_dependent`` and by nothing else.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import scopes
from ray_tpu.ops.attention import cached_attention
from ray_tpu.ops.paged_attention import (gather_kv_window,
                                         page_attention_scale, visible_pages)

KERNEL_NAME = "paged_decode_attn"
_MASKED = -0.7 * float(jnp.finfo(jnp.float32).max)
_BUFFERS = 2
_STEP_ROWS = 1024


def paged_decode_attention_reference(q, k_pages, v_pages, k_scale, v_scale,
                                     layer, table, pos, active,
                                     selected=None, *, window=None):
    """The gather formulation: every slot's window copied out
    (``gather_kv_window``), then ``cached_attention`` over the copy with
    the causal limit ``key position <= pos``; for a sliding layer only
    the pages that hold keys ``> pos - window`` are copied; with
    ``selected`` ([B, PB x page] bool) the softmax runs over those keys
    of the table alone. What the kernel is held to, and what every
    platform but the TPU runs."""
    del active      # a dead slot attends over page 0; its row is discarded
    b, _, hd = q.shape
    page = k_pages.shape[2]
    key_start = None
    if window is not None:
        # the window's keys lie in window / page pages, and one more
        table, key_start = visible_pages(
            table, pos - window + 1, -(-(window - 1) // page) + 1, page)
    kg, vg = gather_kv_window(k_pages, v_pages, k_scale, v_scale, layer,
                              table)
    nkv = kg.shape[-2]
    out = cached_attention(q[:, None], kg.reshape(b, -1, nkv, hd),
                            vg.reshape(b, -1, nkv, hd), pos,
                            scale=page_attention_scale(hd), window=window,
                            key_start=key_start,
                            seen=None if selected is None
                            else selected[:, None])
    return out[:, 0]


def step_pages(pool) -> int:
    """The pages one step of the kernel's walk takes of a pool [L, P,
    page, nkv, hd] (an array or its shape and type): as many as make
    ``_STEP_ROWS`` rows ``page * nkv``, where a page's rows lie dense in
    the pool (module docstring)."""
    _, _, page, nkv, _ = pool.shape
    # dense: the KV heads fill a tile's sublanes with no padding (a power
    # of two of them, a 32-bit sublane of two bf16 or four int8 at least)
    dense = nkv & (nkv - 1) == 0 and nkv * jnp.dtype(pool.dtype).itemsize >= 4
    return max(1, _STEP_ROWS // (page * nkv)) if dense else 1


def _kernel(layer_ref, table_ref, count_ref, next_ref,     # SMEM
            q_ref, k_hbm, v_hbm, *rest, pages_per_slot, page, nkv,
            per_step, quantized, window, selects=False):
    """See the module docstring. ``rest``: the window's scale rows (int8
    only), the selection's flag rows (``selects`` only), the output, the
    step buffers and their DMA semaphores."""
    if quantized:
        ks_ref, vs_ref, *rest = rest
    if selects:
        flags_ref, *rest = rest
    o_ref, k_buf, v_buf, sem = rest
    slots, nh, hd = q_ref.shape
    page_rows = page * nkv
    rows = per_step * page_rows
    scale = page_attention_scale(hd)
    layer = layer_ref[0]
    # what the gather formulation attends over: the pages' own type, or
    # the dequantised bf16 window
    kv_dtype = jnp.bfloat16 if quantized else k_buf.dtype

    def blocks(count):
        # never past the table's row (the gather formulation's window
        # ends there too; the engine's reservations keep counts inside)
        return jnp.minimum((count + page - 1) // page, pages_per_slot)

    def copies(slot, block, buf):
        """The copies of the step that begins at page ``block`` of
        ``slot``'s walk, for each of its pages: whether the walk holds
        the page (its first it always does), its K copy and its V copy."""
        out = []
        n_blocks = blocks(count_ref[slot]) if per_step > 1 else None
        for j in range(per_step):
            held, at = True, block
            if j:
                held = block + j < n_blocks
                at = jnp.minimum(block + j, pages_per_slot - 1)
            p = table_ref[slot * pages_per_slot + at]
            # a buffer is a page, or the step's rows with the page's own
            # among them
            to = (buf if per_step == 1
                  else (buf, pl.ds(j * page_rows, page_rows)))
            out.append((held,
                        pltpu.make_async_copy(k_hbm.at[layer, p],
                                              k_buf.at[to], sem.at[0, buf]),
                        pltpu.make_async_copy(v_hbm.at[layer, p],
                                              v_buf.at[to], sem.at[1, buf])))
        return out

    def start(slot, block, buf):
        for held, *pair in copies(slot, block, buf):
            for c in pair:
                pl.when(held)(c.start)

    # which query head may see which row of a step: row = token * nkv + kv
    # head; head r reads kv head r // group
    col = lax.broadcasted_iota(jnp.int32, (nh, rows), 1)
    row = lax.broadcasted_iota(jnp.int32, (nh, rows), 0)
    own_head = (col % nkv) == (row // (nh // nkv))
    token = col // nkv

    def first_block(slot):
        """The first page of a slot's walk: 0, or the page of the oldest
        key its window holds."""
        if window is None:
            return 0
        # (the chain ends at ``slots``, which is no slot: read the last)
        count = count_ref[jnp.minimum(slot, slots - 1)]
        return jnp.maximum(count - window, 0) // page

    if per_step > 1:
        # a walk's last step may hold fewer pages than its buffer: the
        # rows it does not fetch are masked, and a masked probability
        # times whatever VMEM held is a NaN if that was one. Zeros, then
        # a fetched page's rows, are all the V buffers ever hold
        v_buf[...] = jnp.zeros(v_buf.shape, v_buf.dtype)

    first = next_ref[0]

    @pl.when(first < slots)
    def _():
        start(first, first_block(first), 0)

    def slot_body(slot, step):
        count = count_ref[slot]
        n_blocks = blocks(count)
        begin = first_block(slot)
        # step i of the walk begins at page ``at(i)``; the walk ends
        # before step ``end``, at key ``keys_end`` (one page a step: the
        # steps are the pages)
        def at(i):
            return i if per_step == 1 else begin + (i - begin) * per_step

        end, keys_end = n_blocks, count
        if per_step > 1:
            end = begin + (n_blocks - begin + per_step - 1) // per_step
            keys_end = jnp.minimum(count, n_blocks * page)

        def block_body(i, carry):
            m, l, acc, step = carry
            buf = step % _BUFFERS
            more = i + 1 < end
            nslot = jnp.where(more, slot, next_ref[slot + 1])
            nblock = jnp.where(more, at(i + 1), first_block(nslot))

            @pl.when(nslot < slots)
            def _():
                start(nslot, nblock, (step + 1) % _BUFFERS)

            page0 = at(i)
            fetched = copies(slot, page0, buf)
            for held, k_copy, _ in fetched:
                pl.when(held)(k_copy.wait)
            q = q_ref[slot]
            k = k_buf[buf].reshape(rows, hd).astype(kv_dtype)
            s = lax.dot_general(q, k.astype(q.dtype), (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
            if quantized:
                s = s * _scale_row(ks_ref, slot, page0, per_step)
            # the step's first row is key ``page0 * page``; rows past the
            # walk's last key are unseen, an unfetched page's among them
            seen = own_head & (token < keys_end - page0 * page)
            if window is not None:
                seen = seen & (token >= count - window - page0 * page)
            if selects:
                seen = seen & (_scale_row(flags_ref, slot, page0,
                                          per_step) > 0)
            s = jnp.where(seen, s, _MASKED)
            m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)
            l = alpha * l + p.sum(axis=-1, keepdims=True)
            for held, _, v_copy in fetched:
                pl.when(held)(v_copy.wait)
            v = v_buf[buf].reshape(rows, hd).astype(kv_dtype)
            if quantized:
                p = p * _scale_row(vs_ref, slot, page0, per_step)
            acc = alpha * acc + lax.dot_general(
                p.astype(kv_dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return m_new, l, acc, step + 1

        m, l, acc, step = lax.fori_loop(
            begin, end, block_body,
            (jnp.full((nh, 1), -jnp.inf, jnp.float32),
             jnp.zeros((nh, 1), jnp.float32),
             jnp.zeros((nh, hd), jnp.float32), step))
        # a slot with no keys: zeros (l == 0), never a NaN
        o_ref[slot] = (acc / jnp.where(l > 0, l, 1.0)).astype(o_ref.dtype)
        return step

    lax.fori_loop(0, slots, slot_body, 0)


def _scale_row(scales_ref, slot, block, per_step):
    """The scales (or the selection's flags) of a step's rows [1, rows],
    laid out as its score columns are: those of its pages, from ``block``
    on, side by side."""
    # (``block + 0`` would be one more instruction of a one-page step)
    pages = [scales_ref[slot, pl.ds(block + j if j else block, 1), :]
             for j in range(per_step)]
    return pages[0] if per_step == 1 else jnp.concatenate(pages, axis=1)


def paged_decode_attention_kernel(q, k_pages, v_pages, k_scale, v_scale,
                                  layer, table, pos, active, selected=None,
                                  *, window=None, interpret=False):
    """The kernel's launch; arguments as ``paged_decode_attention``."""
    slots, nh, hd = q.shape
    layers, num_pages, page, nkv, _ = k_pages.shape
    pb = table.shape[1]
    per_step = step_pages(k_pages)
    quantized = k_pages.dtype == jnp.int8
    count = jnp.where(active, pos + 1, 0).astype(jnp.int32)
    # next_live[0]: the first slot with keys; next_live[s + 1]: the first
    # after s (``slots`` when there is none)
    idx = jnp.arange(slots, dtype=jnp.int32)
    live_at = jnp.where(count > 0, idx, slots)
    after = lax.cummin(live_at, reverse=True)
    next_live = jnp.concatenate([after, jnp.full((1,), slots, jnp.int32)])
    table_c = jnp.maximum(table, 0).astype(jnp.int32)
    buffer = (_BUFFERS, page, nkv, hd)
    if per_step > 1:
        # a page as its rows [page * nkv, hd], which is how it lies in
        # the pool (a bitcast): they arrive dense in a step's buffer
        k_pages, v_pages = (pool.reshape(layers, num_pages, page * nkv, hd)
                            for pool in (k_pages, v_pages))
        buffer = (_BUFFERS, per_step * page * nkv, hd)
    operands = [q, k_pages, v_pages]
    in_specs = [pl.BlockSpec(memory_space=pltpu.VMEM),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY)]
    if quantized:
        # the window's scales as rows [B, PB, page * nkv]: 1/32 of the
        # window's bytes, laid out as the score columns are (and as many
        # pages more as a step that begins at the table's last may read)
        scaled = table_c
        if per_step > 1:
            scaled = jnp.pad(table_c, ((0, 0), (0, per_step - 1)))
        operands += [k_scale[layer, scaled].reshape(slots, -1, page * nkv),
                     v_scale[layer, scaled].reshape(slots, -1, page * nkv)]
        in_specs += [pl.BlockSpec(memory_space=pltpu.VMEM)] * 2
    static = dict(pages_per_slot=pb, page=page, nkv=nkv, per_step=per_step,
                  quantized=quantized, window=window)
    if selected is not None:
        # the selection as rows [B, PB, page * nkv]: a key's flag under
        # each of its KV heads' score columns (and as many pages of
        # zeros more as a step that begins at the table's last may read)
        flags = jnp.repeat(selected.reshape(slots, pb, page), nkv, axis=-1)
        operands.append(jnp.pad(flags.astype(jnp.int32),
                                ((0, 0), (0, per_step - 1), (0, 0))))
        in_specs.append(pl.BlockSpec(memory_space=pltpu.VMEM))
        static["selects"] = True
    return pl.pallas_call(
        functools.partial(_kernel, **static),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(1,), in_specs=in_specs,
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            scratch_shapes=[
                pltpu.VMEM(buffer, k_pages.dtype),
                pltpu.VMEM(buffer, v_pages.dtype),
                pltpu.SemaphoreType.DMA((2, _BUFFERS))]),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret, name=KERNEL_NAME,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), table_c.reshape(-1), count,
      next_live, *operands)


@functools.cache
def _lowerings(window):
    """The two lowerings for a layer of this window, made once a window:
    see ``paged_decode_attention`` on why they are not made a call."""
    if window is None:
        return paged_decode_attention_kernel, paged_decode_attention_reference
    return (functools.partial(paged_decode_attention_kernel, window=window),
            functools.partial(paged_decode_attention_reference,
                              window=window))


def paged_decode_attention(q, k_pages, v_pages, k_scale, v_scale, layer,
                           table, pos, active, *, window=None,
                           selected=None):
    """One decode step's attention for every slot, scores scaled by
    ``page_attention_scale(head_dim)`` (this entry's choice, stated
    there: a block with another scale folds the ratio into the q it hands
    over). q [B, nh, hd]; stacked pools [L, P, page, nkv, hd]
    (bf16, or int8 with their scale pools [L, P, page, nkv]); ``layer`` a
    scalar; ``table`` [B, PB] page ids (-1 = hole); slot b attends key
    positions <= pos[b] of its pages, with ``window`` (static: a sliding
    layer's) those > pos[b] - window alone, if ``active[b]`` (a dead
    slot's row is unspecified, and discarded); with ``selected`` ([B, PB
    x page] bool: a layer that picks its keys) over those of them alone.
    Returns [B, nh, hd] in q's dtype.

    The two branches are the module's own functions, not closures made a
    call: JAX then keeps their traces, and an engine's decode programs
    that differ in their chunk alone trace them once (a trace of both is
    0.2-0.3 s of a program's set-up on the chip's host)."""
    kernel, reference = _lowerings(window)
    args = (q, k_pages, v_pages, k_scale, v_scale, layer, table, pos, active)
    if selected is not None:
        args += (selected,)
    with jax.named_scope(scopes.ATTN):
        return lax.platform_dependent(*args, tpu=kernel, default=reference)
