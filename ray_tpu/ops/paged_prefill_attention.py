"""Prefill attention over the KV pages where they lie: one Pallas kernel.

Reference: ABSENT from the reference repo (SURVEY P15). The paged
engine's prefill program writes a layer's new K and V rows into the pages
and then attends the suffix queries over their rows' pages. The plain
formulation (``paged_prefill_attention_reference``) copies each row's
whole page window out of the pool and calls ``cached_attention`` on the
copy, which writes the float32 scores [n, heads, T, S] to HBM, masks
them, reads them for the max, again for the sum, writes bf16
probabilities and reads those: two cold 2048-token prompts at 32 heads
are 1 GiB of scores a layer. The kernel reads the pages of the STACKED
pool [L, P, page, nkv, hd] in place and keeps scores and softmax state in
VMEM:

- the grid is (row, PAIR of KV heads, query block). A query block is
  ``block_q`` positions; each of the pair's KV heads has its ``heads /
  nkv`` query heads folded into the rows of one matrix [heads / nkv *
  block_q, hd], so a head's scores are one matmul against that head's
  keys and nothing is computed for another head's. Grouped (32/8, 48/8)
  and plain (16/16) attention are one code at different shapes. (The
  decode kernel scores every query head against every KV head's rows of
  a page and masks: free where the page's bytes bound the step, ``nkv``
  times the arithmetic here.) Why pairs: in the pool's layout a token's
  KV heads 2p and 2p + 1 are the two halves of one 32-bit row (bf16
  packs two rows a sublane), so no copy and no read can take ONE head
  out of a page; the pair's words are one strided 32-bit read of the
  page in VMEM, and each half is its head's value. A page is so fetched
  ``nkv / 2`` times a query block: 0.4 ms of the bandwidth at two cold
  2048-token prompts, behind the arithmetic;
- the pools stay in HBM (``pl.ANY``); the layer index, the flattened page
  tables, ``starts`` and ``slens`` come through scalar prefetch. A
  block's walk goes over its row's pages in CHUNKS of ``chunk_pages``
  pages (512 keys): each page [page, nkv, hd] is one contiguous async
  copy into the chunk's buffer, double buffered, the next chunk's copies
  in flight while this one is computed; the last chunk of a block starts
  the first chunk of the next live block (of this pair, the next pair or
  the next row), so only the very first copy of a call is waited for
  with nothing to do;
- the walk of query block ``i`` ends at the chunk that holds its last
  valid query's position, ``starts + min((i + 1) * block_q, slens) - 1``:
  pages wholly after it (a cold prompt's causal half, the window's tail
  past the row's context) are neither fetched nor computed, and a block
  of padding alone (``i * block_q >= slens``) is zeros at no cost. Every
  chunk is masked by position (v5e: 1.06 ms a call at two cold 2048-token
  prompts; masking only the chunks that straddle a block's diagonal, as a
  second loop, 1.18, and as a branch in one loop, 1.68);
- under a ``window`` (static: a sliding layer's) the walk has its other
  end too. It STARTS at the chunk that holds the oldest key the block's
  first query sees, ``starts + i * block_q - window + 1`` (clamped at 0):
  the chunks wholly before every query's window are neither fetched nor
  computed, and whoever starts a block's first copies (the grid's first
  block, the block before it, a block of padding) starts THAT chunk's.
  The mask gains its second side, key ``> qpos - window``. A chunk can
  be wholly masked for some rows of a block (the first one walked, for
  the block's last rows): ``_MASKED`` is finite, so such a row's ``m``
  rises at its first visible key and ``alpha`` zeroes what it summed
  before. Without a window none of this is traced (the branches are
  Python's, on the static argument): a full layer's kernel has no
  first-chunk arithmetic and a one-sided mask, and its digests hold
  (``tests/test_tpu_compile_kernels.py``);
- online softmax in float32, probabilities cast to the pages' dtype
  before the weighted sum and the sum divided by the float32 denominator
  at the end, as the decode kernel does (``cached_attention`` normalises
  first: one unit in the last place of a bf16 output apart).

- a full layer that PICKS ITS KEYS (``flags``: a learned selection,
  ``ops/index_select.py:selection_flags``) hands the kernel ``kept``'s
  set as one byte a query and key [n, T, S]: a block's flags against
  every key of the table [1, block_q, S] come in with its queries (the
  pipeline's own copy, once a pair: 1 MiB at 128 queries of 8,192 keys),
  a chunk's columns of them are tiled over the group's query heads and
  ANDed into the mask; the kernel computes no index score, no top-k and
  no tie, and fetches every page of the walk as before. A chunk none of
  whose keys a row's set holds, met before any that it has, sums ones
  under the finite ``_MASKED``, which the ``alpha`` of its first such
  key zeroes. Without flags none of this is traced;

``paged_prefill_attention`` is the entry. The kernel ENGAGES by one rule
on the traced shapes (``kernel_engages``): bf16 pools, and float32
scores of the plain path for the layer over ``KERNEL_SCORES_BYTES`` (256
MiB): a full layer's [n, heads, T, the table's keys]; a sliding layer's
what its plain path writes block by block, [n, heads, T, ``query_block``
+ window] (or the table's keys where that is narrower).
Every program that holds the kernel pays its trace and Mosaic lowering in
every run, warm too, so the small programs (a prefix hit's suffix, a
short prompt) stay the plain path's, with the lowered text they had. Over
the rule, ``jax.lax.platform_dependent`` chooses where the program is
LOWERED: the kernel for a TPU, the plain formulation for anything else.
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

KERNEL_NAME = "paged_prefill_attn"
_MASKED = -0.7 * float(jnp.finfo(jnp.float32).max)
_BUFFERS = 2
# rows of a query block's matrix (query heads of a KV head x positions)
# and keys of a chunk: scores [1024, 512] float32 are 2 MiB of VMEM
_BLOCK_ROWS = 1024
_CHUNK_KEYS = 512
# of the core's 128 MiB: the query and output blocks twice, two chunks of
# K and V pages, a pair's scores, probabilities and accumulators (about
# 20 MiB at 48 heads over 8)
_VMEM_BYTES = 48 << 20

# Float32 scores of one ``cached_attention`` call that the plain
# formulation may hold, [n, heads, T, S] (the call keeps about twice that
# beside them): past it the plain path goes over its queries in blocks
# whose scores are a quarter of it. From the traced shapes, as ``ops.moe``
# picks its formulation; 1 GiB is two cold prompts of 2048 tokens at 32
# heads.
SCORES_MAX_BYTES = 1 << 30

# The same float32 scores past which a full layer over bf16 pages takes
# the kernel on a TPU. A program that holds the kernel costs the host
# 0.25-0.5 s more to trace and lower in every run (v5e's host), so the
# line is drawn where the kernel saves a dispatch more than a few
# milliseconds: over 256 MiB are the cold prompts of 2048 tokens at 32
# heads and of 1024 behind a cached prefix (three of serve-chat's thirty
# warm programs, two of serve-doc's twelve); at 64 MiB, where ten of
# serve-chat's programs held it, that cell's set-up was 12% longer.
KERNEL_SCORES_BYTES = 256 << 20


def _pow2_floor(n: int) -> int:
    return 1 << (max(n, 1).bit_length() - 1)


def query_block(n: int, t: int, heads: int, keys: int, window) -> int:
    """How many of a prefill's ``t`` query positions a row attends with at
    once in the plain formulation (``t``: all of them). A full layer goes
    whole while its scores fit ``SCORES_MAX_BYTES``, and past that in
    blocks whose scores are a quarter of it. A sliding layer goes window
    by window, a block of ``window`` queries over the two windows of keys
    it can see, whatever ``t``, under the same bound: where those scores
    (of the ``t`` queries, where they are fewer than a window) pass
    ``SCORES_MAX_BYTES``, in blocks of fewer queries, each over its
    ``block + window`` keys, whose scores are a quarter of it. That is a
    window of thousands of keys, or a wide group under a small one: at a
    window of 512 and 72 heads, groups of 8 rows and more
    (``4 * n * 72 * 512 * 1024``), which go in blocks of 128; up to 7
    rows a window of 512 goes as it did before the bound.

    Since the kernel takes a window the sliding branch serves the
    programs UNDER the rule (a cached document's question, a short
    suffix) and every platform but the TPU; ``kernel_engages`` reads the
    block it gives to say what the plain path would write."""
    def scores(block):
        seen = keys if window is None else block + window
        return 4 * n * heads * block * seen

    block = t if window is None else 1 << (window - 1).bit_length()
    if scores(min(block, t)) > SCORES_MAX_BYTES:
        while block > 16 and scores(block) > SCORES_MAX_BYTES // 4:
            block //= 2
    return block if block < t and t % block == 0 else t


def kernel_engages(q_shape, pools, table_width: int, window) -> bool:
    """The rule (module docstring), from shapes, the layer's kind and the
    pools' dtype: whether the attention of a prefill's queries ``q_shape``
    [n, T, heads, hd] over ``table_width`` pages a row of ``pools`` (a
    stacked pool, or its shape and dtype), under ``window`` keys where the
    layer slides, is the kernel's on a TPU. The kernel reads KV heads in
    pairs and head sizes in whole lanes."""
    n, t, heads, hd = q_shape
    page, nkv = pools.shape[2:4]
    keys = table_width * page
    if window is not None:
        # what the plain path writes for the layer: every block of its
        # queries against the block's and a window's keys
        keys = min(keys, query_block(n, t, heads, keys, window) + window)
    return (pools.dtype == jnp.bfloat16 and nkv % 2 == 0 and hd % 128 == 0
            and 4 * n * heads * t * keys > KERNEL_SCORES_BYTES)


def paged_prefill_attention_reference(q, k_pages, v_pages, k_scale, v_scale,
                                      layer, table_rows, starts, slens=None,
                                      flags=None, *, window=None):
    """The gather formulation: one gather of the rows' whole tables and
    one ``cached_attention`` where that fits; past ``SCORES_MAX_BYTES``
    the same call on blocks of queries, one after another (one program,
    one dispatch: the host sees nothing of it). A sliding layer goes in
    blocks of its window, and for each gathers only the pages that the
    block's queries can see; with ``flags`` ([n, T, S] int8: a full
    layer that picks its keys) a query's softmax runs over the keys it
    flags alone. What the kernel is held to, and what every platform but
    the TPU runs; ``slens`` is the kernel's to use (the rows of padding
    are computed here)."""
    del slens
    if flags is not None and window is not None:
        raise ValueError("a sliding layer takes no selection")
    n, t, heads, hd = q.shape
    mp = table_rows.shape[1]
    page_size, nkv = k_pages.shape[2], k_pages.shape[3]
    block = query_block(n, t, heads, mp * page_size, window)
    # the pages that hold the keys of ``block`` queries' windows
    seen = (mp if window is None
            else -(-(block + window - 2) // page_size) + 1)

    def attend(q, first, chosen=None):
        """``q`` [n, block, heads, hd], the first of them at ``first``;
        ``chosen`` [n, block, S]: their flags, where the layer selects."""
        rows, where = table_rows, {}
        if chosen is not None:
            where = {"seen": chosen != 0}
        if window is not None:
            rows, key_start = visible_pages(table_rows, first - window + 1,
                                            seen, page_size)
            where = {"window": window, "key_start": key_start}
        # gathered AFTER the suffix writes: queries attend over cached
        # prefix + their own fresh KV; positions beyond start+i are
        # masked causally, stale page contents beyond the prompt never
        # influence the result
        kg, vg = gather_kv_window(k_pages, v_pages, k_scale, v_scale, layer,
                                  rows)
        return cached_attention(q, kg.reshape(n, -1, nkv, hd),
                                vg.reshape(n, -1, nkv, hd), first,
                                scale=page_attention_scale(hd), **where)

    if block == t:
        return attend(q, starts, flags)
    firsts = starts[None, :] + block * jnp.arange(
        t // block, dtype=jnp.int32)[:, None]                  # [blocks, n]
    qb = jnp.moveaxis(q.reshape(n, t // block, block, heads, hd), 1, 0)
    xs = (qb, firsts)
    if flags is not None:
        xs += (jnp.moveaxis(flags.reshape(n, t // block, block, -1), 1, 0),)
    out = jax.lax.map(lambda xs: attend(*xs), xs)
    return jnp.moveaxis(out, 0, 1).reshape(n, t, heads, hd)


def _kernel(layer_ref, table_ref, starts_ref, slens_ref,        # SMEM
            q_ref, k_hbm, v_hbm, *rest, grid, pages_per_row, chunk_pages,
            window, selects=False):
    """See the module docstring. ``q_ref`` / ``o_ref``: the query heads of
    the pair's two KV heads at ``block_q`` positions, [1, block_q, 2 *
    group * hd]; before ``o_ref``, where the layer ``selects``, the
    block's flags [1, block_q, S]; ``steps_ref``: the chunks walked so far, which
    names the buffer the next one lands in, carried from block to block.
    ``window`` is static: under None a walk starts at chunk 0 and no
    statement of a first chunk is traced."""
    if selects:
        flags_ref, *rest = rest
    o_ref, k_buf, v_buf, sem, steps_ref = rest
    n, pairs, nq = grid
    b, pair, qi = (pl.program_id(i) for i in range(3))
    bq, hd = q_ref.shape[1], k_hbm.shape[4]
    page, nkv = k_hbm.shape[2], k_hbm.shape[3]
    group = q_ref.shape[2] // (2 * hd)
    rows = group * bq
    ck = chunk_pages * page
    scale = page_attention_scale(hd)
    layer = layer_ref[0]

    def page_copy(which, row, chunk, c, buf):
        """The copy of page ``c`` of a chunk, K's (0) or V's (1): a whole
        page, which is contiguous in the pool."""
        p = table_ref[row * pages_per_row + chunk * chunk_pages + c]
        hbm, vmem = ((k_hbm, k_buf), (v_hbm, v_buf))[which]
        return pltpu.make_async_copy(
            hbm.at[layer, p], vmem.at[buf, pl.ds(c * page, page)],
            sem.at[which, buf])

    def start(row, chunk, buf):
        @pl.loop(0, chunk_pages)
        def _(c):
            for which in range(2):
                page_copy(which, row, chunk, c, buf).start()

    def wait(which, buf):
        """Until the chunk in ``buf`` has all its K or V pages."""
        @pl.loop(0, chunk_pages)
        def _(c):
            page_copy(which, 0, 0, c, buf).wait()

    def heads_of_pair(pages, buf):
        """The chunk's rows [ck, hd] of this block's two KV heads. A page
        holds KV heads 2p and 2p + 1 of a token in the halves of one
        32-bit row (bf16 packs two rows a sublane), so no single head can
        be sliced out of it: the pair's words are one strided read, and
        each half, moved to the top of a word, is that head's value as
        float32, exactly."""
        words = pages.at[buf].reshape(ck * nkv, hd).bitcast(jnp.uint32)[
            pl.ds(pair, ck, stride=nkv // 2), :]
        return [pltpu.bitcast(half, jnp.float32).astype(pages.dtype)
                for half in (words << 16, words & jnp.uint32(0xFFFF0000))]

    def live(row, block):
        return block * bq < slens_ref[row]

    def first_chunk(row, block):
        """The chunk that holds the oldest key a query of ``block`` sees:
        its first query's, ``window - 1`` keys back (a full layer: 0)."""
        if window is None:
            return 0
        oldest = (starts_ref[jnp.minimum(row, n - 1)] + block * bq
                  - (window - 1))
        return jnp.clip(oldest, 0, pages_per_row * page - 1) // ck

    # this block and the one after it, in the grid's order
    this = (b * pairs + pair) * nq + qi
    nxt = this + 1
    nb, nqi = nxt // (pairs * nq), nxt % nq
    next_live = (nxt < n * pairs * nq) & live(jnp.minimum(nb, n - 1), nqi)
    next_chunk0 = first_chunk(nb, nqi)

    @pl.when(this == 0)
    def _():
        steps_ref[0] = 0

        @pl.when(live(0, 0))
        def _():
            start(0, first_chunk(0, 0), 0)

    first = qi * bq
    row_start, slen = starts_ref[b], slens_ref[b]

    @pl.when(first < slen)
    def _():
        # keys the block's last valid query sees, and the chunks that
        # hold them
        visible = jnp.minimum(row_start + jnp.minimum(first + bq, slen),
                              pages_per_row * page)
        n_chunks = (visible + ck - 1) // ck
        chunk0 = first_chunk(b, qi)
        step0 = steps_ref[0]

        def walked(j):
            """The chunks of this block's walk before chunk ``j``: they,
            not its place in the row, name a chunk's buffer."""
            return j if window is None else j - chunk0
        # a KV head's rows: query head r of its group at position i ->
        # r * bq + i
        qs = [jnp.concatenate(
            [q_ref[0, :, (h * group + r) * hd:(h * group + r + 1) * hd]
             for r in range(group)], axis=0) for h in range(2)]
        qpos = row_start + first + lax.broadcasted_iota(
            jnp.int32, (rows, 1), 0) % bq
        kcol = lax.broadcasted_iota(jnp.int32, (1, ck), 1)

        def chunk_body(j, carry):
            buf = (step0 + walked(j)) % _BUFFERS
            # the copies after this chunk's: the block's next chunk, or
            # the first of the next live block
            more = j + 1 < n_chunks

            @pl.when(more | next_live)
            def _():
                start(jnp.where(more, b, nb),
                      jnp.where(more, j + 1, next_chunk0),
                      (step0 + walked(j) + 1) % _BUFFERS)

            wait(0, buf)
            if selects:
                # the chunk's columns of the block's flags, under every
                # query head of the group (row r * bq + i is position i)
                flagged = jnp.concatenate(
                    [flags_ref[0, :, pl.ds(pl.multiple_of(j * ck, ck), ck)
                               ].astype(jnp.int32)] * group, axis=0) > 0
            probs = []
            for (m, l, _), q, k in zip(carry, qs, heads_of_pair(k_buf, buf)):
                s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
                seen = j * ck + kcol <= qpos
                if window is not None:
                    seen &= j * ck + kcol > qpos - window
                if selects:
                    seen &= flagged
                s = jnp.where(seen, s, _MASKED)
                m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
                alpha = jnp.exp(m - m_new)
                p = jnp.exp(s - m_new)
                l = alpha * l + p.sum(axis=-1, keepdims=True)
                probs.append((m_new, l, alpha, p.astype(v_buf.dtype)))
            wait(1, buf)
            out = []
            for (m, l, alpha, p), (_, _, acc), v in zip(
                    probs, carry, heads_of_pair(v_buf, buf)):
                acc = alpha * acc + lax.dot_general(
                    p, v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                out.append((m, l, acc))
            return tuple(out)

        carry = tuple((jnp.full((rows, 1), -jnp.inf, jnp.float32),
                       jnp.zeros((rows, 1), jnp.float32),
                       jnp.zeros((rows, hd), jnp.float32))
                      for _ in range(2))
        carry = lax.fori_loop(chunk0, n_chunks, chunk_body, carry)
        steps_ref[0] = step0 + walked(n_chunks)
        for h, (_, l, acc) in enumerate(carry):
            # every valid row sees its own key, so l > 0 (a row whose
            # walk held nothing it sees, which is padding: l = the keys
            # walked, every one at ``_MASKED``)
            out = (acc / l).astype(o_ref.dtype)
            for r in range(group):
                at = (h * group + r) * hd
                o_ref[0, :, at:at + hd] = out[r * bq:(r + 1) * bq]

    @pl.when(first >= slen)
    def _():
        # a block of padding: zeros, and the next live block's first chunk
        o_ref[...] = jnp.zeros_like(o_ref)

        @pl.when(next_live)
        def _():
            start(nb, next_chunk0, steps_ref[0] % _BUFFERS)


def paged_prefill_attention_kernel(q, k_pages, v_pages, k_scale, v_scale,
                                   layer, table_rows, starts, slens,
                                   flags=None, *, window=None,
                                   interpret=False):
    """The kernel's launch; arguments as ``paged_prefill_attention`` (bf16
    pools; ``window`` static, a sliding layer's or None; ``flags`` a
    full layer's that selects)."""
    del k_scale, v_scale
    n, t, heads, hd = q.shape
    _, _, page, nkv, _ = k_pages.shape
    wp = table_rows.shape[1]
    group = heads // nkv
    bq = min(t, max(16, _pow2_floor(_BLOCK_ROWS // group)))
    chunk_pages = min(_pow2_floor(max(1, _CHUNK_KEYS // page)),
                      wp & -wp)       # a power of two that divides wp
    grid = (n, nkv // 2, t // bq)
    block = pl.BlockSpec((1, bq, 2 * group * hd),
                         lambda b, g, i, *_: (b, i, g))
    buffers = (_BUFFERS, chunk_pages * page, nkv, hd)
    static = dict(grid=grid, pages_per_row=wp, chunk_pages=chunk_pages,
                  window=window)
    in_specs = [block, pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY)]
    selection = ()
    if flags is not None:
        in_specs.append(pl.BlockSpec((1, bq, wp * page),
                                     lambda b, g, i, *_: (b, i, 0)))
        selection = (flags,)
        static["selects"] = True
    out = pl.pallas_call(
        functools.partial(_kernel, **static),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=grid,
            in_specs=in_specs,
            out_specs=block,
            scratch_shapes=[
                pltpu.VMEM(buffers, k_pages.dtype),
                pltpu.VMEM(buffers, v_pages.dtype),
                pltpu.SemaphoreType.DMA((2, _BUFFERS)),
                pltpu.SMEM((1,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((n, t, heads * hd), q.dtype),
        # blocks run in the grid's order: each starts the next one's copy
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3,
            vmem_limit_bytes=_VMEM_BYTES),
        interpret=interpret, name=KERNEL_NAME,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      jnp.maximum(table_rows, 0).astype(jnp.int32).reshape(-1),
      starts.astype(jnp.int32), slens.astype(jnp.int32),
      q.reshape(n, t, heads * hd), k_pages, v_pages, *selection)
    return out.reshape(n, t, heads, hd)


def paged_prefill_attention(q, k_pages, v_pages, k_scale, v_scale, layer,
                            table_rows, starts, slens, *, window=None,
                            flags=None):
    """A prefill's attention for one layer, scores scaled by
    ``page_attention_scale(head_dim)`` (this entry's choice, stated
    there: a block with another scale folds the ratio into the q it hands
    over). q [n, T, heads, hd] at positions ``starts + i``; stacked pools
    [L, P, page, nkv, hd] (bf16, or int8 with their scale pools [L, P,
    page, nkv]) whose rows of this prefill are written; ``layer`` a
    scalar; ``table_rows`` [n, wp] page ids (-1 = hole); query ``i`` of
    row ``b`` attends key positions <= starts[b] + i of its pages, with
    ``window`` (static: a sliding layer's) those > starts[b] + i - window
    alone, with ``flags`` ([n, T, wp x page] int8: a full layer that
    picks its keys) those it flags alone. ``slens`` [n]: the rows' valid
    queries; the rows of padding past them come back finite and
    otherwise unspecified. Returns [n, T, heads, hd] in q's dtype.

    Under the rule (``kernel_engages``) this IS the plain formulation,
    called directly: the program's lowered text is what it was. Over it
    the two lowerings are one pair of partials a window (``_lowerings``),
    not closures made a call, so the programs of an engine trace them
    once."""
    with jax.named_scope(scopes.ATTN):
        if not kernel_engages(q.shape, k_pages, table_rows.shape[1], window):
            return paged_prefill_attention_reference(
                q, k_pages, v_pages, k_scale, v_scale, layer, table_rows,
                starts, flags=flags, window=window)
        kernel, plain = _lowerings(window)
        args = (q, k_pages, v_pages, k_scale, v_scale, layer, table_rows,
                starts, slens)
        if flags is not None:
            args += (flags,)
        return lax.platform_dependent(*args, tpu=kernel, default=plain)


@functools.lru_cache(maxsize=None)
def _lowerings(window):
    """The kernel and the plain formulation under ``window``: one pair of
    partials a window, the same objects at every call."""
    return (functools.partial(paged_prefill_attention_kernel, window=window),
            functools.partial(paged_prefill_attention_reference,
                              window=window))
