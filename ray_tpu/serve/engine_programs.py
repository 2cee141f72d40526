"""The paged engine's device programs and the stores they carry.

What the host loop (``serve/paged_llm.py``) dispatches, and nothing of
scheduling: the stores a model's layer plan states (``store_shapes``,
stated once: the engine allocates from it and a test lowers a cell's
program over it), the two programs over them with their jit caches and
names, the order of their arguments (``_Order``, stated once) and their
bodies as they are jitted (``bound_program``), and which kernels a
program of given shapes runs (``EnginePrograms.prefill_kernels``,
``decode_kernels``).

- The KV cache is a POOL of fixed-size pages [L, P, page, nkv, hd] over
  the L layers that ATTEND, uniform over them whatever their kind (a
  model's full and sliding layers share its KV heads and head size; a
  sliding layer keeps every page too, and reads only its window's:
  releasing what has fallen out of every window is ROADMAP Queue 2 B.1).
  What a layer holds and does is the layer plan's to say, run by run
  (``LayerStack``), and every store has as many layers as the plan has
  layers that keep it (``_pool_slices``, ``_places``). A page id names
  the same ``page_size`` tokens in every pool, so the loop's one page
  table, one allocator and one prefix cache serve them all. A run may
  keep the K/V twins AND further rows beside them (``LayerStack.beside``:
  the key of an indexer that picks, among K/V rows, the ones a query
  attends over): its format is the twins' four pools and a pool a row
  behind them, written in the same step at the same place; a shared page
  carries the index keys as it carries K and V, each a function of the
  token's prefix alone.
- Both programs attend over the pages WHERE THEY LIE: a layer's new
  rows are written, then read through the page table by the entries of
  ``ops/paged_decode_attention.py``, ``ops/paged_prefill_attention.py``
  and ``ops/latent_attention.py``, each of which says what it reads and
  when it is a Pallas kernel (chosen where the program is lowered, by
  nothing else). The table's width sets no bytes a decode step reads.
  KV heads of half a lane tile (64) lie two a row of the pools
  (``ops/paged_attention.py:rows_of_heads``, which says why): a block's
  q, k and v pass through it behind the module's projections, and the
  attention's result through ``own_parts``; both are the identity, and
  nothing traced, where a head fills its row.
- ``kv_dtype="int8"`` stores K/V pages quantized (per-token-per-head
  symmetric scales in a parallel scale pool): half the KV HBM. The
  decode kernel is compute-bound over int8 pages (conversion on the
  VPU), so a step's attention takes about as long as over bf16 pages
  (v5e, kernel alone: 326 against 283 us a layer at 32 slots of 1-1.9k
  tokens): int8 is a CAPACITY trade, the right default only when KV
  footprint is the binding constraint (long contexts / many concurrent
  slots).
- The programs keep the pools IN PLACE: the layer loop carries the
  stacked pools (and scale pools) whole, beside the activations, and
  scans over (layer weights, layer index), one scan for each run of
  identical layers in the model's layer plan, the runs in order over the
  pools' layers; a layer scatters its new rows at [layer, page, offset]
  and reads its pages at [layer, table] (the format:
  ``ops/paged_attention.py``). Scanning OVER the pools instead hands
  each layer a slice: XLA then copies every layer's K and V pool out and
  back, every layer of every step, and the prefill program holds a
  second pool (measured on a v5e at 12 layers x 544 pages: 43% of the
  device's time, 5.9 GB of HBM).
- The WEIGHTS stay in place too: the decode program projects q, k and v
  from ONE stack where the block's module states how
  (``fuse_attention_projections``, which says why: a stack the compiler
  can park on the core is written back and fetched again WHOLE round
  every layer's attention kernel, 201 MB a layer-step at Mistral-7B's
  widths, a quarter of a step), built once at the program's entry,
  outside the step and layer loops; ``params`` stay the caller's, in the
  published layout. Such a move has no name of its own in a trace
  (``copy-done``, ``slice-done`` of stacked-weight shape among the
  costliest operations is all that shows); to see one, compile the
  program for a described chip and look for ``S(1)`` in the layout of a
  weight stack inside a loop
  (``tests/compiled_text.py:stack_moves_in_loops``).
- Where the plan has a RECURRENT run (``LayerStack.state``: a
  state-space mixer, beside the attention on the same input as
  Falcon-H1's or a layer's one sublayer as Nemotron-H's), a sequence's
  state is its pages and, per layer of such a run, the arrays the run
  states, which do not grow with the context. They live in the SLOT: one
  array a kind [L', max_batch, ...], carried and donated as the pools
  are. A prefill runs the mixer over the padded prompt from the zero
  state (padding moves nothing) and INSTALLS each row's state after its
  last token at [layer, slot], whole; a decode step advances the active
  slots' states where they lie and leaves the others' as they were, bit
  for bit (``ops/ssm.py:ssm_state_step``). A slot that finishes
  mid-chunk decodes on, and its state is garbage afterwards: the next
  tenant's prefill overwrites it before any decode step of that tenant
  runs (the device runs dispatches in order).
- Where the plan says its state is worth a PAGE's keeping
  (``RecurrentState.pages_keep``: a state of kilobytes), every page
  keeps the state at its END: one more store an array of the state,
  [L', num_pages, ...] under the page's own id, so that the page is
  allocated, shared, refcounted, evicted and recycled with its state by
  the one allocator and the one prefix cache, which know nothing of it.
  The PREFILL program carries these stores beside the pools and the
  slots' state (``_Order.keeps``): a row whose ``starts`` is ``k`` pages
  begins from what page ``k - 1`` of its table keeps (0: from zeros),
  and the state after the last token of every page the suffix completes
  is written to that page's place in the same program that writes the
  page's K and V, before the page can be registered. The decode
  programs neither take nor write them: only freshly PREFILLED full
  pages are ever registered (``PrefixCache.insert``), so a page that
  decode fills is never looked up (registering answers, for multi-turn
  reuse, would have decode write them: ROADMAP Queue 2 B.5). Over such
  a plan the prefix is reusable; over a plan whose state pages do not
  keep it is not, and the engine refuses the cache.
- What is the MODEL's comes from the model's module (``_model_module``).
  What is the ENGINE's is here, once for every model: the page write,
  the attention over the pages, the scans over the plan's runs,
  sampling, the chunk loop. A block is projections, attention, its
  output, the feed-forward on the stream behind it; where a run's
  feed-forward needs something of the layer's INPUT too (a router that
  reads the rows the projections read), the plan says so
  (``LayerStack.ahead``) and the block asks the module for it before the
  attention and hands it on after (``_ahead``).
"""

from __future__ import annotations

import math
import sys
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ray_tpu.models.decoding import select_tokens
from ray_tpu.ops import scopes
from ray_tpu.ops.index_select import (decode_selection, index_kernel_engages,
                                      prefill_selection)
from ray_tpu.ops.latent_attention import (latent_decode_attention,
                                          latent_kernel_engages,
                                          latent_prefill_attention,
                                          latent_prefill_kernel_engages,
                                          write_latent)
from ray_tpu.ops.moe import expert_kernel_engages
from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.paged_attention import (ROW_LANES, own_parts, pool_heads,
                                         row_pool, rows_of_heads, write_kv,
                                         write_rows)
from ray_tpu.ops.paged_decode_attention import (paged_decode_attention,
                                                step_pages)
from ray_tpu.ops.paged_prefill_attention import (kernel_engages,
                                                 paged_prefill_attention)
from ray_tpu.ops.ssm import state_kernel_engages
from ray_tpu.serve.llm import _named_jit


class _Order(NamedTuple):
    """The order of one program's arguments and results, stated HERE for
    the program's body, its donation and its callers. Arguments: the
    weights; the POOLS the plan states (``_pool_slices``: for K/V twins
    ``k_pages, v_pages, k_scale, v_scale``); the host's ``inputs``, under
    these names; the slots' recurrent STATE arrays (none, for a plan of
    pages alone) and, only beside a state, ``beside_state``. Results: the
    pools, the ``results`` under these names, the state. Pools and state
    are donated, and come back in the place they went in at."""
    inputs: tuple
    results: tuple
    beside_state: tuple = ()
    # whether the program carries, behind the slots' state arrays, what
    # the PAGES keep of it (``store_shapes``'s third): donated and
    # returned with them, ``carried`` says in which order
    keeps: bool = False

    def carried(self, state, kept) -> tuple:
        """The state arrays a call of this program takes and returns: the
        slots', and behind them the pages' where it ``keeps``."""
        return (*state, *kept) if self.keeps else tuple(state)

    def arguments(self, weights, pools, inputs: dict, state) -> tuple:
        """A call's arguments."""
        beside = [inputs[name] for name in self.beside_state] if state else []
        return (weights, *pools, *(inputs[name] for name in self.inputs),
                *state, *beside)

    def taken(self, args, n_pools: int) -> tuple:
        """What a program's body was called with after the weights,
        apart: (the pools, {name: input}, the state)."""
        pools, rest = args[:n_pools], args[n_pools:]
        inputs = dict(zip(self.inputs, rest))
        state = rest[len(self.inputs):]
        if state and self.beside_state:
            at = len(state) - len(self.beside_state)
            inputs.update(zip(self.beside_state, state[at:]))
            state = state[:at]
        return pools, inputs, state

    def donated(self, n_pools: int, n_state: int) -> tuple:
        """The positions of the pools and the state among a call's
        arguments."""
        kinds = self.arguments(
            "weights", ["pool"] * n_pools,
            dict.fromkeys(self.inputs + self.beside_state, "input"),
            ["state"] * n_state)
        return tuple(i for i, kind in enumerate(kinds)
                     if kind in ("pool", "state"))

    def returned(self, pools, results: dict, state) -> tuple:
        """What a program's body returns."""
        return (*pools, *(results[name] for name in self.results), *state)

    def split(self, out, n_pools: int) -> tuple:
        """A call's outputs apart: (the pools, {name: result}, the
        state)."""
        at = n_pools + len(self.results)
        return (list(out[:n_pools]),
                dict(zip(self.results, out[n_pools:at])), tuple(out[at:]))


# decode: the (bucketed) page table [B, PB] and, a slot, its last token,
# length, whether it is live and its temperature; back come the [chunk, B]
# tokens, the advanced lengths, the merged last tokens and the
# feed-forward's statistics, all on the device
_DECODE = _Order(
    inputs=("table", "tokens", "lengths", "active", "temps", "key"),
    results=("toks", "lengths", "last", "stats"))
# prefill: each row's page table [n, W], its suffix tokens [n, T] padded to
# the bucket, the suffix's length, where it starts (the cached prefix's
# length), its temperature and, beside a state, the slot that state is
# installed in; back come the first tokens. Its state arrays are the
# slots' and then the pages' (a plan whose pages keep none: the slots')
_PREFILL = _Order(
    inputs=("table_rows", "tokens", "slens", "starts", "temps", "key"),
    results=("firsts",), beside_state=("slots",), keeps=True)


_PIECES = ("layer_plan", "embed", "head_logits")
_ATTENTION_PIECES = ("rotary_tables", "attention_output")
_KV_PIECES = ("attention_projections",)
_INDEX_PIECES = ("index_projections",)
_LATENT_PIECES = ("latent_projections",)
_RECURRENT_PIECES = ("recurrent_mixer", "recurrent_step")
_FEED_PIECES = ("feed_forward",)
_AHEAD_PIECES = ("feed_ahead",)


def _model_module(cfg):
    """The module that states ``cfg``'s block: the one its config class
    is defined in, which must hold the pieces its layer plan USES and no
    others: the plan itself, the stream's start and the head; where a
    run attends, what attention takes in (as q, k and v where the run
    keeps K/V twins, with an indexer's inputs where such a run picks its
    keys, as a latent's inputs where it keeps rows), its rotary tables
    and its end; the mixer's two forms where a run holds a
    recurrent mixer; the feed-forward where a run ends in one, and what
    it takes of the layer's input where a run states that
    (``LayerStack.ahead``)."""
    model = sys.modules.get(type(cfg).__module__)
    missing = [name for name in _PIECES if not hasattr(model, name)]
    if "layer_plan" not in missing:
        plan = model.layer_plan(cfg)
        attends = [run for run in plan if run.attends]
        asked = (
            _ATTENTION_PIECES * bool(attends)
            + _KV_PIECES * any(run.rows is None for run in attends)
            + _INDEX_PIECES * any(run.rows is None and run.selects is not None
                                  for run in attends)
            + _LATENT_PIECES * any(run.rows is not None for run in attends)
            + _RECURRENT_PIECES * (_recurrent(plan) is not None)
            + _FEED_PIECES * any(run.feeds for run in plan)
            + _AHEAD_PIECES * any(run.ahead for run in plan))
        missing += [name for name in asked if not hasattr(model, name)]
    if missing:
        raise TypeError(
            f"the paged engine cannot serve {type(cfg).__name__}: its "
            f"module {type(cfg).__module__} states no {', '.join(missing)}")
    return model


def _recurrent(plan):
    """What the plan's recurrent runs keep per sequence and layer
    (``LayerStack.state``), or None where no run holds a recurrent mixer.
    One statement a plan: the slots' arrays span the layers of every run
    that states it, and no other layer."""
    states = {run.state for run in plan if run.state is not None}
    if len(states) > 1:
        raise ValueError("a layer plan's recurrent runs must keep the "
                         f"same state, not {sorted(states)}")
    return next(iter(states), None)


def _state_layers(plan) -> int:
    """How many of the plan's layers keep recurrent state: the leading
    axis of the slots' state arrays."""
    return sum(run.layers for run in plan if run.state is not None)


def _format(run):
    """What the layers of a run that attends keep a token, as the key its
    pools go by: ``LayerStack.rows`` (None: the K/V twins; else the rows
    it names) and, for a run that keeps rows BESIDE its twins
    (``LayerStack.beside``), (None, those rows): the twins first."""
    return run.rows if run.beside is None else (None, *run.beside)


def _twins(fmt) -> bool:
    """Whether a format holds the K/V twins (alone, or first)."""
    return fmt is None or fmt[0] is None


def _rows(fmt) -> tuple:
    """The rows of a format that are a pool each: those beside the twins
    (none, for the twins alone), or all of a format of rows."""
    return (fmt or ())[1:] if _twins(fmt) else fmt


def _pool_slices(plan) -> tuple:
    """Where each page format of a layer plan lies among the pools the
    two programs carry: ({format: slice}, how many pools). A format
    (``_format``) is what the layers of a run that attends keep a token:
    None, the K/V twins, which are four pools (K, V and their scale
    pools); the twins and the rows beside them, a pool each behind the
    four; else the rows it names, a pool each. Runs of one format share
    its pools, which span THEIR layers in the plan's order."""
    slices, at = {}, 0
    for run in plan:
        fmt = _format(run)
        if run.attends and fmt not in slices:
            n = 4 * _twins(fmt) + len(_rows(fmt))
            slices[fmt] = slice(at, at + n)
            at += n
    if not slices:
        raise ValueError("no run of the layer plan attends: the engine "
                         "admits, reserves and retires by pages")
    return slices, at


def _pool_layers(plan, fmt) -> int:
    """How many of the plan's layers keep pages of the format ``fmt``
    (``_format``)."""
    return sum(run.layers for run in plan
               if run.attends and _format(run) == fmt)


def _plan_runs(plan, blocks, fuse=None) -> list:
    """What each run of a layer plan scans over: (its stacked weights,
    its layers' places). A run's layers take the layers of every store
    they keep in the plan's order, each store by its own count
    (``_places``). The places are those in the run's pools, or in the
    state arrays for a run that keeps no page; ``_state_place`` gives
    the others. ``fuse``: what a module does to its blocks once at a
    program's entry (``fuse_attention_projections``)."""
    layers = []
    for run, (pool_at, state_at) in zip(plan, _places(plan)):
        at = pool_at if run.attends else state_at or 0
        layers.append(jnp.arange(at, at + run.layers))
    if fuse is not None:
        blocks = fuse(blocks)
    return [(blocks if run.key is None else blocks[run.key], idx)
            for run, idx in zip(plan, layers)]


def _ahead(model, cfg, run, p, x) -> dict:
    """What a layer's feed-forward takes of the layer's input ``x``, as
    the keyword it is handed over under: made HERE, where the layer
    begins (a router's choice then stands before the attention in the
    program), for a run that states it (``LayerStack.ahead``); nothing
    for any other."""
    return {"ahead": model.feed_ahead(cfg, p, x)} if run.ahead else {}


def _routes(run, weights) -> bool:
    """Whether a run's layers end in a ROUTED feed-forward
    (``ops/moe.py:moe_ffn_dropless``): the run's weights hold a router."""
    return run.feeds and "router" in weights


def _places(plan) -> list:
    """For each run, (its first layer's place in its format's pools, that
    in the slots' state arrays), None for a store the run does not
    keep."""
    places, first, states = [], {}, 0
    for run in plan:
        pool_at = state_at = None
        if run.attends:
            pool_at = first.get(_format(run), 0)
            first[_format(run)] = pool_at + run.layers
        if run.state is not None:
            state_at, states = states, states + run.layers
        places.append((pool_at, state_at))
    return places


def _state_place(place: tuple, layer):
    """A layer's place in the state arrays from its place ``layer`` among
    its run's scanned indices (``_plan_runs``): the same number where the
    run keeps no page or its two places coincide (a plan whose every
    layer keeps both), else moved by the difference of the run's two
    first places."""
    pool_at, state_at = place
    if pool_at is None or pool_at == state_at:
        return layer
    return layer + (state_at - pool_at)


def _over_layers(stats: list) -> dict:
    """The feed-forward statistics of one step's runs, each {name:
    [layers of the run]}, as {name: [the layers that report it]}: a run
    of dense layers reports none."""
    names = {name for run_stats in stats for name in run_stats}
    out = {}
    for name in sorted(names):
        parts = [run_stats[name] for run_stats in stats if name in run_stats]
        out[name] = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
    return out


def store_shapes(cfg, *, max_batch: int, num_pages: int, page_size: int,
                 kv_dtype: str) -> tuple:
    """The stores ``cfg``'s layer plan states, as shapes and types in the
    order the two programs carry them: (the pools, the state, what the
    pages keep of the state). The pools lie as ``_pool_slices`` says: K/V
    twins are K and V pages [L, P, page, nkv, hd] (heads narrower than a
    lane tile two a row: ``pool_heads``) and their scale pools
    (per-token-per-head dequant scales in int8 mode; tiny dummies in bf16
    mode so every program shares one signature and donation set); a row
    is a pool (``row_pool``). The state is one array a kind [L',
    max_batch, ...] over the L' layers that keep it (none, for a plan of
    pages alone: the programs then take no such argument). Where the plan
    says its pages keep the state at their end
    (``RecurrentState.pages_keep``) the third is one array a kind [L',
    num_pages, ...], which the prefill program alone carries behind the
    state (``_Order.carried``); else it is empty. ``EnginePrograms``
    allocates from this, and a test that wants a cell's program at the
    cell's sizes lowers it over this, with no array made."""
    plan = _model_module(cfg).layer_plan(cfg)
    quantized = kv_dtype == "int8"
    pools = []
    for fmt in _pool_slices(plan)[0]:
        layers, rows = _pool_layers(plan, fmt), _rows(fmt)
        if _twins(fmt):
            nkv = getattr(cfg, "n_kv_heads", None) or cfg.n_heads
            shape = (layers, num_pages, page_size,
                     *pool_heads(nkv, cfg.head_dim))
            pages = jax.ShapeDtypeStruct(
                shape, jnp.int8 if quantized else jnp.bfloat16)
            scales = jax.ShapeDtypeStruct(
                shape[:-1] if quantized else (layers, 1, 1, 1), jnp.float32)
            pools += [pages, pages, scales, scales]
        if rows and quantized:
            raise ValueError(
                "kv_dtype='int8' over a layer plan that keeps rows "
                f"({', '.join(row.name for row in rows)}): only K/V "
                "twins are stored quantised")
        pools += [jax.eval_shape(partial(row_pool, layers, num_pages,
                                         page_size, row)) for row in rows]
    recurrent = _recurrent(plan)

    def state_arrays(rows: int) -> tuple:
        return tuple(jax.ShapeDtypeStruct(
            (_state_layers(plan), rows, *shape), dtype)
            for _, shape, dtype in recurrent.arrays)

    state = state_arrays(max_batch) if recurrent else ()
    kept = (state_arrays(num_pages)
            if recurrent and recurrent.pages_keep else ())
    return pools, state, kept


def bound_program(cfg, program: str, *, page_size: int, kv_dtype: str,
                  **static) -> tuple:
    """A program's body BOUND as the engine jits it, and the order it is
    called by: ``program`` is "decode" (``static``: its ``chunk``) or
    "prefill". A caller builds the call with ``_Order.arguments`` and
    takes it apart with ``_Order.split``, and donates ``_Order.donated``
    of as many pools and state arrays as ``store_shapes`` gives
    (``_Order.carried`` of its second and third)."""
    impl, order = {"decode": (_paged_decode_impl, _DECODE),
                   "prefill": (_paged_prefill_impl, _PREFILL)}[program]
    return partial(impl, cfg, page_size=page_size,
                   quantized=kv_dtype == "int8", **static), order


class EnginePrograms:
    """The stores one engine's layer plan states (``pools``, ``state``)
    and the programs over them. A dispatch takes the host's inputs by
    name and gives the program's results by name; the pools and the
    state go in donated and what comes back in their place is kept
    here."""

    def __init__(self, cfg, params, *, max_batch: int, num_pages: int,
                 page_size: int, kv_dtype: str):
        plan = _model_module(cfg).layer_plan(cfg)
        self.cfg = cfg
        self.params = params
        self.num_pages = num_pages
        self.page_size = page_size
        self.kv_dtype = kv_dtype
        # what each slot keeps per layer beside its pages (None: nothing)
        self.recurrent = _recurrent(plan)
        # the pools and the slots' recurrent state, as ``store_shapes``
        # states them: pages and state empty, scales one
        where = _pool_slices(plan)[0]
        pools, state, kept = store_shapes(
            cfg, max_batch=max_batch, num_pages=num_pages,
            page_size=page_size, kv_dtype=kv_dtype)
        scales = {at.start + i for fmt, at in where.items() if _twins(fmt)
                  for i in (2, 3)}
        self.pools = [(jnp.ones if i in scales else jnp.zeros)(a.shape,
                                                               a.dtype)
                      for i, a in enumerate(pools)]
        self.state = tuple(jnp.zeros(a.shape, a.dtype) for a in state)
        # what the pages keep of the state at their end (none, unless the
        # plan says so): the prefill program's, behind the state
        self.kept = tuple(jnp.zeros(a.shape, a.dtype) for a in kept)
        self.bf16_row_bytes = 0     # a token's rows over the layers, bf16
        self._page_layers = {}      # layers that keep pages, by format
        twins = None                # the K pool of the layers with twins
        for fmt, at in where.items():
            layers, rows = _pool_layers(plan, fmt), _rows(fmt)
            self._page_layers[",".join(
                ["k+v"] * _twins(fmt)
                + [f"{row.name}:{row.width}" for row in rows])] = layers
            if _twins(fmt):
                twins = self.pools[at][0]
                self.bf16_row_bytes += (
                    layers * 2 * 2 * math.prod(twins.shape[3:]))
            self.bf16_row_bytes += layers * 2 * sum(
                pool.shape[-1] for pool in self.pools[at][4 * _twins(fmt):])
        # the rows a token keeps in a page, by format: "k+v" for K/V
        # twins (then the rows beside them), else the rows' names and
        # widths
        self.page_rows = ";".join(self._page_layers)
        self.state_slot_bytes = sum(
            a.size * a.dtype.itemsize for a in self.state) // max_batch
        self.state_page_bytes = sum(
            a.size * a.dtype.itemsize for a in self.kept) // num_pages
        # a sliding layer's window, and the keys a layer that picks them
        # attends over at most, if the plan has such layers
        self.window = next(
            (run.window for run in plan if run.window is not None), None)
        self.selects = next(
            (run.selects for run in plan if run.selects is not None), None)
        # which kernels a program lowered HERE can hold: each on a TPU
        # alone, for a plan with the layers it serves (full attention
        # over K/V twins, and a window of them; a state whose arrays pass
        # ``ops/ssm.py``'s rule; layers that pick their keys; a run whose
        # weights hold a router)
        on_tpu = jax.default_backend() == "tpu"
        blocks = params["blocks"]
        twin_runs = [run for run in plan if run.attends and run.rows is None]
        self._kernel_backend = on_tpu and any(
            run.window is None for run in twin_runs)
        self._window_kernel_backend = on_tpu and any(
            run.window is not None for run in twin_runs)
        self._state_kernel = on_tpu and any(
            state_kernel_engages(a) for a in self.state)
        # layers that pick their keys: whether any does (their index
        # scores), and whether any over latent rows
        self._selects_backend = on_tpu and self.selects is not None
        self._latent_backend = on_tpu and any(
            run.selects is not None and run.rows is not None for run in plan)
        # the runs that attend over latent rows, as the prefill kernel's
        # rule takes them: (heads, the rows' pool's place, window, the
        # keys its indexer keeps, the indexer's heads)
        self._latent_runs = [
            (cfg.n_heads if run.window is None
             else getattr(cfg, "n_heads_sliding", cfg.n_heads),
             where[_format(run)].start, run.window, run.selects,
             getattr(cfg, "index_heads", 0))
            for run in plan
            if on_tpu and run.attends and run.rows is not None]
        # an index key's width in its pool, whole lanes (pools: latent
        # rows or the K/V twins, then index keys)
        self._index_width = next(
            (-(-_format(run)[1].width // ROW_LANES) * ROW_LANES
             for run in plan if run.selects is not None), None)
        # the pages a step of the decode kernel's walk takes over the K/V
        # twins (``ops/paged_decode_attention.py``'s rule on their shape)
        self._attn_step_pages = (
            step_pages(twins) if on_tpu and twins is not None else 0)
        self._expert_backend = on_tpu and any(
            _routes(run, blocks if run.key is None else blocks[run.key])
            for run in plan)
        self._compiled: dict[str, object] = {}     # by program name
        self.scatter_firsts = _named_jit("scatter_firsts", _scatter_firsts)

    def holds(self) -> dict:
        """What the plan's layers hold, as the stores were sized: the
        layers that keep pages, by format, and state, with the bytes of
        one page (the state it keeps at its end among them) and of one
        slot's state over them."""
        return {"page_layers": ";".join(
                    f"{rows}={n}" for rows, n in self._page_layers.items()),
                "page_bytes": self.pages_bytes() // self.num_pages,
                "state_layers": self.state[0].shape[0] if self.state else 0,
                "state_slot_bytes": self.state_slot_bytes,
                "state_page_bytes": self.state_page_bytes}

    def pages_bytes(self) -> int:
        """The pools' own bytes: every pool that holds a row a token (K
        and V pages, with their dequant scales in int8 mode; a latent
        plan's rows), not the bf16 mode's one-element scale dummies; and
        what the pages keep of the recurrent state."""
        return sum(a.size * a.dtype.itemsize
                   for a in (*self.pools, *self.kept)
                   if a.shape[1] == self.num_pages)

    # -- the programs, compiled once a shape ---------------------------

    def _program(self, name: str, program: str, **static):
        """The jitted body of ``program`` (``bound_program``) under
        ``name``, which carries its static facts and so tells the
        programs apart here too."""
        fn = self._compiled.get(name)
        if fn is None:
            body, order = bound_program(
                self.cfg, program, page_size=self.page_size,
                kv_dtype=self.kv_dtype, **static)
            fn = self._compiled[name] = _named_jit(
                name, body, donate_argnums=order.donated(
                    len(self.pools),
                    len(order.carried(self.state, self.kept))))
        return fn

    def _decode_paged(self, chunk: int, pages: int):
        return self._program(f"paged_decode_c{chunk}_w{pages}", "decode",
                             chunk=chunk)

    def _prefill_paged(self, pages: int):
        """The prefill program over a ``pages``-page window (it must
        cover every row's start + suffix), bucketed so a short-prompt
        batch reads a fraction of the full window's KV bytes. It
        specializes per (n, bucket) shape besides."""
        return self._program(f"paged_prefill_w{pages}", "prefill")

    # A dispatch is made READY here and CALLED by the loop, from the
    # frame that dispatches it: ``program(*arguments)``; what comes back
    # goes to ``prefilled`` / ``decoded``. A method here that made the
    # call would be Python frames under everything a first call traces
    # and lowers, a deep recursion: CPython 3.12 frees a chunk of its
    # frame stack when the frame at the chunk's start returns, and two
    # frames more doubled every prefill program's lowering on the chip's
    # host, 7 s of ``serve-chat``'s ``setup_s`` (PERF.md, PR 47: the loop
    # thread's minor page faults are the count to watch).

    def decode(self, chunk: int, pages: int, **inputs) -> tuple:
        """``chunk`` decode steps over a table ``pages`` wide, ``inputs``
        as ``_DECODE`` names them: (the program, its arguments)."""
        return self._decode_paged(chunk, pages), _DECODE.arguments(
            self.params, self.pools, inputs, self.state)

    def decoded(self, out) -> dict:
        """A decode call's results by name, on the device; the pools and
        the state that came back with them are kept."""
        self.pools, results, self.state = _DECODE.split(out, len(self.pools))
        return results

    def prefill(self, pages: int, **inputs) -> tuple:
        """One prefill over a window of ``pages``, ``inputs`` as
        ``_PREFILL`` names them: (the program, its arguments)."""
        return self._prefill_paged(pages), _PREFILL.arguments(
            self.params, self.pools, inputs,
            _PREFILL.carried(self.state, self.kept))

    def prefilled(self, out):
        """A prefill call's first tokens, on the device; the pools, the
        state and what the pages keep of it, which came back with them,
        are kept."""
        self.pools, results, state = _PREFILL.split(out, len(self.pools))
        self.state, self.kept = (state[:len(self.state)],
                                 state[len(self.state):])
        return results["firsts"]

    # -- which kernels a program runs ----------------------------------

    def prefill_kernels(self, group: int, bucket: int, pages: int) -> dict:
        """Whether the prefill program of ``group x bucket`` token-rows
        over a window of ``pages`` attends in the prefill kernel (its
        full layers, ``attn_kernel``; its sliding layers, at their own
        head count where the family states one, ``window_attn_kernel``;
        a run of its layers over latent rows, ``latent_attn_kernel``)
        and computes its routed experts in the grouped one: the rules
        the program itself is traced by (``kernel_engages``,
        ``latent_prefill_kernel_engages``, ``expert_kernel_engages``),
        on the host's own shapes."""
        cfg = self.cfg
        return {
            "attn_kernel": int(self._kernel_backend and kernel_engages(
                (group, bucket, cfg.n_heads, self.pools[0].shape[-1]),
                self.pools[0], pages, None)),
            "window_attn_kernel": int(
                self._window_kernel_backend and kernel_engages(
                    (group, bucket,
                     getattr(cfg, "n_heads_sliding", cfg.n_heads),
                     self.pools[0].shape[-1]), self.pools[0], pages,
                    self.window)),
            "latent_attn_kernel": int(any(
                latent_prefill_kernel_engages(
                    (group, bucket, heads, 0), self.pools[at], pages, window,
                    index_heads if selects is not None
                    and pages * self.page_size > selects else 0)
                for heads, at, window, selects, index_heads
                in self._latent_runs)),
            "expert_kernel": int(self._expert_backend
                                 and expert_kernel_engages(group * bucket))}

    def decode_kernels(self, pages: int) -> dict:
        """Whether a decode program over a table ``pages`` wide advances
        the slots' state in the state kernel and reads the rows its
        layers pick in the latent kernel, scored in the index kernel
        (``latent_kernel_engages``, ``index_kernel_engages``, on the
        program's own table), and the pages a step of its attention
        kernel's walk takes (0: no layer attends over K/V twins, or no
        kernel does)."""
        return {
            "attn_step_pages": self._attn_step_pages,
            "state_kernel": int(self._state_kernel),
            "latent_kernel": int(
                self._latent_backend and latent_kernel_engages(
                    self.page_size, pages, self.selects)),
            "index_kernel": int(
                self._selects_backend and index_kernel_engages(
                    self.page_size, pages, self.selects,
                    self._index_width))}


def _scatter_firsts(last, slots, firsts):
    """A prefill's first tokens into the loop's last-token vector."""
    with jax.named_scope(scopes.SAMPLE):
        return last.at[slots].set(firsts.astype(last.dtype))


def _paged_decode_impl(cfg, params, *args, chunk, page_size,
                       quantized):
    """``chunk`` decode steps over every slot in one compiled program;
    KV rows written, then attended over where they lie, through the
    (bucketed) page table [B, PB]. ``args`` and what is returned: in
    ``_DECODE``'s order; the [chunk, max_batch] token matrix and the
    advanced lengths stay ON DEVICE so chained chunks never need a host
    upload. Inactive slots are computed but masked (their writes drop).
    Slots finishing mid-chunk keep decoding; the host drops their
    surplus tokens. In int8 mode (``quantized``) writes quantize per
    token+head and the kernel dequantizes against the scale pages.
    Nested scans: over steps, carrying the pools, last tokens, lengths,
    key and state; inside it over the layers of each run of the plan in
    turn (module docstring: in place)."""
    model = _model_module(cfg)
    # the model's layers as runs of identical layers (one run, for a
    # model that repeats one block); each run's weights and its
    # layers' places in the pools, built here once, outside every scan
    plan = model.layer_plan(cfg)
    where, n_pools = _pool_slices(plan)
    pools, ins, state = _DECODE.taken(args, n_pools)
    table, tokens, lengths = ins["table"], ins["tokens"], ins["lengths"]
    active, temps, key = ins["active"], ins["temps"], ins["key"]
    num_pages = pools[0].shape[1]
    # q, k and v from ONE weight stack where the block's module states
    # how (module docstring)
    runs = _plan_runs(plan, params["blocks"], getattr(
        model, "fuse_attention_projections", None))
    places = _places(plan)

    def one_step(carry, _):
        *pools, toks, lens, key = carry[:n_pools + 3]
        state = carry[n_pools + 3:]
        key, sub = jax.random.split(key)
        pos = jnp.where(active, lens, 0)                    # [B]
        x = model.embed(cfg, params, toks[:, None])         # [B,1,d]
        rotary = model.rotary_tables(cfg, pos[:, None])
        # per-slot write target for this token
        pidx = jnp.take_along_axis(
            table, (pos // page_size)[:, None], axis=1)[:, 0]
        # holes (beyond reserved pages) drop; inactive slots drop too
        pidx = jnp.where((pidx >= 0) & active, pidx, num_pages)
        ip = pos % page_size

        def block(run, place, carry, xs):
            x, *rest = carry
            state = rest[n_pools:]
            p, layer = xs
            ahead = _ahead(model, cfg, run, p, x)

            def mixer_step():
                # the mixer on the layer's input, over the slots'
                # states at the layer's place among those that keep one
                return model.recurrent_step(
                    cfg, p, x, state, _state_place(place, layer), active)

            if not run.attends:
                if run.state is not None:
                    mixed, state = mixer_step()   # the one sublayer
            elif run.rows is not None:
                # a layer that keeps rows: the step's own written,
                # then the slot's rows read where they lie (a
                # sliding layer: its window's; a layer with an
                # indexer: the ones it picks)
                held = rest[where[_format(run)]]
                inputs = model.latent_projections(
                    cfg, p, x, *rotary[run.kind])
                held = write_latent(inputs, held, layer, pidx, ip)
                attn = latent_decode_attention(
                    inputs, held, layer, table, pos, window=run.window,
                    active=active)
            else:
                held = rest[where[_format(run)]]
                twins, beside = held[:4], held[4:]
                q, k, v = model.attention_projections(
                    cfg, p, x, *rotary[run.kind])
                heads = k.shape[-2:]    # as the module states them
                q, k, v = rows_of_heads(q, k, v)
                if run.state is not None:
                    # the mixer beside the attention, on the same input
                    mixed, state = mixer_step()
                twins = write_kv(*twins, layer, k[:, 0], v[:, 0], pidx,
                                 ip, quantized)
                selected = None
                if run.selects is not None:
                    # the step's index key beside its K and V, then the
                    # slot's index keys scored where they lie and the
                    # keys its query attends over picked
                    index = model.index_projections(
                        cfg, p, x, *rotary[run.kind])
                    beside = [write_rows(beside[0], layer, index.key[:, 0],
                                         pidx, ip)]
                    selected = decode_selection(
                        index, beside[0], layer, table,
                        jnp.where(active, pos + 1, 0))
                # each live slot's pages up to its length (a sliding
                # layer: the pages of its window), read where they
                # lie; the row just written is among them; of a layer
                # that selects, the rows it does not pick masked
                attn = own_parts(paged_decode_attention(
                    q[:, 0], *twins, layer, table, pos, active,
                    window=run.window, selected=selected), *heads)
                held = [*twins, *beside]
            if run.attends:
                x = model.attention_output(cfg, p, x, attn)
                rest[where[_format(run)]] = held
            if run.state is not None:
                x = x + mixed
            stats = {}
            if run.feeds:
                x, stats = model.feed_forward(cfg, p, x,
                                              valid=active[:, None], **ahead)
            return (x, *rest[:n_pools], *state), stats

        carry = (x, *pools, *state)
        stats = []
        for run, place, xs in zip(plan, places, runs):
            carry, run_stats = jax.lax.scan(
                partial(block, run, place), carry, xs)
            stats.append(run_stats)
        x, *rest = carry
        x = rms_norm(x, params["final_norm"], eps=cfg.rms_eps)[:, 0]
        logits = model.head_logits(cfg, params, x)
        nxt = select_tokens(logits, temps, sub)
        lens = jnp.where(active, lens + 1, lens)
        return (*rest[:n_pools], nxt, lens, key,
                *rest[n_pools:]), (nxt, _over_layers(stats))

    carry, (toks, stats) = jax.lax.scan(
        one_step, (*pools, tokens, lengths, key, *state), None,
        length=chunk)
    pools, lens, state = (carry[:n_pools], carry[n_pools + 1],
                          carry[n_pools + 3:])
    # merged last-token vector: chunk-active slots advance to their
    # newest token, others keep their prior value — the loop chains
    # every next dispatch off this DEVICE array, so admissions /
    # retirements never force a host round trip to rebuild last_tok
    new_last = jnp.where(active, toks[-1], tokens)
    # the feed-forward's statistics [chunk, layers], as the chunk's
    # means (nothing, for a block that hands back none)
    stats = jax.tree.map(jnp.mean, stats)
    return _DECODE.returned(pools, dict(
        toks=toks, lengths=lens, last=new_last, stats=stats), state)

def _paged_prefill_impl(cfg, params, *args, page_size, quantized):
    """Prefill ``n`` prompt SUFFIXES (one padded bucket) into pages
    and sample each row's first token, in a single program (``args``
    and what is returned: in ``_PREFILL``'s order): each dispatch has a
    fixed sync cost, so a 16-request burst admitted one-by-one would pay
    16 of them serially in TTFT before any compute. ``tokens`` holds
    only the tokens past each row's cached prefix (``starts`` absolute
    offsets; 0 = no prefix reuse, the plain prefill). Suffix KV is
    written into the pages first, then attention runs over the row's
    pages (``paged_prefill_attention``), so suffix queries see the
    reused prefix KV exactly as the original prompt computed it. The
    layer scans carry the activations and the stacked pools, as
    decode's do: the program holds one pool, the donated one. A row's
    mixer starts from the zero state where the row starts at 0, which
    over a plan whose pages do not keep its state is every row (the
    engine refuses the prefix cache there). Over a plan whose pages do
    (``RecurrentState.pages_keep``; the state arrays are then the slots'
    and behind them the pages') a row that starts behind ``k`` reused
    pages begins from what page ``k - 1`` of its table keeps, and the
    state at the end of every page the suffix completes is written to
    that page's place. A slot past the last one drops."""
    model = _model_module(cfg)
    plan = model.layer_plan(cfg)
    where, n_pools = _pool_slices(plan)
    pools, ins, state = _PREFILL.taken(args, n_pools)
    recurrent = _recurrent(plan)
    n_state = len(recurrent.arrays) if recurrent else 0
    # whether the pages' arrays stand behind the slots' among ``state``
    keeps = recurrent is not None and recurrent.pages_keep
    table_rows, tokens, slens = ins["table_rows"], ins["tokens"], ins["slens"]
    starts, temps, key = ins["starts"], ins["temps"], ins["key"]
    slots = ins.get("slots")    # beside a state alone
    num_pages = pools[0].shape[1]
    n, t = tokens.shape
    x = model.embed(cfg, params, tokens)
    rel = jnp.arange(t, dtype=jnp.int32)
    positions = starts[:, None] + rel[None, :]            # [n, T]
    rotary = model.rotary_tables(cfg, positions)
    pidx_all = jnp.take_along_axis(
        table_rows, positions // page_size, axis=1)       # [n, T]
    valid = rel[None, :] < slens[:, None]                 # [n, T]
    pidx_all = jnp.where((pidx_all >= 0) & valid, pidx_all,
                         num_pages)
    ip_all = positions % page_size
    if keeps:
        # the page whose end a row starts from (``starts`` is whole
        # pages), and the pages the suffix completes: page j of the
        # block, where all its tokens are the row's (else dropped)
        first = starts // page_size
        restored = starts > 0
        before = jnp.take_along_axis(
            table_rows, jnp.maximum(first - 1, 0)[:, None], axis=1)[:, 0]
        ends = jnp.arange(t // page_size, dtype=jnp.int32)
        done = jnp.take_along_axis(
            table_rows, jnp.minimum(first[:, None] + ends[None, :],
                                    table_rows.shape[1] - 1), axis=1)
        done = jnp.where(
            (done >= 0) & ((ends[None, :] + 1) * page_size <= slens[:, None]),
            done, num_pages)                              # [n, T / page]

    def block(run, place, stacked, carry, xs):
        x, *rest = carry
        state = rest[n_pools:]
        p, layer, *at = xs
        ahead = _ahead(model, cfg, run, p, x)

        def mixer_pass():
            """The mixer over the rows, each from the zero state or from
            what the page before its start keeps, and each row's state
            after its last token INSTALLED whole in its slot, at the
            layer's place among those that keep one; the state at the
            end of every page the rows complete written to the page's."""
            slot_state, kept = state[:n_state], state[n_state:]
            at = _state_place(place, layer)
            fresh = tuple(jnp.zeros((n, *a.shape[2:]), a.dtype)
                          for a in slot_state)
            if not keeps:
                mixed, final = model.recurrent_mixer(cfg, p, x, fresh, valid)
            else:
                with jax.named_scope(scopes.STATE_SNAPSHOT):
                    fresh = tuple(
                        jnp.where(restored.reshape(-1, *[1] * (a.ndim - 2)),
                                  a[at, jnp.maximum(before, 0)], zero)
                        for a, zero in zip(kept, fresh))
                mixed, final, at_ends = model.recurrent_mixer(
                    cfg, p, x, fresh, valid, page_ends=page_size)
                with jax.named_scope(scopes.STATE_SNAPSHOT):
                    kept = [a.at[at, done].set(new, mode="drop")
                            for a, new in zip(kept, at_ends)]
            with jax.named_scope(scopes.SSM_MIXER):
                return mixed, [*(a.at[at, slots].set(new, mode="drop")
                                 for a, new in zip(slot_state, final)),
                               *kept]

        if not run.attends:
            if run.state is not None:
                mixed, state = mixer_pass()
        elif run.rows is not None:
            held = rest[where[_format(run)]]
            inputs = model.latent_projections(cfg, p, x,
                                              *rotary[run.kind])
            held = write_latent(inputs, held, layer, pidx_all, ip_all)
            attn = latent_prefill_attention(
                inputs, held, layer, table_rows, starts, slens,
                window=run.window)
        else:
            held = rest[where[_format(run)]]
            twins, beside = held[:4], held[4:]
            q, k, v = model.attention_projections(cfg, p, x,
                                                  *rotary[run.kind])
            heads = k.shape[-2:]    # as the module states them
            q, k, v = rows_of_heads(q, k, v)
            if run.state is not None:
                mixed, state = mixer_pass()
            twins = write_kv(*twins, layer, k, v, pidx_all, ip_all,
                             quantized)
            flags = None
            if run.selects is not None:
                # the suffix's index keys beside its K and V, then each
                # query's keys picked among the rows' (a reused prefix's
                # out of its shared pages)
                index = model.index_projections(cfg, p, x,
                                                *rotary[run.kind])
                beside = [write_rows(beside[0], layer, index.key, pidx_all,
                                     ip_all)]
                flags = prefill_selection(index, beside[0], layer,
                                          table_rows, starts)
            attn = own_parts(paged_prefill_attention(
                q, *twins, layer, table_rows, starts, slens,
                window=run.window, flags=flags), *heads)
            held = [*twins, *beside]
        if run.attends:
            x = model.attention_output(cfg, p, x, attn)
            rest[where[_format(run)]] = held
        if run.state is not None:
            x = x + mixed
        if run.feeds:
            x, _ = model.feed_forward(
                cfg, p, x, valid=valid, **ahead,
                **({"stacked": (stacked, at[0])} if at else {}))
        return (x, *rest[:n_pools], *state), None

    carry = (x, *pools, *state)
    # a program whose routed experts run in the grouped kernel (the
    # rule on its rows) hands a run that routes the run's OWN stacks
    # and each layer's index in them: the kernel reads a layer's
    # experts where they lie, where a layer sliced out of the scan's
    # stacks to feed it would be a copy of them, a GB a layer
    grouped = expert_kernel_engages(n * t)
    for run, place, (stacks, places) in zip(
            plan, _places(plan), _plan_runs(plan, params["blocks"])):
        at = ((jnp.arange(run.layers),)
              if grouped and _routes(run, stacks) else ())
        carry, _ = jax.lax.scan(partial(block, run, place, stacks),
                                carry, (stacks, places, *at))
    x, *rest = carry
    x = rms_norm(x, params["final_norm"], eps=cfg.rms_eps)
    with jax.named_scope(scopes.LM_HEAD):
        x = jnp.take_along_axis(
            x, (slens - 1)[:, None, None], axis=1).squeeze(1)
    first = select_tokens(model.head_logits(cfg, params, x), temps, key)
    return _PREFILL.returned(rest[:n_pools], dict(firsts=first),
                             rest[n_pools:])
