"""Paged-KV continuous-batching LLM engine.

Reference: ABSENT from the reference repo (it serves models via user
code in replicas — SURVEY.md P15). This is the one serving engine: a
continuous-batching host loop over device programs that keep their KV in
the vLLM-style paged format of ``ray_tpu/ops/paged_attention.py``.

- **Continuous batching**: a fixed-shape decode program runs every chunk
  over all ``max_batch`` slots; which slots are live is a mask, so
  admitting or retiring a request never recompiles. New requests are
  prefilled into a free slot (prompt padded to a power-of-two bucket, a
  handful of compiled prefill variants in all) while decode keeps
  streaming for everyone else. Tokens stream back through per-request
  queues (``serve/llm.py``: ``Request``).

- The KV cache is a POOL of fixed-size pages [L, P, page, nkv, hd] over
  the L layers that ATTEND, uniform over them whatever their kind (a
  model's full and sliding layers share its KV heads and head size; a
  sliding layer keeps every page too, and reads only its window's:
  releasing what has fallen out of every window is ROADMAP Queue 2 B.1).
  What a layer holds and does is the layer plan's to say, run by run
  (``LayerStack``: pages or none, recurrent state or none, attention, a
  mixer, a feed-forward, each or not), and every store has as many
  layers as the plan has layers that keep it: a layer that is a mixer
  alone or a feed-forward alone (Nemotron-H's ``M`` and ``E``) has no
  layer in any pool, a layer that is attention alone none in the state
  arrays, and a run is handed its layers' places in each store it uses,
  each store by its own count (``_plan_runs``, ``_places``). Where a run of the
  model's layer plan states that its layers keep ROWS and no K/V twins
  (``LayerStack.rows``: a latent-attention layer's one compressed row a
  token, and its indexer's key), the pools are what the plan states: one
  [L', P, page, lanes] a kind of row, over the L' layers that keep it,
  in place of the twins, carried and donated as they are. ONE page
  table, one allocator and one prefix cache serve every pool: a page id
  names the same ``page_size`` tokens in all of them, so a reused
  prefix brings its rows of every layer. Decode attends over such rows
  in the absorbed form where they lie, prefill in the expanded one
  (``ops/latent_attention.py``: plain ``jax.numpy``, but for a decode
  step's layers that pick their keys, which on a program lowered for a
  TPU read the slot's rows in place in a Pallas kernel, the selection a
  mask, while the table holds no more than eight times the keys they
  pick; the dispatch's span says so, ``latent_kernel``, and ``stats()``
  counts ``latent_kernel_dispatches`` of ``decode_dispatches``); for a
  plan of K/V twins alone the arrays, the programs' arguments and their
  lowered text are what they were.
  Each slot owns a page list. HBM scales with TOKENS IN FLIGHT
  (reserved per request = prompt + max_new_tokens), not with
  ``max_batch * max_len`` — a 256-token chat on a 2048-token engine
  stops reserving 8x its need.
- Decode attends over the pages WHERE THEY LIE: after a layer's new row
  is written, one Pallas kernel (``ops/paged_decode_attention.py``)
  reads each live slot's pages of that layer from the stacked pool, up
  to the slot's length and no further; a dead slot costs nothing. No
  window is gathered and no copy of one exists. The page table a decode
  program takes is still BUCKETED (the power-of-two page count covering
  the longest RESERVED page list among the live slots: ``_pages_bucket``),
  which now only sets the table's width, not the bytes a step reads. On
  a platform other than the TPU the same call is the plain formulation
  (gather the window, ``cached_attention``), chosen where the program
  is lowered; nothing sets it.
- Prefill attends over the pages where they lie too, where that pays:
  in a full-attention layer over bf16 pages whose float32 scores would
  pass 256 MiB, the suffix queries go through a second Pallas kernel
  (``ops/paged_prefill_attention.py``), scores and softmax state in
  VMEM. The rule reads the traced shapes (``kernel_engages``); under it,
  in a sliding layer, over int8 pages and off the TPU a prefill gathers
  its rows' page windows and calls ``cached_attention`` as it did.
- Allocation is reserve-on-admit (pages for prompt + budget + one
  chained-overshoot page, released at retirement): admission applies
  backpressure when the pool is exhausted, and a mid-flight sequence
  can never fail an allocation — the deadlock-free policy (optimistic
  allocation + preemption is a future extension).
- ``kv_dtype="int8"`` stores pages quantized (per-token-per-head
  symmetric scales in a parallel scale pool): half the KV HBM, so the
  same pool holds 2x the tokens in flight. Decode dequantizes in VMEM,
  inside the kernel (the scales multiply the scores and the
  probabilities; only the window's scales, 1/32 of its bytes, are
  gathered); prefill dequantizes the window it gathers. The kernel is
  compute-bound over int8 pages (conversion on the VPU), so a step's
  attention takes about as long as over bf16 pages (v5e, kernel alone:
  326 against 283 us a layer at 32 slots of 1-1.9k tokens): int8 is a
  CAPACITY trade, the right default only when KV footprint is the
  binding constraint (long contexts / many concurrent slots).

- The device programs keep the pools IN PLACE: the layer loop carries
  the stacked pools (and scale pools) whole, beside the activations,
  and scans over (layer weights, layer index), one scan for each run of
  identical layers in the model's LAYER PLAN (``layer_plan`` of its
  module: one run for a model that repeats one block; a leading layer,
  then sliding x 3, full x 1, ... for Laguna), the runs in order over
  the pools' layers; a layer scatters its new
  rows at [layer, page, offset] (``write_kv``) and reads its pages at
  [layer, table]: decode in its kernel, prefill in its own or by
  gathering its window (``gather_kv_window``; all state the format, in
  ``ops/paged_attention.py``). Scanning OVER the pools
  instead hands each layer a slice: XLA then copies every layer's K
  and V pool out and back, every layer of every step, and the prefill
  program holds a second pool (measured on a v5e at 12 layers x 544
  pages: 43% of the device's time, 5.9 GB of HBM).
- The WEIGHTS stay in place too: a decode step reads each layer's
  weights once, where the stacks lie in HBM. The decode program
  projects q, k and v from ONE stack where the block's module states
  how (``fuse_attention_projections``: ``wqkv``, columns q | k | v),
  built once at the program's entry, outside the step and layer loops;
  ``self.params`` stay the caller's, in the published layout. Why one
  stack: it must NOT fit the core's memory. A stack the compiler can
  park there (Mistral-7B's ``wk`` over 12 layers: 100.7 MB of a v5e's
  128 MiB) becomes a value of the layer loop that is written back to
  HBM whole before the attention kernel, which needs the room, and
  fetched whole again after it, in every layer of every step, to read
  one layer of it: 201 MB a layer-step, a quarter of a step. The fused
  stack (604 MB) cannot be parked, so its matmul takes the stack and
  the layer index and reads its layer. Such a move has no name of its
  own in a trace (``copy-done``, ``slice-done`` of stacked-weight
  shape among the costliest operations is all that shows); to see one,
  compile the program for a described chip and look for ``S(1)`` in
  the layout of a weight stack inside a loop
  (``tests/test_tpu_compile.py:_stack_moves_in_loops``).

- A sequence's state is its pages and, where the model's layer plan
  has a RECURRENT run (a state-space mixer, beside the attention on the
  same input as Falcon-H1's or a layer's one sublayer as Nemotron-H's:
  ``LayerStack.state``), one more thing: per layer of such a run, the
  arrays the run states (a float32 state [heads, width, state size] and
  the last rows its convolution saw), which do not grow with the
  context. They live in the SLOT: one array a kind [L', max_batch, ...]
  over the L' layers that keep state, allocated once, donated to both
  programs and got back in place, as the pools are. A prefill runs the
  mixer over the padded prompt from the zero
  state (``recurrent_mixer``: padding moves nothing) and INSTALLS each
  row's state after its last token at [layer, slot], whole (the layer's
  place among the layers that keep state); a decode
  step hands the model's ``recurrent_step`` the STACKED arrays, that
  place and the slots that are active, and gets the arrays back with
  the active slots' states at [layer] advanced one token and the others'
  as they were, bit for bit. The engine slices nothing out: the float32
  state is updated where it lies (``ops/ssm.py:ssm_state_step``: on a
  program lowered for a TPU one Pallas kernel that reads a slot's state
  once and writes it once, aliased from input to output, where the plain
  formulation's lowering made three passes; the dispatch's span says so,
  ``state_kernel``, and ``stats()`` counts ``state_kernel_dispatches``
  of ``decode_dispatches``). A slot that finishes mid-chunk decodes on, as
  today, and its state is garbage afterwards: nothing reads it, for the
  next tenant's prefill overwrites it before any decode step of that
  tenant runs (the device runs dispatches in order). A prefix hit would
  hand a request its prefix's pages WITHOUT the state at their end, so
  over such a plan the prefix cache is off by rule (``prefix_cache=
  True`` raises; reuse by state snapshot is ROADMAP Queue 2 B.5). For a
  plan of pages alone nothing is allocated and the programs take no such
  argument: they lower to the text they lowered to before.

- What is the MODEL's comes from the model's module, the one its
  config's class is defined in (``_model_module``, which checks it for
  the pieces its plan USES and no others): the layer plan
  (``layer_plan``: the runs of identical layers, each with what its
  layers hold and do: whether they attend, of what kind and under what
  window, what they keep per sequence beside pages, whether they end in
  a feed-forward), the stream's start (``embed``),
  the rotary tables of each kind (``rotary_tables``, once a step; a
  kind's may be empty: a model with no rotary embedding), the
  attention projections (``attention_projections``: norm, q/k/v,
  whatever the block does to them, rotary; for a run that keeps rows
  ``latent_projections``: the queries, the row a token keeps, the
  expansion, the indexer's inputs), the sublayer's end
  (``attention_output``: ``wo`` and the residual, a per-head gate where
  the block has one), the recurrent mixer's two forms where the plan
  has such a run, the feed-forward (``feed_forward``: a dense SwiGLU, or
  routed experts, held whole or as this chip's share) and the output
  head (``head_logits``). What is the
  ENGINE's stays here, once for every model: the page write, decode's
  attention over the pages (the kernel), prefill's
  (``paged_prefill_attention``: its kernel, or the gather and
  ``cached_attention``, whole or over blocks of queries), the scans over
  the plan's runs, sampling, the chunk loop. A
  feed-forward may hand back statistics of its call (scalars; a dense
  one has none, a run with no feed-forward likewise): the decode program
  averages them over the chunk's steps and the layers that report them
  (``_over_layers``), and they go on the chunk's ``engine.emit`` span.
  What the plan's layers hold (the layers that keep pages, by format,
  and state, with the bytes of a page and of a slot's state) is in
  ``stats()`` and, while spans are recorded, on ``engine.construct``.

- What the two programs compute and what reaches a client differ, and
  the loop keeps the account (always on, integers in ``stats()``; on the
  spans while spans are recorded). A decode chunk is ``chunk x
  max_batch`` slot-steps whatever the slots hold; where it is read back
  each is one of: DELIVERED (a token on a request's stream), OVERRUN
  TAIL (a live slot's steps after its answer ended inside the chunk),
  OVERRUN AHEAD (all of a live slot's steps where its answer had ended
  in the chunk read before: the double buffer dispatches chunk N+1
  before it reads chunk N, so an end the host could not foresee, an
  ``eos_id``'s, costs the chunk in flight; and a request whose first
  token is its last is live in the one chunk dispatched behind its
  prefill, since ends are foreseen where decode is dispatched), VACANT (slots not live at
  dispatch); the four sum to the chunk's slot-steps, exactly. An end
  the host CAN foresee costs no chunk in flight: every request carries
  its ``max_new_tokens``, the loop knows how many tokens it has asked
  the device for, and the slot whose answer ends inside the chunk just
  dispatched is released there (``_release_foreseen``), to be taken by
  the next admission while its last tokens are still to be read. A
  prefill dispatch is ``group x bucket`` token-rows, of which the rows'
  suffixes are prompt tokens and the rest padding to the bucket.

Threading: one engine thread owns the device loop (admission, prefill
and decode dispatches, emission); a watcher thread blocks on each
dispatch in stream order and stamps when the device ran it; callers
enqueue requests and read token queues — no JAX calls on caller threads.
"""

from __future__ import annotations

import itertools
import math
import queue
import sys
import threading
import time
import uuid
from collections import deque
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models.decoding import select_tokens
from ray_tpu.ops.latent_attention import (latent_decode_attention,
                                          latent_kernel_engages,
                                          latent_prefill_attention,
                                          write_latent)
from ray_tpu.ops.moe import expert_kernel_engages
from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.paged_attention import (PageAllocator, PrefixCache,
                                         page_hashes, row_pool, write_kv)
from ray_tpu.ops.paged_decode_attention import paged_decode_attention
from ray_tpu.ops.paged_prefill_attention import (kernel_engages,
                                                 paged_prefill_attention)
from ray_tpu.ops.ssm import state_kernel_engages
from ray_tpu.serve.llm import _STAGES, Request, _named_jit, _serve_hist
from ray_tpu.util import metrics as _metrics
from ray_tpu.util import tracing as _tracing


class _Chunk(NamedTuple):
    """A dispatched decode chunk until its tokens are read back."""
    toks: object          # [chunk, max_batch] tokens, on the device
    active_idx: list      # the slots live at dispatch
    reqs: list            # the request each of them decoded for
    seq: int              # its place among the chunks (``_dispatch_seq``)
    stream_seq: int       # among all dispatches (the spans' ``seq``)
    drain: bool           # the short chunk (``_use_drain_chunk``)


def _wall(mono: float) -> float:
    """A ``time.monotonic()`` stamp on the wall clock spans are kept on."""
    return time.time() - (time.monotonic() - mono)


def _bucket(n: int, minimum: int = 16) -> int:
    b = minimum
    while b < n:
        b *= 2
    return b


_PIECES = ("layer_plan", "embed", "head_logits")
_ATTENTION_PIECES = ("rotary_tables", "attention_output")
_KV_PIECES = ("attention_projections",)
_LATENT_PIECES = ("latent_projections",)
_RECURRENT_PIECES = ("recurrent_mixer", "recurrent_step")
_FEED_PIECES = ("feed_forward",)


def _model_module(cfg):
    """The module that states ``cfg``'s block: the one its config class
    is defined in, which must hold the pieces its layer plan USES and no
    others (module docstring): the plan itself, the stream's start and
    the head; where a run attends, what attention takes in (as q, k and v
    where the run keeps K/V twins, as a latent's inputs where it keeps
    rows), its rotary tables and its end; the mixer's two forms where a
    run holds a recurrent mixer; the feed-forward where a run ends in
    one."""
    model = sys.modules.get(type(cfg).__module__)
    missing = [name for name in _PIECES if not hasattr(model, name)]
    if "layer_plan" not in missing:
        plan = model.layer_plan(cfg)
        attends = [run for run in plan if run.attends]
        asked = (
            _ATTENTION_PIECES * bool(attends)
            + _KV_PIECES * any(run.rows is None for run in attends)
            + _LATENT_PIECES * any(run.rows is not None for run in attends)
            + _RECURRENT_PIECES * (_recurrent(plan) is not None)
            + _FEED_PIECES * any(run.feeds for run in plan))
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


def _pool_slices(plan) -> tuple:
    """Where each page format of a layer plan lies among the pools the
    two programs carry: ({format: slice}, how many pools). A format is
    what the layers of a run that attends keep a token
    (``LayerStack.rows``): None, the K/V twins, which are four pools (K,
    V and their scale pools); else the rows it names, a pool each. Runs
    of one format share its pools, which span THEIR layers in the plan's
    order: a run that does not attend keeps no page and has no layer in
    any pool."""
    slices, at = {}, 0
    for run in plan:
        if run.attends and run.rows not in slices:
            n = 4 if run.rows is None else len(run.rows)
            slices[run.rows] = slice(at, at + n)
            at += n
    if not slices:
        raise ValueError("no run of the layer plan attends: the engine "
                         "admits, reserves and retires by pages")
    return slices, at


def _pool_layers(plan, rows) -> int:
    """How many of the plan's layers keep pages of the format ``rows``."""
    return sum(run.layers for run in plan
               if run.attends and run.rows == rows)


def _plan_runs(plan, blocks, fuse=None) -> list:
    """What each run of a layer plan scans over: (its stacked weights,
    its layers' places). A run's layers take the layers of every store
    they keep in the plan's order, each store by its own count: the
    pools of the run's page format over the runs that attend, the slots'
    state arrays over the runs that hold a mixer. The places are those in
    the run's pools, or in the state arrays for a run that keeps no
    page; ``_state_place`` gives the others. ``fuse``: what a module does
    to its blocks once at a program's entry
    (``fuse_attention_projections``)."""
    layers = []
    for run, (pool_at, state_at) in zip(plan, _places(plan)):
        at = pool_at if run.attends else state_at or 0
        layers.append(jnp.arange(at, at + run.layers))
    if fuse is not None:
        blocks = fuse(blocks)
    return [(blocks if run.key is None else blocks[run.key], idx)
            for run, idx in zip(plan, layers)]


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
            pool_at = first.get(run.rows, 0)
            first[run.rows] = pool_at + run.layers
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


class PagedLLMEngine:
    """Continuous batching over a paged KV pool (see module docstring).

    With ``prefix_cache=True`` (default), full prompt pages are also a
    content-addressed PREFIX CACHE (vLLM-style automatic prefix caching,
    chained page hashes — reference repo has no serving engine at all):
    a new request whose prompt starts with an already-cached page chain
    reuses those pages read-only and prefills only the suffix, cutting
    both TTFT and prefill compute for shared-system-prompt workloads.
    Unreferenced cached pages stay resident and are evicted LRU only
    when admission needs their space."""

    def __init__(self, cfg, params, *, max_batch: int = 8,
                 max_len: int = 2048, decode_chunk: int | None = None,
                 page_size: int | None = None,
                 num_pages: int | None = None,
                 prefix_cache: bool | None = None, kv_dtype: str = "bf16"):
        from ray_tpu.utils.config import get_config

        _cfg = get_config()
        if page_size is None:
            page_size = _cfg.serve_kv_page_size    # flag
        plan = _model_module(cfg).layer_plan(cfg)
        # what each slot keeps per layer beside its pages (None: nothing)
        self._recurrent = _recurrent(plan)
        if self._recurrent is not None and prefix_cache:
            raise ValueError(
                "prefix_cache=True over a layer plan with a recurrent run: "
                "a prefix hit would hand a request its prefix's KV pages "
                "without the recurrent state at their end, and its tokens "
                "would be silently wrong (reuse by state snapshot: "
                "ROADMAP Queue 2 B.5)")
        if prefix_cache is None:
            # the flag, for a plan whose prefix is its pages alone
            prefix_cache = (_cfg.serve_prefix_cache_enabled   # flag
                            and self._recurrent is None)
        if kv_dtype not in ("bf16", "int8"):
            raise ValueError(f"kv_dtype must be 'bf16' or 'int8', "
                             f"got {kv_dtype!r}")
        self.cfg = cfg
        self.params = params
        self.max_batch = max_batch
        self.max_len = max_len
        self.kv_dtype = kv_dtype
        self.page_size = page_size
        self.max_pages_per_seq = -(-max_len // page_size)
        # default pool: half of max_batch full-length sequences — the
        # paged layout's raison d'être is NOT reserving worst-case
        # length per slot — floored so every slot can hold a minimal
        # reservation (prompt page + 1 overshoot page); without the
        # floor, short-sequence configs (max_pages_per_seq == 2) starve
        # half of max_batch and admission waits a full generation for
        # pages, not slots
        if num_pages is not None:
            self.num_pages = num_pages
        else:
            half_dense = max_batch * self.max_pages_per_seq // 2
            floor = max_batch * min(2, self.max_pages_per_seq)
            self.num_pages = max(half_dense, floor)
        # tokens generated per device round trip: one host sync per CHUNK
        # of decode steps (lax.scan), not per token — every sync has a
        # fixed host cost, so fewer dispatches per token. Admission of
        # waiting requests happens between chunks (adds <= chunk *
        # step_time to queueing latency). Default: flag serve_decode_chunk.
        if decode_chunk is None:
            decode_chunk = _cfg.serve_decode_chunk
        self.decode_chunk = max(1, decode_chunk)
        # drain-mode decode: a SHORT chunk used when a slot is about to
        # retire while requests wait, so admission happens within a few
        # steps instead of a full chunk (TTFT <- admission latency);
        # flag serve_drain_chunk
        self._drain_chunk = max(1, min(_cfg.serve_drain_chunk,
                                       self.decode_chunk))
        # serve replica identity: set by the hosting _Replica before it
        # constructs the deployment body; engines built outside serve
        # get a private tag (bench / direct use)
        from ray_tpu.serve.context import get_replica_context
        ctx = get_replica_context()
        self.deployment_name = ctx.deployment if ctx else "-"
        self.replica_tag = (ctx.replica_tag if ctx
                            else f"engine-{id(self) & 0xffffff:06x}")
        # continuous admission (flag serve_continuous_admission): the
        # loop opens a timed window between chunk dispatches so a
        # request arriving mid-chunk prefills behind ONE in-flight
        # chunk instead of waiting out the full double-buffered
        # pipeline (the dominant queue_wait term in BENCH_r07)
        self._continuous_admission = bool(_cfg.serve_continuous_admission)
        self._window_frac = min(0.95, max(
            0.0, float(_cfg.serve_admission_window_frac)))
        self._sync_t: float | None = None       # last chunk-sync finish
        self._chunk_period: float | None = None  # EMA between syncs
        # host-side slot state (the trusted copy of the device lengths)
        self._lengths = np.zeros((max_batch,), np.int32)
        # what the device's last-token vector starts from (_last_dev)
        self._last_tok = np.zeros((max_batch,), np.int32)
        # the request a slot will next be asked to decode for. One whose
        # end lies inside what is already dispatched has given its slot
        # up (_release_foreseen) and waits in _leaving, by request id,
        # for its last tokens: the chunks in flight know whom they
        # decoded for (_Chunk.reqs), whoever holds the slot by then
        self._active: list[Request | None] = [None] * max_batch
        self._leaving: dict[int, Request] = {}
        self._waiting: "queue.Queue[Request]" = queue.Queue()
        self._req_ids = itertools.count()
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self._key = jax.random.key(0)
        self.error: BaseException | None = None
        self._submit_lock = threading.Lock()
        # metrics (TTFT window is bounded: a long-lived replica must not
        # grow memory per request, and a recent window tracks current
        # latency better than an all-time mean)
        self.total_generated = 0
        self.total_finished = 0
        self.ttfts: "deque[float]" = deque(maxlen=1024)
        # pre-resolved per-(deployment, replica) stage-histogram handles
        self._h_stage = {s: _serve_hist.handle(
            {"stage": s, "deployment": self.deployment_name,
             "replica": self.replica_tag}) for s in _STAGES}
        # ready watcher: handed EVERY dispatch (prefill and decode chunk)
        # in stream order, it stamps when the device started and finished
        # each — block_until_ready OFF the loop thread, so the
        # measurement never stalls the decode pipeline (see
        # _ready_watcher; started with the loop, joined by stop())
        self._ready_q: "queue.Queue" = queue.Queue()
        self._watcher: threading.Thread | None = None
        # every dispatch's place in the device stream (prefills and
        # chunks together; _dispatch_seq below counts chunks alone)
        self._stream_seq = itertools.count()
        # the engine loop's spans are one trace (util/tracing.phase)
        self._trace_id = uuid.uuid4().hex[:16]
        # requests the loop has taken off the queue whose prefill is not
        # dispatched yet (a failing dispatch must still end their
        # streams: see _loop), and requests whose first token went out
        # before the watcher had stamped their prefill (_publish_stamped)
        self._admitting: list[Request] = []
        self._unpublished: list[Request] = []
        # device-resident loop inputs (see _device_inputs)
        self._dev_inputs: dict | None = None
        self._dev_dirty = True
        # device-resident last-token vector (chained through decode
        # programs and prefill scatters; see _dispatch_decode)
        self._last_dev = None
        self._scatter_fn = _named_jit(
            "scatter_firsts", lambda last, slots, firsts:
            last.at[slots].set(firsts.astype(last.dtype)))
        # prefill batches whose first tokens haven't reached the host
        # yet: (dispatch_seq_at, items, firsts_device)
        self._pending_firsts: list = []
        self._dispatch_seq = 0
        # set when an admission failed on pages (not slots) this round —
        # gates the free-slot drain clause
        self._admission_blocked = False

        # -- device state: the pools, their host-side bookkeeping and the
        # programs compiled so far
        built = time.time()
        # the pools, as the plan's runs state them (``_pool_slices``):
        # one list, in the order both programs take and return them
        self._pools = []
        self._bf16_row_bytes = 0    # a token's rows over the layers, bf16
        self._page_layers = {}      # layers that keep pages, by format
        for rows in _pool_slices(plan)[0]:
            layers = _pool_layers(plan, rows)
            self._page_layers[
                "k+v" if rows is None else
                ",".join(f"{row.name}:{row.width}" for row in rows)] = layers
            if rows is None:
                self._pools += self._kv_twins(layers)
                self._bf16_row_bytes += (
                    layers * 2 * 2 * math.prod(self._pools[-4].shape[3:]))
                continue
            if self.kv_dtype == "int8":
                raise ValueError(
                    "kv_dtype='int8' over a layer plan that keeps rows "
                    f"({', '.join(row.name for row in rows)}): only K/V "
                    "twins are stored quantised")
            self._pools += [row_pool(layers, self.num_pages,
                                     self.page_size, row) for row in rows]
            self._bf16_row_bytes += layers * 2 * sum(
                pool.shape[-1] for pool in self._pools[-len(rows):])
        # the slots' recurrent state, one array a kind [L', max_batch,
        # ...] over the L' layers that keep it, where the plan has a
        # recurrent run (else none, and the programs take no such
        # argument): donated to both programs and got back, in place as
        # the pools. A prefill INSTALLS each row's final state in its
        # slot whole, so a slot's new tenant never reads its last one's;
        # decode advances the live slots' states
        self._state = tuple(
            jnp.zeros((_state_layers(plan), max_batch, *shape), dtype)
            for _, shape, dtype in (self._recurrent.arrays
                                    if self._recurrent else ()))
        self._state_slot_bytes = sum(
            a.size * a.dtype.itemsize for a in self._state) // max_batch
        if _tracing.recording():
            _tracing.emit("engine.construct", start=built,
                          duration=time.time() - built, kind="serve",
                          attrs=self._holds())
        self.state_installs = 0
        # decode dispatches, and those whose program advances the state
        # in the state kernel: the rule on the arrays' own shapes, on a
        # program lowered for a TPU (``ops/ssm.py:ssm_state_step``)
        self._state_kernel = jax.default_backend() == "tpu" and any(
            state_kernel_engages(a) for a in self._state)
        self.decode_dispatches = 0
        self.state_kernel_dispatches = 0
        # what became of every slot-step the decode programs computed
        # (chunk x max_batch a dispatch), counted where a chunk is read
        # back (_sync_chunk): delivered + overrun_tail + overrun_ahead +
        # vacant == slot_steps after every chunk
        self.decode_slot_steps = 0
        self.decode_delivered = 0       # a token on a request's stream
        self.decode_overrun_tail = 0    # after the answer's end, same chunk
        self.decode_overrun_ahead = 0   # a chunk in flight at the answer's end
        self.decode_vacant = 0          # slots not live at dispatch
        # answers whose end the loop foresaw from its own dispatches,
        # and of their slots those a waiting request took before the
        # end had been read back (_release_foreseen, _admit_round)
        self.retirements_foreseen = 0
        self.slots_handed_over = 0
        self._table = np.full((self.max_batch, self.max_pages_per_seq),
                              -1, np.int32)
        self._alloc = PageAllocator(self.num_pages)
        # deferred page frees: [syncs_remaining, slot_pages] — a chunk
        # dispatched before the retirement was observed may still write
        # into the retired slot's own pages; they return to the free
        # list only after two chunk syncs have drained the pipeline
        self._deferred_free: list[list] = []
        self._decode_cache: dict[tuple[int, int], object] = {}
        self._prefill_cache: dict[int, object] = {}
        # a sliding layer's window, if the model's plan has such layers
        # (for the decode dispatch's count of the KV rows a step reads)
        self._window = next(
            (run.window for run in plan if run.window is not None), None)
        # prefill dispatches, and those whose program holds the prefill
        # attention kernel: a model with full-attention layers, lowered
        # for a TPU (``_dispatch_prefill``)
        self._kernel_backend = jax.default_backend() == "tpu" and any(
            run.attends and run.window is None and run.rows is None
            for run in plan)
        # the keys a layer that picks them attends over at most, if the
        # plan has such layers (for the decode dispatch's count of the
        # rows a step reads after its selection)
        self._selects = next(
            (run.selects for run in plan if run.selects is not None), None)
        # decode dispatches whose program reads such a layer's rows in
        # place, in the latent kernel: lowered for a TPU, by the rule on
        # the program's own table (``_dispatch_decode``)
        self._latent_backend = (jax.default_backend() == "tpu"
                                and self._selects is not None)
        self.latent_kernel_dispatches = 0
        # prefill dispatches whose program computes the routed experts in
        # the grouped kernel: a plan with a run whose weights hold a
        # router, lowered for a TPU, by the rule on the dispatch's rows
        # (``ops/moe.py:expert_kernel_engages``)
        blocks = params["blocks"]
        self._expert_backend = jax.default_backend() == "tpu" and any(
            _routes(run, blocks if run.key is None else blocks[run.key])
            for run in plan)
        self.expert_kernel_dispatches = 0
        # the rows a token keeps in a page, by format, for the prefill
        # dispatch's span: "k+v" for K/V twins, else the rows' names and
        # widths
        self._page_rows = ";".join(self._page_layers)
        self.prefill_dispatches = 0
        self.prefill_kernel_dispatches = 0
        # the token-rows the prefill programs computed (group x bucket a
        # dispatch) and the prompt tokens among them (the suffixes past
        # the cached prefixes); the rest is padding
        self.prefill_token_rows = 0
        self.prefill_new_tokens = 0
        # per dispatched decode chunk, the feed-forward's statistics on
        # the device until the chunk is emitted (_sync_chunk)
        self._chunk_stats: deque = deque()
        # prefix cache state: shared (read-only, refcounted) pages per
        # slot, the slot's cached-prefix token count, and the full-page
        # hash chain awaiting registration after its prefill dispatch
        self._prefix_enabled = prefix_cache
        self._prefix = PrefixCache()
        self._shared: dict[int, list[int]] = {}
        self._prefix_len = np.zeros((self.max_batch,), np.int32)
        self._pending_hashes: dict[int, list[bytes]] = {}
        # prefix-cache digest publishing (serve/prefix_router.py): the
        # engine periodically drops a compact digest — chained full-page
        # hashes + pool occupancy — into the process annex registry;
        # the metrics pusher piggybacks it to the GCS and handles route
        # repeat-prefix traffic to the replica already holding the pages
        self._digest_enabled = (self._prefix_enabled
                                and _cfg.serve_prefix_routing_enabled)
        self._digest_interval = float(_cfg.serve_digest_publish_interval_s)
        self._digest_t = 0.0

    def _holds(self) -> dict:
        """What the plan's layers hold, as the stores were sized: the
        layers that keep pages, by format, with the bytes of one page
        over them, and the layers that keep recurrent state, with the
        bytes of one slot's over them."""
        return {"page_layers": ";".join(
                    f"{rows}={n}" for rows, n in self._page_layers.items()),
                "page_bytes": self._pages_bytes() // self.num_pages,
                "state_layers": self._state[0].shape[0] if self._state else 0,
                "state_slot_bytes": self._state_slot_bytes}

    def _pages_bytes(self) -> int:
        """The pools' own bytes: every pool that holds a row a token (K
        and V pages, with their dequant scales in int8 mode; a latent
        plan's rows), not the bf16 mode's one-element scale dummies."""
        return sum(a.size * a.dtype.itemsize for a in self._pools
                   if a.shape[1] == self.num_pages)

    def _kv_twins(self, layers: int) -> list:
        """The four pools of ``layers`` layers that keep K/V twins: K
        and V pages [L, P, page, nkv, hd] and their scale pools."""
        cfg = self.cfg
        nkv = getattr(cfg, "n_kv_heads", None) or cfg.n_heads
        shape = (layers, self.num_pages, self.page_size, nkv, cfg.head_dim)
        page_dtype = jnp.int8 if self.kv_dtype == "int8" else jnp.bfloat16
        # per-token-per-head dequant scales (int8 mode; tiny dummies in
        # bf16 mode so every program shares one signature/donation set)
        scale_shape = (shape[:-1] if self.kv_dtype == "int8"
                       else (layers, 1, 1, 1))
        return [jnp.zeros(shape, page_dtype), jnp.zeros(shape, page_dtype),
                jnp.ones(scale_shape, jnp.float32),
                jnp.ones(scale_shape, jnp.float32)]

    # the K/V twins' four pools by name (a plan of K/V twins alone)
    _k_pages = property(lambda self: self._pools[0])
    _v_pages = property(lambda self: self._pools[1])
    _k_scale = property(lambda self: self._pools[2])
    _v_scale = property(lambda self: self._pools[3])

    # -- compiled programs -------------------------------------------------

    def _decode_paged(self, chunk: int, pages_bucket: int):
        key = (chunk, pages_bucket)
        fn = self._decode_cache.get(key)
        if fn is None:
            fn = _named_jit(
                f"paged_decode_c{chunk}_w{pages_bucket}",
                partial(self._paged_decode_impl, self.cfg, chunk=chunk,
                        page_size=self.page_size,
                        quantized=self.kv_dtype == "int8"),
                donate_argnums=self._donated())
            self._decode_cache[key] = fn
        return fn

    def _prefill_paged(self, window_pages: int):
        """Prefill program gathering a ``window_pages``-page KV window —
        bucketed like decode so a short-prompt batch reads a fraction of
        the full window's KV bytes (the window must cover every row's
        start + suffix). It specializes per (n, bucket) shape besides;
        admission splits bursts into power-of-two groups so the variant
        count stays logarithmic."""
        fn = self._prefill_cache.get(window_pages)
        if fn is None:
            fn = _named_jit(
                f"paged_prefill_w{window_pages}",
                partial(self._paged_prefill_impl, self.cfg,
                        page_size=self.page_size,
                        quantized=self.kv_dtype == "int8"),
                donate_argnums=self._donated())
            self._prefill_cache[window_pages] = fn
        return fn

    def _donated(self) -> tuple:
        """The programs' donated arguments: the pools, which follow the
        weights, and the slots' recurrent state, which follows the key
        (six arguments lie between)."""
        pools = len(self._pools)
        return tuple(range(1, 1 + pools)) + tuple(
            range(7 + pools, 7 + pools + len(self._state)))

    def _window_pages(self, max_covered: int) -> int:
        """Power-of-two page count covering ``max_covered`` tokens,
        clamped to the table width."""
        need = max(1, -(-max_covered // self.page_size))
        return min(_bucket(need, minimum=1), self.max_pages_per_seq)

    @staticmethod
    def _paged_decode_impl(cfg, params, *args, chunk, page_size,
                           quantized):
        """``chunk`` decode steps over every slot in one compiled program;
        KV rows written, then attended over where they lie, through the
        (bucketed) page table [B, PB]. ``args``: the pools the plan
        states (``_pool_slices``: for K/V twins ``k_pages, v_pages,
        k_scale, v_scale``), then ``table, tokens, lengths, active,
        temps, key`` and the slots' recurrent ``state``. Returns the
        pools, the [chunk,
        max_batch] token matrix and the advanced lengths (kept ON DEVICE
        so chained chunks never need a host upload). Inactive slots are
        computed but masked (their writes drop). Slots finishing
        mid-chunk keep decoding; the host drops their surplus tokens.
        In int8 mode (``quantized``) writes quantize per token+head and
        the kernel dequantizes against the scale pages — half the KV
        bytes per step. Nested scans: over steps, carrying the
        pools, last tokens, lengths and key; inside it over the layers
        of each run of the model's layer plan in turn, carrying the
        activations and the same stacked pools (module docstring: in
        place), scanning over the run's weights and its layers'
        indices. ``state``: the slots' recurrent state, one array a kind
        [L, max_batch, ...], for a plan with a recurrent run (else
        none): carried as the pools are, a layer's mixer advancing the
        ACTIVE slots' states at [layer] and leaving the others' as they
        are; returned after the rest."""
        model = _model_module(cfg)
        # the model's layers as runs of identical layers (one run, for a
        # model that repeats one block); each run's weights and its
        # layers' places in the pools, built here once, outside every scan
        plan = model.layer_plan(cfg)
        where, n_pools = _pool_slices(plan)
        pools = args[:n_pools]
        table, tokens, lengths, active, temps, key, *state = args[n_pools:]
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
                    held = rest[where[run.rows]]
                    inputs = model.latent_projections(
                        cfg, p, x, *rotary[run.kind])
                    held = write_latent(inputs, held, layer, pidx, ip)
                    attn = latent_decode_attention(
                        inputs, held, layer, table, pos, window=run.window,
                        active=active)
                else:
                    held = rest[where[run.rows]]
                    q, k, v = model.attention_projections(
                        cfg, p, x, *rotary[run.kind])
                    if run.state is not None:
                        # the mixer beside the attention, on the same input
                        mixed, state = mixer_step()
                    held = write_kv(*held, layer, k[:, 0], v[:, 0], pidx,
                                    ip, quantized)
                    # each live slot's pages up to its length (a sliding
                    # layer: the pages of its window), read where they
                    # lie; the row just written is among them
                    attn = paged_decode_attention(
                        q[:, 0], *held, layer, table, pos, active,
                        window=run.window)
                if run.attends:
                    x = model.attention_output(cfg, p, x, attn)
                    rest[where[run.rows]] = held
                if run.state is not None:
                    x = x + mixed
                stats = {}
                if run.feeds:
                    x, stats = model.feed_forward(cfg, p, x,
                                                  valid=active[:, None])
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
        return (*pools, toks, lens, new_last, stats, *state)

    @staticmethod
    def _paged_prefill_impl(cfg, params, *args, page_size, quantized):
        """Prefill ``n`` prompt SUFFIXES (one padded bucket) into pages
        and sample each row's first token, in a single program
        (``args``: the pools the plan states, then ``table_rows,
        tokens, slens, starts, temps, key`` and ``state``): each
        dispatch has a fixed sync cost, so a 16-request burst admitted
        one-by-one would pay 16 of them serially in TTFT before any
        compute. ``tokens`` holds only the
        tokens past each row's cached prefix (``starts`` absolute
        offsets; 0 = no prefix reuse, the plain prefill). Suffix KV is
        written into the pages first, then attention runs over the
        row's pages (``paged_prefill_attention``), so suffix queries see
        the reused prefix KV exactly as the original prompt computed it.
        table_rows: [n, max_pages_per_seq]. The layer scans (one a run
        of the model's layer plan) carry the activations and the stacked
        pools, as decode's do: the program holds one pool, the donated
        one. ``state``, for a plan with a recurrent run (else none): the
        slots' recurrent state arrays and, last, each row's slot [n]. A
        row's mixer starts from the zero state (no prefix is reused
        over such a plan: every prompt starts at 0) and its state after
        the row's last token is INSTALLED at [layer, slot], whole: what
        the slot's last tenant left there is never read. A slot past
        the last one (a warm-up's row) drops."""
        model = _model_module(cfg)
        plan = model.layer_plan(cfg)
        where, n_pools = _pool_slices(plan)
        pools = args[:n_pools]
        table_rows, tokens, slens, starts, temps, key, *state = \
            args[n_pools:]
        num_pages = pools[0].shape[1]
        n, t = tokens.shape
        *state, slots = state or (None,)
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

        def block(run, place, stacked, carry, xs):
            x, *rest = carry
            state = rest[n_pools:]
            p, layer, *at = xs

            def mixer_pass():
                """The mixer over the rows from the zero state, and each
                row's state after its last token INSTALLED whole in its
                slot, at the layer's place among those that keep one."""
                fresh = tuple(jnp.zeros((n, *a.shape[2:]), a.dtype)
                              for a in state)
                mixed, final = model.recurrent_mixer(cfg, p, x, fresh, valid)
                at = _state_place(place, layer)
                return mixed, [a.at[at, slots].set(new, mode="drop")
                               for a, new in zip(state, final)]

            if not run.attends:
                if run.state is not None:
                    mixed, state = mixer_pass()
            elif run.rows is not None:
                held = rest[where[run.rows]]
                inputs = model.latent_projections(cfg, p, x,
                                                  *rotary[run.kind])
                held = write_latent(inputs, held, layer, pidx_all, ip_all)
                attn = latent_prefill_attention(
                    inputs, held, layer, table_rows, starts,
                    window=run.window)
            else:
                held = rest[where[run.rows]]
                q, k, v = model.attention_projections(cfg, p, x,
                                                      *rotary[run.kind])
                if run.state is not None:
                    mixed, state = mixer_pass()
                held = write_kv(*held, layer, k, v, pidx_all, ip_all,
                                quantized)
                attn = paged_prefill_attention(
                    q, *held, layer, table_rows, starts, slens,
                    window=run.window)
            if run.attends:
                x = model.attention_output(cfg, p, x, attn)
                rest[where[run.rows]] = held
            if run.state is not None:
                x = x + mixed
            if run.feeds:
                x, _ = model.feed_forward(
                    cfg, p, x, valid=valid,
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
        x = jnp.take_along_axis(
            x, (slens - 1)[:, None, None], axis=1).squeeze(1)
        first = select_tokens(model.head_logits(cfg, params, x), temps, key)
        return (*rest[:n_pools], first, *rest[n_pools:])

    # -- warm-up -----------------------------------------------------------

    def _warm_prefill(self, start: int, bucket: int, top: int):
        """Run the prefill program for ``bucket`` new tokens behind
        ``start`` cached ones at each power-of-two group size up to
        ``top``, against an empty page table (every write drops); yields
        each group's size and its first tokens (on the device)."""
        wp = self._window_pages(start + bucket)
        prefill = self._prefill_paged(wp)
        n = 1
        while n <= top:
            rows = jnp.full((n, wp), -1, jnp.int32)
            # no slot: every row's state drops, as its KV rows do
            nowhere = jnp.full((n,), self.max_batch, jnp.int32)
            firsts = self._took(prefill(
                self.params, *self._pools, rows,
                jnp.zeros((n, bucket), jnp.int32),
                jnp.ones((n,), jnp.int32),
                jnp.full((n,), start, jnp.int32),
                jnp.zeros((n,), jnp.float32), self._next_key(),
                *self._state_args(nowhere)), 1)[0]
            yield n, firsts
            n *= 2

    def _took(self, out: tuple, results: int) -> tuple:
        """A program's outputs: the pools come first and the slots'
        recurrent state last, both kept here in place of the donated
        ones; between them its ``results``, which are returned."""
        pools = len(self._pools)
        self._pools = list(out[:pools])
        self._state = tuple(out[pools + results:])
        return out[pools:pools + results]

    def _state_args(self, slots) -> tuple:
        """What a prefill program takes after its key: the slots'
        recurrent state and each row's slot, or nothing."""
        return (*self._state, slots) if self._state else ()

    def warmup_prefix(self, prefix_len: int, tail_len: int,
                      max_n: int | None = None):
        """Compile the SUFFIX prefill variants that prefix-cache hits
        dispatch (tail bucket + the window covering prefix+tail), so a
        deployment with a known system-prompt shape doesn't pay XLA
        compilation inside the first shared-prefix request's TTFT.
        ``warmup`` alone only covers the cold (starts=0) path."""
        bucket = min(_bucket(tail_len), self.max_len)
        top = max_n if max_n is not None else self.max_batch
        for _, firsts in self._warm_prefill(prefix_len, bucket, top):
            np.asarray(firsts)

    def warmup(self, prompt_len: int):
        """Deterministically compile every program a burst at this
        prompt bucket can hit: the prefill at each power-of-two group
        size up to max_batch, and the decode programs at every
        pages-bucket a run can touch. Call BEFORE start()
        (request-driven warmup races the admit loop, so which
        (n, bucket) prefill variants compile is scheduling-dependent —
        a missed one lands seconds of JIT inside a measured or
        user-facing TTFT). For shared-prefix workloads also call
        ``warmup_prefix`` with the expected (prefix, tail) shape."""
        bucket = min(_bucket(prompt_len), self.max_len)
        if self._last_dev is None:
            self._last_dev = jnp.asarray(self._last_tok)
        for n, firsts in self._warm_prefill(0, bucket, self.max_batch):
            # warm the firsts scatter at this group size too: it
            # specializes per slots-shape, and a compile inside _admit
            # stalls the loop ~0.5s per NEW burst size (measured)
            self._last_dev = self._scatter_fn(
                self._last_dev, jnp.arange(n, dtype=jnp.int32), firsts)
            np.asarray(firsts)
        self._last_dev = jnp.asarray(self._last_tok)
        active = jnp.zeros((self.max_batch,), bool)
        # every pages-bucket a run can touch: powers of two PLUS the
        # non-power-of-two cap (_pages_bucket clamps to it — e.g.
        # max_pages_per_seq=6 serves buckets {1,2,4,6})
        buckets = []
        pb = 1
        while pb < self.max_pages_per_seq:
            buckets.append(pb)
            pb *= 2
        buckets.append(self.max_pages_per_seq)
        for pb in buckets:
            for chunk in {self.decode_chunk, self._drain_chunk}:
                fn = self._decode_paged(chunk, pb)
                toks = self._took(fn(
                    self.params, *self._pools,
                    jnp.full((self.max_batch, pb), -1, jnp.int32),
                    jnp.zeros((self.max_batch,), jnp.int32),
                    jnp.zeros((self.max_batch,), jnp.int32), active,
                    jnp.zeros((self.max_batch,), jnp.float32),
                    self._next_key(), *self._state), 4)[0]
                np.asarray(toks)
        self._lengths[:] = 0

    # -- threads and submission --------------------------------------------

    def start(self):
        self._watcher = threading.Thread(
            target=self._ready_watcher, daemon=True,
            name="llm-ready-watcher")
        self._watcher.start()
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    def stop(self):
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=30)
        # the watcher goes after the loop (no dispatch follows its
        # sentinel) and is waited for: a daemon thread still blocked on
        # the device when the interpreter exits aborts the process
        self._ready_q.put(None)
        if self._watcher is not None:
            self._watcher.join(timeout=30)

    def _ready_watcher(self):
        """The device's timeline as the host sees it. Every dispatch
        (kind, stream seq, an output of the program, dispatch_t, the
        requests it prefills, its dispatch span or None) arrives in
        stream order, and the device runs them in that order, so
        blocking on each in turn gives when it finished (done) and when
        it started: at its dispatch, or when the one before it finished,
        whichever is later. Prefilled requests get ``start_t`` /
        ``ready_t``; a dispatch made while spans are recorded gets a
        ``device.run`` child."""
        prev_done = float("-inf")
        while True:
            item = self._ready_q.get()
            if item is None:
                return
            kind, seq, result, dispatch_t, reqs, span = item
            try:
                result.block_until_ready()
            except Exception:  # noqa: BLE001 - a failed run has ended too
                pass
            done = time.monotonic()
            start = max(dispatch_t, prev_done)
            prev_done = done
            for r in reqs:
                r.start_t = start
                r.ready_t = done       # last: _publish_stamped waits on it
            if span is not None:
                _tracing.emit(
                    "device.run", start=_wall(start), duration=done - start,
                    parent=span, kind="serve",
                    attrs={"kind": kind, "seq": seq,
                           "wait_s": start - dispatch_t})

    def submit(self, prompt, *, max_new_tokens: int = 128,
               temperature: float = 0.0, eos_id: int | None = None) -> Request:
        req = Request(
            request_id=next(self._req_ids),
            prompt=np.asarray(prompt, np.int32),
            max_new_tokens=max_new_tokens,
            temperature=temperature,
            eos_id=eos_id,
        )
        req.engine = self
        if _tracing.recording():
            # with no ambient span (a caller outside serve: the
            # benchmark's client) the request is a trace of its own
            req.trace_ctx = _tracing.current_context() or \
                _tracing.SpanContext(uuid.uuid4().hex[:16], "")
            req.submit_wall = time.time()
        # Lock pairs with the drain in _loop's finally: a request either
        # lands in _waiting before the drain (and gets its sentinel
        # there) or observes the dead/stopped engine here — never neither.
        with self._submit_lock:
            if self.error is not None or self._stop.is_set():
                req.out.put(None)  # engine is dead: fail fast at tokens()
            else:
                self._waiting.put(req)
        return req

    # -- admission and prefill ---------------------------------------------

    def _free_slots(self) -> list[int]:
        """The slots no request holds; last among them those whose
        occupant still waits for a chunk in flight, so that
        ``slots_handed_over`` counts the admissions no other slot could
        have taken."""
        leaving = {r.slot for r in self._leaving.values()}
        return sorted((i for i, r in enumerate(self._active) if r is None),
                      key=leaving.__contains__)

    def _reserve_pages(self, req: Request, slot: int) -> bool:
        """Reserve-on-admit: pages for prompt + token budget + one page
        of chained-dispatch overshoot. False = backpressure: the caller
        requeues the request until pages free up and stops admitting
        this round (or, with ``req.error`` set, rejects it).

        With the prefix cache, cached full-prefix pages are mapped
        read-only into the slot's table (refcounted, never re-written:
        suffix writes start at the first non-reused page boundary and
        decode writes past the prompt) and only the remainder is
        allocated fresh; idle cached pages are LRU-evicted into the
        free list when admission needs the space."""
        plen = len(req.prompt)
        budget = min(plen + req.max_new_tokens, self.max_len)
        pages = min(-(-budget // self.page_size) + 1,
                    self.max_pages_per_seq)
        if pages > self.num_pages:
            # can NEVER fit, even with the pool empty: reject now
            # (_admit_round turns req.error into a terminated stream)
            req.error = MemoryError(
                f"request needs {pages} KV pages "
                f"(prompt {plen} + budget {req.max_new_tokens}) but the "
                f"pool holds only {self.num_pages}; raise num_pages or "
                f"lower max_new_tokens")
            return False
        hits: list[int] = []
        hashes: list[bytes] = []
        if self._prefix_enabled:
            prompt = np.asarray(req.prompt, np.int32)
            hashes = page_hashes(prompt, self.page_size)
            # keep at least one suffix token: the first output token is
            # sampled from the suffix prefill's logits
            max_reuse = (plen - 1) // self.page_size
            hits = self._prefix.acquire(hashes[:max_reuse])
        n_fresh = pages - len(hits)
        if n_fresh > len(self._alloc.free) and self._deferred_free:
            # Deferred frees are reclaimable for a NEW admission: the
            # prefill it dispatches is ordered AFTER every in-flight
            # chunk on the device stream, and prefill + decode write
            # each page position before the causal mask exposes it, so
            # a stale in-flight write to a reclaimed page is always
            # overwritten before any read. The sync-count deferral only
            # protects the no-reuse window; claiming under pressure
            # saves up to two chunk periods of admission latency — the
            # dominant queue_wait term when the pool runs tight.
            self._age_deferred_frees(drain_all=True)
        if n_fresh > len(self._alloc.free) + self._prefix.evictable():
            self._prefix.release(hits)   # nothing dispatched yet
            return False
        if n_fresh > len(self._alloc.free):
            self._alloc.free.extend(
                self._prefix.evict(n_fresh - len(self._alloc.free)))
        page_ids = self._alloc.alloc(slot, n_fresh)
        self._table[slot, :] = -1
        if hits:
            self._table[slot, :len(hits)] = hits
        self._table[slot, len(hits):pages] = page_ids
        self._shared[slot] = list(hits)
        self._prefix_len[slot] = len(hits) * self.page_size
        if self._prefix_enabled:
            self._pending_hashes[slot] = hashes
        return True

    def _pack_admit(self, req: Request, slot: int, plen: int) -> tuple:
        """One admit item (req, slot, plen, padded): the tokens the
        prefill program must actually process, padded to a power-of-two
        bucket. Only the SUFFIX past the slot's cached prefix — a
        shared-prefix request prefills (and buckets) just its tail."""
        start = int(self._prefix_len[slot])
        suffix = np.asarray(req.prompt, np.int32)[start:]
        bucket = min(_bucket(len(suffix)), self.max_len)
        padded = np.zeros((bucket,), np.int32)
        padded[:len(suffix)] = suffix
        return (req, slot, plen, padded)

    def _dispatch_prefill(self, part: list, bucket: int, ph):
        """Dispatch one prefill sub-batch (``part`` is a list of
        (req, slot, plen, padded)); returns the device first-tokens.
        ``ph`` is the dispatch's span (``tracing.phase``), which gets
        the window and the prefix-cache counts."""
        tokens = jnp.asarray(np.stack([it[3] for it in part]))
        starts_np = np.array([self._prefix_len[it[1]] for it in part],
                             np.int32)
        slens_np = np.array([it[2] for it in part], np.int32) - starts_np
        wp = self._window_pages(int((starts_np + slens_np).max()))
        # whether this program's full layers attend in the prefill kernel
        # (the rule the program itself was traced by, on a TPU alone)
        kernel = self._kernel_backend and kernel_engages(
            (len(part), bucket, self.cfg.n_heads, self.cfg.head_dim),
            self._k_pages, wp, None)
        token_rows, new_tokens = len(part) * bucket, int(slens_np.sum())
        # and whether its routed experts run in the grouped kernel
        expert_kernel = (self._expert_backend
                         and expert_kernel_engages(token_rows))
        self.prefill_dispatches += 1
        self.prefill_kernel_dispatches += int(kernel)
        self.expert_kernel_dispatches += int(expert_kernel)
        self.prefill_token_rows += token_rows
        self.prefill_new_tokens += new_tokens
        if ph:
            # what the prefix cache gave this dispatch, counted as its
            # lookups were (PrefixCache.acquire): the full pages before
            # a prompt's last token, those reused and those missed
            page, cached = self.page_size, int(starts_np.sum())
            lookups = (sum((it[2] - 1) // page for it in part)
                       if self._prefix_enabled else 0)
            ph.set(token_rows=token_rows, new_tokens=new_tokens,
                   cached_tokens=cached,
                   missed_pages=lookups - cached // page,
                   attn_kernel=int(kernel), expert_kernel=int(expert_kernel),
                   page_rows=self._page_rows)
        slots = None
        if self._state:
            # every row's final state goes into its slot; the scan cuts
            # the padded bucket into chunks, padding included
            slots = jnp.asarray(np.array([it[1] for it in part], np.int32))
            self.state_installs += len(part)
            if ph:
                ph.set(state_installs=len(part),
                       scan_chunks=len(part) * -(-bucket
                                                 // self._recurrent.chunk))
        prefill = self._prefill_paged(wp)
        slens = jnp.asarray(slens_np)
        rows = jnp.asarray(np.stack(
            [self._table[it[1]][:wp] for it in part]))
        temps = jnp.asarray(np.array(
            [it[0].temperature for it in part], np.float32))
        firsts, = self._took(prefill(
            self.params, *self._pools, rows, tokens, slens,
            jnp.asarray(starts_np), temps, self._next_key(),
            *self._state_args(slots)), 1)
        # the dispatch above is what makes each slot's full prompt pages
        # valid on device: REGISTER them in the prefix cache now — any
        # future admission's prefill program runs after this one on the
        # device stream, so a reader can never observe unwritten pages
        for req, slot, plen, _ in part:
            self._register_prefix(slot, plen)
        return firsts

    def _register_prefix(self, slot: int, plen: int):
        """Move this slot's freshly prefilled FULL prompt pages into the
        prefix cache (reused pages are already registered). A page that
        becomes cached is reclassified exclusive -> shared so retirement
        releases a reference instead of freeing it."""
        hashes = self._pending_hashes.pop(slot, [])
        if not hashes:
            return
        owned = self._alloc.owned.get(slot, [])
        shared = self._shared.setdefault(slot, [])
        n_shared = len(shared)
        for i in range(n_shared, min(len(hashes), plen // self.page_size)):
            page = int(self._table[slot, i])
            if page < 0 or not self._prefix.insert(hashes[i], page):
                # hash raced in from an identical concurrent prompt:
                # keep our copy exclusive (freed normally at retirement)
                continue
            if page in owned:
                owned.remove(page)
            shared.append(page)
            self._prefix.ref(page)

    def _admit(self, first: "Request | None" = None):
        with _tracing.phase("engine.admit", kind="serve") as ph:
            handed_over = self.slots_handed_over
            admitted = self._admit_round(first)
            if ph:
                ph.set(admitted=admitted,
                       handed_over=self.slots_handed_over - handed_over)

    def _admit_round(self, first: "Request | None") -> int:
        """Prefill waiting requests into free slots, those given up
        ahead of their occupant's last read-back (``_release_foreseen``)
        among them. All prefills of the round are DISPATCHED first and
        their first tokens extracted in one host pass — each sync has a
        fixed cost, so a burst of admissions pays ~one, not one per
        request. ``first``: a request already pulled off the queue (the
        admission window's timed get) — admitted ahead of the queue,
        requeued on backpressure like any other. Returns the number of
        requests admitted."""
        admits = []   # (req, slot, plen, padded)
        self._admission_blocked = False
        pulled = first
        for slot in self._free_slots():
            if pulled is not None:
                req, pulled = pulled, None
            else:
                try:
                    req = self._waiting.get_nowait()
                except queue.Empty:
                    break
            plen = len(req.prompt)
            if plen >= self.max_len:
                req.error = ValueError(
                    f"prompt length {plen} >= engine max_len "
                    f"{self.max_len}")
                req.out.put(None)
                continue
            if not self._reserve_pages(req, slot):
                if req.error is not None:
                    # permanently infeasible (e.g. a reservation larger
                    # than the whole page pool): reject — requeueing
                    # would hang it and head-of-line-block the queue
                    req.out.put(None)
                    continue
                self._waiting.put(req)   # backpressure: retry later
                self._admission_blocked = True
                break
            admits.append(self._pack_admit(req, slot, plen))
        if pulled is not None:
            self._waiting.put(pulled)   # no free slot took it
        if not admits:
            return 0
        self._admitting = [item[0] for item in admits]
        # Group by bucket, then split each group into POWER-OF-TWO
        # sub-batches: one batched-prefill dispatch per sub-batch (a
        # 16-burst = 1 dispatch; 15 = 8+4+2+1 = 4) with one stacked
        # prompt upload each. Per-dispatch sync costs would otherwise
        # dominate burst TTFT.
        groups: dict[int, list] = {}
        for item in admits:
            groups.setdefault(len(item[3]), []).append(item)
        batches = []   # (items, first_tokens_device)
        for bucket, items in groups.items():
            i = 0
            while i < len(items):
                m = 1
                while m * 2 <= len(items) - i:
                    m *= 2
                part = items[i:i + m]
                i += m
                with _tracing.phase("engine.dispatch_prefill",
                                    kind="serve") as ph:
                    firsts = self._dispatch_prefill(part, bucket, ph)
                    now = time.monotonic()
                    seq = next(self._stream_seq)
                    if ph:
                        ph.set(seq=seq, group=len(part), bucket=bucket)
                reqs = [it[0] for it in part]
                for req in reqs:
                    req.dispatch_t = now
                self._ready_q.put(
                    ("prefill", seq, firsts, now, reqs, ph or None))
                batches.append((part, firsts))
        # ASYNC first tokens: scatter each batch's firsts into the
        # device last-token vector (so the very next decode chunk
        # covers the new slots with no host round trip) and activate
        # the slots NOW; the host-side emission of the first tokens
        # happens in _drain_firsts when the async copy lands. Blocking
        # here for the sync RTT stalled the whole decode pipeline once
        # per admission round — with small chunks that stall WAS the
        # sustained-TTFT/throughput ceiling.
        for part, firsts in batches:
            slots = jnp.asarray(np.array([it[1] for it in part],
                                         np.int32))
            self._last_dev = self._scatter_fn(self._last_dev, slots,
                                              firsts)
            try:
                firsts.copy_to_host_async()
            except Exception:  # noqa: BLE001 - backend without async copy
                pass
            for (req, slot, plen, _) in part:
                # a HAND-OVER where the slot's last occupant still waits
                # for tokens of a chunk in flight: that chunk knows whom
                # it decoded for, and lies before this prefill on the
                # device stream
                self.slots_handed_over += any(
                    r.slot == slot for r in self._leaving.values())
                req.slot = slot
                self._active[slot] = req
                self._lengths[slot] = plen
            # any chunk dispatched from here on (seq >= _dispatch_seq)
            # executes after this prefill on the device stream
            self._pending_firsts.append(
                (self._dispatch_seq, part, firsts))
        self._admitting = []
        self._dev_dirty = True   # active set / lengths changed
        return len(admits)

    def _drain_firsts(self, completed_seq: int | None = None):
        """Emit first tokens whose prefill results reached the host.
        ``completed_seq``: a decode chunk with this dispatch seq has
        been READ on the host — every prefill dispatched before it is
        device-complete, so blocking on those firsts costs only the
        (already overlapped) copy."""
        if not self._pending_firsts:
            return
        keep = []
        for seq_at, part, firsts in self._pending_firsts:
            # NOTE: no is_ready() polling — a readiness query can
            # itself block on the device, which (measured in round 5)
            # serialized the whole loop. Readiness is derived purely
            # from device-stream ordering via completed_seq.
            if completed_seq is None or seq_at > completed_seq:
                keep.append((seq_at, part, firsts))
                continue
            t_drain = time.monotonic()
            with _tracing.phase("engine.wait_device", kind="serve",
                                attrs={"what": "firsts"}):
                vals = np.asarray(firsts)
            now = time.monotonic()
            with _tracing.phase("engine.emit", kind="serve") as ph:
                finished = self.total_finished
                for (req, slot, plen, _), first in zip(part, vals):
                    req.drain_t = t_drain
                    req.first_token_t = now
                    self.ttfts.append(req.ttft)
                    self._unpublished.append(req)
                    self._emit(req, int(first))
                if ph:
                    ph.set(what="firsts", tokens=len(part),
                           finished=self.total_finished - finished)
        self._pending_firsts = keep
        self._publish_stamped()

    def _publish_stamped(self):
        """Publish the TTFT breakdown (stage histograms, trace spans) of
        every request whose first token has gone out and whose
        prefill the watcher has stamped. The loop thread and the watcher
        wake on the same device event, so the stamp may be a moment
        behind the token: such a request waits here for the loop's next
        pass, and its stages are never made up."""
        if not self._unpublished:
            return
        keep = []
        for req in self._unpublished:
            if req.ready_t is None:
                keep.append(req)
                continue
            bd = req.breakdown
            if _metrics.enabled():
                for stage in _STAGES:
                    self._h_stage[stage].observe(bd[f"{stage}_s"])
            if req.trace_ctx is not None:
                self._emit_trace_spans(req, bd)
        self._unpublished = keep

    def _emit_trace_spans(self, req: Request, bd: dict):
        """The engine's span subtree for one traced request: an
        ``engine.request`` parent spanning submit -> first token
        (wall-anchored at the submit stamp, parented to the replica's
        run span, or the root of the request's own trace), with the five
        TTFT stages as SEQUENTIAL children. ``breakdown`` clamps the
        stamps, so the children tile the parent exactly — the waterfall
        shows queue_wait/device_wait/prefill/pipeline_stall/ship summing
        to the traced TTFT."""
        parent = _tracing.emit(
            "engine.request", start=req.submit_wall, duration=req.ttft,
            parent=req.trace_ctx, kind="serve",
            attrs={"request_id": req.request_id,
                   "deployment": self.deployment_name,
                   "replica": self.replica_tag})
        t = req.submit_wall
        for stage in _STAGES:
            d = bd[f"{stage}_s"]
            _tracing.emit(f"engine.{stage}", start=t, duration=d,
                          parent=parent, kind="serve")
            t += d

    def _admission_window(self) -> "Request | None":
        """Continuous admission: between the previous chunk's sync and
        the NEXT chunk's dispatch, block on the waiting queue for up to
        a fraction of the EMA chunk period; the loop prefills an arrival
        immediately (``_iteration``) and asks again. A prefill
        dispatched then queues behind only the
        ONE in-flight chunk — without the window, a request arriving
        just after an emit waits out the whole double-buffered pipeline
        (~2.5 chunks of queue_wait, the dominant TTFT term in
        BENCH_r07). The wait costs no device time: the in-flight chunk
        computes while this thread sleeps, and the remaining period
        fraction covers the next dispatch. Closed until the loop has a
        period estimate, when no slot is free, or under page
        backpressure (a request the pool can't place would spin).
        Returns the request that arrived, or None once the window is
        closed or has run out."""
        if (not self._continuous_admission or self._chunk_period is None
                or self._sync_t is None or self._stop.is_set()
                or self._admission_blocked
                or not any(r is None for r in self._active)):
            return None
        timeout = (self._sync_t + self._window_frac * self._chunk_period
                   - time.monotonic())
        if timeout <= 0:
            return None
        with _tracing.phase("engine.wait_arrivals", kind="serve",
                            attrs={"what": "window"}) as ph:
            try:
                req = self._waiting.get(timeout=timeout)
            except queue.Empty:
                req = None
            if ph:
                ph.set(arrivals=int(req is not None))
        return req

    # -- emission and retirement -------------------------------------------

    def _next_key(self):
        self._key, sub = jax.random.split(self._key)
        return sub

    def _emit(self, req: Request, tok: int):
        req.generated += 1
        self.total_generated += 1
        # the cache-capacity cutoff counts prompt + emitted tokens — the
        # _lengths mirror is chunk-granular (pre-advanced at dispatch)
        # and would trip this up to two chunks early
        done = (req.eos_id is not None and tok == req.eos_id) or \
            req.generated >= req.max_new_tokens or \
            len(req.prompt) + req.generated >= self.max_len
        req.out.put(tok)
        if done:
            req.done = True
            req.out.put(None)
            self.total_finished += 1
            if self._leaving.pop(req.request_id, None) is None:
                # an end the loop could not foresee (an ``eos_id``): the
                # slot is released here, where the end is read, and the
                # chunk in flight behind it was dispatched for nobody
                self._active[req.slot] = None
                self._retire_slot(req.slot)

    def _release_foreseen(self, slots):
        """Of ``slots``, live in the chunk just dispatched, release
        those whose answer ends inside it, whatever tokens come back:
        the first token and every chunk asked of the device
        (``_lengths`` counts them at dispatch and does not lag as
        ``generated`` does) cover the request's ``max_new_tokens`` or
        reach ``max_len``. Such a slot has no use for the next chunk and
        a waiting request does: it is not live at the next dispatch and
        the next admission may take it (a hand-over), a chunk sooner
        than where its end is read back. The request waits in
        ``_leaving`` for its last tokens."""
        for slot in slots:
            req = self._active[slot]
            asked = int(self._lengths[slot]) - len(req.prompt) + 1
            if (asked >= req.max_new_tokens
                    or len(req.prompt) + asked >= self.max_len):
                self._active[slot] = None
                self._leaving[req.request_id] = req
                self.retirements_foreseen += 1
                self._retire_slot(slot)

    def _retire_slot(self, slot: int):
        """A slot was released, where its request's end was read or,
        foreseen, where its last chunk was dispatched: its pages go
        back, the shared ones now and its own after two chunk syncs."""
        self._dev_dirty = True
        # a chunk dispatched before the release (a foreseen end's last
        # chunk; the chunk in flight behind an end that was read) may
        # still write into the slot's own (reserved) pages: defer the
        # free by two chunk syncs. Shared prefix pages are released
        # immediately — nothing ever WRITES them (suffix and decode
        # positions lie past the prefix), and whatever evicts and
        # rewrites one is a prefill dispatched later, so it runs after
        # every chunk in flight that still reads it.
        pages = self._alloc.owned.pop(slot, [])
        shared = self._shared.pop(slot, [])
        self._pending_hashes.pop(slot, None)
        self._table[slot, :] = -1
        self._prefix_len[slot] = 0
        if shared:
            self._prefix.release(shared)
        if pages:
            self._deferred_free.append([2, pages])

    def _age_deferred_frees(self, drain_all: bool = False):
        still = []
        for entry in self._deferred_free:
            entry[0] -= 1
            if drain_all or entry[0] <= 0:
                self._alloc.free.extend(entry[1])
            else:
                still.append(entry)
        self._deferred_free = still

    def _publish_digest(self, force: bool = False):
        """Drop this replica's prefix-cache digest into the process
        annex registry (throttled; the pusher ships it). Engine-thread
        only — ``_by_hash`` has a single mutator."""
        if not self._digest_enabled:
            return
        now = time.monotonic()
        if not force and now - self._digest_t < self._digest_interval:
            return
        self._digest_t = now
        from ray_tpu.runtime import metrics_plane as _mp
        hashes = [int.from_bytes(h[:8], "little")
                  for h in list(self._prefix._by_hash)]
        _mp.set_annex(f"serve/prefix_digest/{self.replica_tag}", {
            "tag": self.replica_tag,
            "deployment": self.deployment_name,
            "page_size": self.page_size,
            "hashes": hashes,
            "kv_free": len(self._alloc.free),
            "kv_total": self.num_pages,
        })

    # -- the loop: decode chunks, double buffered --------------------------

    def _loop(self):
        try:
            self._run_loop()
        except BaseException as e:  # noqa: BLE001 — propagate to callers
            self.error = e
        finally:
            # Runs on BOTH error and clean stop(): every live stream,
            # every waiter and every request the loop had taken off the
            # queue when a prefill dispatch failed gets its sentinel, so
            # no tokens() consumer can hang. Under _submit_lock so no
            # request slips in after the drain (see submit()).
            self._publish_stamped()
            with self._submit_lock:
                self._stop.set()
                live = {id(r): r for r in self._active if r is not None}
                live.update((id(r), r) for r in self._leaving.values())
                live.update((id(r), r) for r in self._admitting)
                for req in live.values():
                    req.out.put(None)
                while True:
                    try:
                        self._waiting.get_nowait().out.put(None)
                    except queue.Empty:
                        break

    def _use_drain_chunk(self) -> bool:
        """Short decode chunks ONLY when a waiting request could
        actually be admitted soon — i.e. a slot is about to retire (an
        active request near its token budget). Draining whenever the
        queue was non-empty ran 4-step chunks for entire saturated runs
        (4x the sync overhead) while no slot could possibly free.

        Two admission opportunities count: a FREE SLOT already exists
        (run the engine with max_batch above the offered concurrency and
        this is the common case — admission then never waits for a
        retirement), or a retirement is imminent. The horizon is 3
        chunks because the double-buffered loop's ``generated`` counts
        lag the device by up to two in-flight chunks. What the short
        chunk buys since ends are foreseen (``_release_foreseen``: no
        chunk in flight is lost behind one) is the shorter TAIL of the
        chunk an answer ends in, and an earlier admission."""
        if self._waiting.empty():
            return False
        if any(r is None for r in self._active) \
                and not self._admission_blocked:
            # a free slot AND admission actually possible (a page-starved
            # engine must not drain forever against a free slot it
            # cannot fill)
            return True
        horizon = 3 * self.decode_chunk
        return any(
            r is not None
            and (r.max_new_tokens - r.generated) <= horizon
            for r in self._active)

    def _device_inputs(self, active_idx):
        """Device-resident loop inputs (active mask, temps, lengths).
        Uploaded only when admission/retirement changed them — a
        per-dispatch host upload would otherwise serialize with the
        decode chunks."""
        if self._dev_inputs is None or self._dev_dirty:
            active = np.zeros((self.max_batch,), bool)
            active[active_idx] = True
            temps = np.array(
                [r.temperature if r is not None else 0.0
                 for r in self._active], np.float32)
            self._dev_inputs = {
                "active": jnp.asarray(active),
                "temps": jnp.asarray(temps),
                # .copy(): the host mirror is mutated right after each
                # dispatch; an asynchronous transfer reading the live
                # buffer would upload a torn lengths vector
                "lens": jnp.asarray(self._lengths.copy()),
            }
            self._dev_dirty = False
        return self._dev_inputs

    def _pages_bucket(self) -> int:
        """Power-of-two page count covering every live slot's RESERVED
        pages — exclusive AND shared-prefix (chained chunks may run
        ahead of the host's view of lengths, but never past the
        reservation)."""
        owned = [len(self._alloc.owned.get(i, ()))
                 + len(self._shared.get(i, ()))
                 for i, r in enumerate(self._active) if r is not None]
        need = max(owned) if owned else 1
        return min(_bucket(need, minimum=1), self.max_pages_per_seq)

    def _dispatch_decode(self, active_idx):
        """Dispatch one decode chunk (no host sync), chained off the
        DEVICE-resident last-token vector — admissions (prefill firsts
        scattered into it) and chunk outputs (merged in the decode
        program) both update it on device, so consecutive dispatches
        never need a host round trip no matter how the active set
        changed in between."""
        with _tracing.phase("engine.dispatch_decode", kind="serve") as ph:
            drain = self._use_drain_chunk()
            chunk = self._drain_chunk if drain else self.decode_chunk
            dev = self._device_inputs(active_idx)
            pb = self._pages_bucket()
            table = ("table", pb)
            if table not in dev:
                # sliced page table uploads only on admission/retirement
                # (the _device_inputs rebuild drops stale entries). The
                # explicit host COPY matters: jnp.asarray may transfer
                # asynchronously from the numpy buffer, and a retirement
                # writing table[slot] = -1 mid-transfer would hand the
                # in-flight chunk a torn table
                dev[table] = jnp.asarray(self._table[:, :pb].copy())
            toks, lens, new_last, stats = self._took(
                self._decode_paged(chunk, pb)(
                    self.params, *self._pools, dev[table], self._last_dev,
                    dev["lens"], dev["active"], dev["temps"],
                    self._next_key(), *self._state), 4)
            self._chunk_stats.append(stats)
            self.decode_dispatches += 1
            self.state_kernel_dispatches += int(self._state_kernel)
            latent_kernel = self._latent_backend and latent_kernel_engages(
                self.page_size, pb, self._selects)
            self.latent_kernel_dispatches += int(latent_kernel)
            now = time.monotonic()
            stream_seq = next(self._stream_seq)
            if ph:
                # KV rows the chunk's first step reads in a layer, over
                # the live slots, from the host's own lengths: all of a
                # slot's rows in a full layer, its window's in a sliding
                rows = self._lengths[active_idx].astype(np.int64) + 1
                ph.set(seq=stream_seq, chunk=chunk, drain=drain,
                       live=len(active_idx), slots=self.max_batch,
                       kv_rows_full=int(rows.sum()))
                if self._window is not None:
                    ph.set(kv_rows_window=int(
                        np.minimum(rows, self._window).sum()))
                if self._selects is not None:
                    # a layer with an indexer scores every row's index
                    # key and attends over the rows it picks: gathered,
                    # or read in place among the slot's (the kernel)
                    ph.set(index_rows=int(rows.sum()),
                           kv_rows_selected=int(
                               np.minimum(rows, self._selects).sum()),
                           latent_kernel=int(latent_kernel))
                if self._state:
                    # the live slots' recurrent state, which one step
                    # reads once and writes once in every layer, and
                    # whether this program does so in the state kernel
                    ph.set(state_slots=len(active_idx),
                           state_bytes=2 * len(active_idx)
                           * self._state_slot_bytes,
                           state_kernel=int(self._state_kernel))
            self._last_dev = new_last
            dev["lens"] = lens   # stays on device for the chained chunk
            # start the token matrix's device->host copy NOW: it overlaps
            # the next chunk's compute instead of adding a serial RTT to
            # every chunk sync
            try:
                toks.copy_to_host_async()
            except Exception:  # noqa: BLE001 - backend without async copy
                pass
            # host mirror advances deterministically (+chunk per active
            # slot) — retired slots are reconciled at admission
            self._lengths[active_idx] += chunk
            reqs = [self._active[i] for i in active_idx]
            self._release_foreseen(active_idx)
            seq = self._dispatch_seq
            self._dispatch_seq += 1
        self._ready_q.put(("decode", stream_seq, toks, now, (), ph or None))
        return _Chunk(toks, active_idx, reqs, seq, stream_seq, drain)

    def _emit_chunk(self, toks_np, active_idx, reqs) -> tuple:
        """A chunk's tokens to the streams of the requests it decoded
        for, whoever holds their slots by now. Returns the steps it
        computed for nobody, in the slots that were live at its
        dispatch: (those after an answer's end inside this chunk, those
        of slots whose answer had ended before this chunk was read: the
        double buffer's price for an end the loop could not foresee)."""
        steps = toks_np.shape[0]
        tail = ahead = 0
        for i, req in zip(active_idx, reqs):
            for t in range(steps):
                if req.done:
                    # finished mid-chunk (drop the surplus tokens), or
                    # in a chunk read before this one (drop them all)
                    if t:
                        tail += steps - t
                    else:
                        ahead += steps
                    break
                self._emit(req, int(toks_np[t, i]))
        # one chunk sync elapsed: age the deferred frees
        self._age_deferred_frees()
        self._publish_digest()
        return tail, ahead

    def _sync_chunk(self, chunk: _Chunk, firsts: bool):
        """Chunk N's host sync, then its tokens to their streams, and
        the account of its ``chunk x max_batch`` slot-steps. ``firsts``:
        the first tokens of prefills dispatched before the chunk are
        still to go out ahead of it (else the caller has drained them),
        so emission order per request is preserved."""
        with _tracing.phase("engine.wait_device", kind="serve",
                            attrs={"what": "chunk"}):
            toks_np = np.asarray(chunk.toks)
        now = time.monotonic()
        if firsts:
            self._drain_firsts(completed_seq=chunk.seq)
        with _tracing.phase("engine.emit", kind="serve") as ph:
            generated, finished = self.total_generated, self.total_finished
            tail, ahead = self._emit_chunk(toks_np, chunk.active_idx,
                                           chunk.reqs)
            steps = toks_np.shape[0]
            slot_steps = steps * self.max_batch
            delivered = self.total_generated - generated
            vacant = slot_steps - len(chunk.active_idx) * steps
            self.decode_slot_steps += slot_steps
            self.decode_delivered += delivered
            self.decode_overrun_tail += tail
            self.decode_overrun_ahead += ahead
            self.decode_vacant += vacant
            # the feed-forward's statistics of this chunk (chunks are
            # emitted in the order they were dispatched). They came out
            # of the program whose tokens the loop has just read, so
            # reading them waits for nothing
            stats = self._chunk_stats.popleft() if self._chunk_stats else {}
            if ph:
                ph.set(what="chunk", tokens=delivered,
                       finished=self.total_finished - finished,
                       slot_steps=slot_steps,
                       overrun_tail=tail, overrun_ahead=ahead,
                       vacant=vacant, seq=chunk.stream_seq, chunk=steps,
                       drain=chunk.drain,
                       **{name: float(v) for name, v in stats.items()})
        return now

    def _wait_idle(self):
        """No live slot and nothing in flight: poll for arrivals every
        millisecond, as ONE span however long the wait (an idle engine
        must not fill the span ring)."""
        with _tracing.phase("engine.wait_arrivals", kind="serve",
                            attrs={"what": "idle"}) as ph:
            while True:
                # every dispatched chunk has synced, so deferred frees
                # cannot race anything — release them all (otherwise
                # pages retired on the last emit before an idle period
                # would strand and deadlock page backpressure)
                if self._deferred_free:
                    self._age_deferred_frees(drain_all=True)
                self._publish_stamped()
                time.sleep(0.001)
                if self._stop.is_set() or not self._waiting.empty():
                    break
            if ph:
                ph.set(arrivals=self._waiting.qsize())

    def _run_loop(self):
        """Double-buffered decode over a device-resident last-token
        vector: while chunk N's tokens copy back to the host and get
        emitted, chunk N+1 already runs on device. Admissions scatter
        their (still on-device) first tokens into the vector, so the
        pipeline NEVER stalls for a prefill sync — first tokens are
        emitted asynchronously when their copy lands (_drain_firsts).
        Emission order per request is preserved: firsts dispatched
        before chunk N are force-drained right after chunk N's sync,
        before the chunk's tokens are emitted. Chunk N+1 is dispatched
        before chunk N is read, so an end seen only in chunk N's tokens
        (an ``eos_id``) leaves chunk N+1 computing for nobody in that
        slot; an end on the request's own bound is known at chunk N's
        dispatch, and chunk N+1 already decodes for the slot's next
        request (``_release_foreseen``).

        Each pass is one ``engine.iteration`` span while spans are
        recorded (``tracing.phase``), its phases its children: what the
        children leave uncovered is host work no phase names."""
        pending = None   # the _Chunk in flight
        self._last_dev = jnp.asarray(self._last_tok)
        for n in itertools.count():
            if self._stop.is_set():
                break
            with _tracing.phase("engine.iteration", kind="serve",
                                trace_id=self._trace_id) as ph:
                if ph:
                    ph.set(seq=n, waiting=self._waiting.qsize(),
                           live=sum(r is not None for r in self._active))
                pending = self._iteration(pending)

    def _iteration(self, pending):
        """One pass of the loop; returns the chunk left in flight.

        Requests are admitted from ONE place: at the top of the pass
        (``first`` None: whatever waits), then, while a chunk is in
        flight, each arrival inside the admission window, prefilled NOW,
        before the next chunk is dispatched behind it. One place,
        because a prefill program that holds a Pallas kernel carries its
        operations' source locations, ten frames of the stack that
        traced it, in its compile-cache key: admitted from two places,
        a program took its key from whichever met it first, and a later
        process that met it the other way compiled it again (v5e: one
        program of ten in every ``serve-chat`` run)."""
        first = None
        while True:
            self._admit(first)
            active_idx = [i for i, r in enumerate(self._active)
                          if r is not None]
            if first is None:
                if not active_idx:
                    self._sync_t = None   # pipeline drains: period resets
                    if pending is not None:
                        self._sync_chunk(pending, firsts=True)
                    elif self._pending_firsts:
                        # every active request is brand-new and nothing
                        # is in flight (e.g. max_new_tokens=1 bursts):
                        # block for the outstanding firsts
                        self._drain_firsts(completed_seq=self._dispatch_seq)
                    else:
                        self._wait_idle()
                    return None
                if pending is None:
                    return self._dispatch_decode(active_idx)
            first = self._admission_window()
            if first is None:
                break
        nxt = self._dispatch_decode(active_idx)
        # EVERY pending prefill was dispatched before nxt: block for
        # their firsts now (bounded by chunk N + prefill compute —
        # chunk N+1 is already queued behind them, so this wait
        # steals no device time) and emit them FIRST. Waiting for
        # the next chunk's sync instead cost a whole extra chunk of
        # first-token latency.
        self._drain_firsts(completed_seq=self._dispatch_seq)
        sync_t = self._sync_t
        now = self._sync_chunk(pending, firsts=False)
        if sync_t is not None:
            period = now - sync_t
            self._chunk_period = (
                period if self._chunk_period is None
                else 0.5 * self._chunk_period + 0.5 * period)
        self._sync_t = now
        return nxt

    # -- metrics -----------------------------------------------------------

    def stats(self) -> dict:
        out = {
            "active_slots": sum(r is not None for r in self._active),
            "waiting": self._waiting.qsize(),
            "total_generated": self.total_generated,
            "total_finished": self.total_finished,
            "prefill_dispatches": self.prefill_dispatches,
            "prefill_kernel_dispatches": self.prefill_kernel_dispatches,
            "expert_kernel_dispatches": self.expert_kernel_dispatches,
            "decode_dispatches": self.decode_dispatches,
            "state_kernel_dispatches": self.state_kernel_dispatches,
            "latent_kernel_dispatches": self.latent_kernel_dispatches,
            # every slot-step the decode programs computed, by what
            # became of it, and the prefill programs' token-rows with
            # the prompt tokens among them (see __init__)
            "decode_slot_steps": self.decode_slot_steps,
            "decode_delivered": self.decode_delivered,
            "decode_overrun_tail": self.decode_overrun_tail,
            "decode_overrun_ahead": self.decode_overrun_ahead,
            "decode_vacant": self.decode_vacant,
            # ends the loop foresaw, and the slots among them that a
            # waiting request took before the end was read back
            "retirements_foreseen": self.retirements_foreseen,
            "slots_handed_over": self.slots_handed_over,
            "prefill_token_rows": self.prefill_token_rows,
            "prefill_new_tokens": self.prefill_new_tokens,
            # recurrent state beside the pages (0 where the plan has no
            # recurrent run): rows whose state a prefill wrote into a
            # slot, and the bytes the slots' state arrays hold
            "state_installs": self.state_installs,
            "state_bytes_held": self._state_slot_bytes * self.max_batch,
            # the layers that keep pages (by format) and state, with the
            # bytes of a page and of a slot's state over them
            **self._holds(),
            "mean_ttft_s": float(np.mean(self.ttfts)) if self.ttfts else None,
            "kv_pages_total": self.num_pages,
            "kv_pages_free": len(self._alloc.free),
        }
        # feed the metrics plane: pool occupancy + prefix-cache hit
        # counters ride the process's next pushed delta frame
        if _metrics.enabled():
            g = _metrics.gauge("ray_tpu_serve_kv_pages",
                               "paged-KV pool size by state",
                               tag_keys=("state", "deployment", "replica"))
            base = {"deployment": self.deployment_name,
                    "replica": self.replica_tag}
            g.set(out["kv_pages_free"], tags={"state": "free", **base})
            g.set(self.num_pages, tags={"state": "total", **base})
        self._publish_digest(force=True)
        out["prefix_cache"] = {
            "enabled": self._prefix_enabled,
            "hit_pages": self._prefix.hit_pages,
            "miss_pages": self._prefix.miss_pages,
            "cached_idle_pages": self._prefix.evictable(),
        }
        out["kv_dtype"] = self.kv_dtype
        out["kv_pages_bytes"] = self._pages_bytes()
        out["cache_bytes_per_token"] = (
            out["kv_pages_bytes"] // (self.num_pages * self.page_size))
        # what max_batch contiguous bf16 rows of max_len would take
        out["kv_dense_equiv_bytes"] = (
            self.max_batch * self.max_len * self._bf16_row_bytes)
        return out
