"""Paged-KV continuous-batching LLM engine: the host loop.

Reference: ABSENT from the reference repo (it serves models via user
code in replicas — SURVEY.md P15). This is the one serving engine: a
continuous-batching host loop (this module: admission, page reservation,
the chunk pipeline, retirement, the accounts and spans) over device
programs that keep their KV in the vLLM-style paged format of
``ray_tpu/ops/paged_attention.py`` (``serve/engine_programs.py``: the
stores a model's layer plan states, the two programs, their arguments'
order, which kernels they run). The loop names a dispatch's host
inputs, calls the program it is handed and gets its results by name;
the pools and the slots' recurrent state are ``EnginePrograms``' to
put in, take back and keep.

- **Continuous batching**: a fixed-shape decode program runs every chunk
  over all ``max_batch`` slots; which slots are live is a mask, so
  admitting or retiring a request never recompiles. New requests are
  prefilled into a free slot (prompt padded to a power-of-two bucket, a
  handful of compiled prefill variants in all) while decode keeps
  streaming for everyone else. Tokens stream back through per-request
  queues (``serve/llm.py``: ``Request``).
- ONE page table, one allocator and one prefix cache serve every pool
  the plan states. Each slot owns a page list. HBM scales with TOKENS IN
  FLIGHT (reserved per request = prompt + max_new_tokens), not with
  ``max_batch * max_len`` — a 256-token chat on a 2048-token engine
  stops reserving 8x its need.
- Allocation is reserve-on-admit (pages for prompt + budget + one
  chained-overshoot page, released at retirement): admission applies
  backpressure when the pool is exhausted, and a mid-flight sequence
  can never fail an allocation — the deadlock-free policy (optimistic
  allocation + preemption is a future extension).
- What the two programs compute and what reaches a client differ, and
  the loop keeps the account (always on, integers in ``stats()``; on the
  spans while spans are recorded). A decode chunk is ``chunk x
  max_batch`` slot-steps whatever the slots hold; where it is read back
  each is one of: DELIVERED (a token on a request's stream), OVERRUN
  TAIL (a live slot's steps after its answer ended inside the chunk),
  OVERRUN AHEAD (all of a live slot's steps where its answer had ended
  in the chunk read before: the price of the double buffer for an end
  the host could not foresee, an ``eos_id``'s; and a request whose first
  token is its last is live in the one chunk dispatched behind its
  prefill), VACANT (slots not live at dispatch); the four sum to the
  chunk's slot-steps, exactly. An end the host CAN foresee costs no
  chunk in flight (``_release_foreseen``). A prefill dispatch is ``group
  x bucket`` token-rows, of which the rows' suffixes are prompt tokens
  and the rest padding to the bucket. Which kernels a dispatch's program
  runs is on its span and counted beside ``prefill_dispatches`` and
  ``decode_dispatches``; what the plan's layers hold is in ``stats()``
  and on ``engine.construct``.

Threading: one engine thread owns the device loop (admission, prefill
and decode dispatches, emission); a watcher thread blocks on each
dispatch in stream order and stamps when the device ran it; callers
enqueue requests and read token queues — no JAX calls on caller threads.
"""

from __future__ import annotations

import itertools
import queue
import threading
import time
import uuid
from collections import deque
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.ops.paged_attention import (PageAllocator, PrefixCache,
                                         page_hashes)
from ray_tpu.serve.engine_programs import (EnginePrograms, _model_module,
                                           _paged_decode_impl,
                                           _paged_prefill_impl, _recurrent)
from ray_tpu.serve.llm import _STAGES, Request, _serve_hist
from ray_tpu.util import metrics as _metrics
from ray_tpu.util import program_scopes as _program_scopes
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
        # what each slot keeps per layer beside its pages (None: nothing)
        recurrent = _recurrent(_model_module(cfg).layer_plan(cfg))
        # a prefix is its pages, and over a recurrent run the state at
        # their end, which the pages keep or do not
        # (``RecurrentState.pages_keep``)
        reusable = recurrent is None or recurrent.pages_keep
        if prefix_cache and not reusable:
            raise ValueError(
                "prefix_cache=True over a layer plan with a recurrent run "
                "whose state the pages do not keep (a Mamba-2 state of "
                "megabytes: Falcon-H1's, Nemotron-H's, Granite 4.0-H's): "
                "a prefix hit would hand a request its prefix's KV pages "
                "without the recurrent state at their end, and its tokens "
                "would be silently wrong (a plan whose state is worth a "
                "page's keeping says so: RecurrentState.pages_keep; what "
                "a state of megabytes would need: ROADMAP Queue 2 B.5)")
        if prefix_cache is None:
            # the flag, for a plan whose prefix its pages hold whole
            prefix_cache = (_cfg.serve_prefix_cache_enabled   # flag
                            and reusable)
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
        # of decode steps, not per token — every sync has a fixed host
        # cost, so fewer dispatches per token. Admission of waiting
        # requests happens between chunks (adds <= chunk * step_time to
        # queueing latency). Default: flag serve_decode_chunk.
        if decode_chunk is None:
            decode_chunk = _cfg.serve_decode_chunk
        self.decode_chunk = max(1, decode_chunk)
        # the SHORT chunk (``_use_drain_chunk``); flag serve_drain_chunk
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
        # continuous admission (``_admission_window``); flag
        # serve_admission_window_frac
        self._window_frac = min(0.95, max(
            0.0, float(_cfg.serve_admission_window_frac)))
        self._sync_t: float | None = None       # last chunk-sync finish
        self._chunk_period: float | None = None  # EMA between syncs
        # host-side slot state (the trusted copy of the device lengths)
        self._lengths = np.zeros((max_batch,), np.int32)
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
        # ready watcher: block_until_ready OFF the loop thread, so the
        # measurement never stalls the decode pipeline (_ready_watcher;
        # started with the loop, joined by stop())
        self._ready_q: "queue.Queue" = queue.Queue()
        self._watcher: threading.Thread | None = None
        # every dispatch's place in the device stream (prefills and
        # chunks together; _dispatch_seq below counts chunks alone)
        self._stream_seq = itertools.count()
        # the engine loop's spans are one trace (util/tracing.phase)
        self._trace_id = uuid.uuid4().hex[:16]
        # the programs dispatched while spans were recorded, by their
        # modules' names: ``stop`` records what their instructions are
        # pieces of (util/program_scopes.py)
        self._traced_programs: set[str] = set()
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
        # prefill batches whose first tokens haven't reached the host
        # yet: (dispatch_seq_at, items, firsts_device)
        self._pending_firsts: list = []
        self._dispatch_seq = 0
        # set when an admission failed on pages (not slots) this round —
        # gates the free-slot drain clause
        self._admission_blocked = False

        # -- device state: the stores the plan states and the programs
        # over them (``engine_programs``), and the host's bookkeeping
        built = time.time()
        self._programs = EnginePrograms(
            cfg, params, max_batch=max_batch, num_pages=self.num_pages,
            page_size=page_size, kv_dtype=kv_dtype)
        if _tracing.recording():
            _tracing.emit("engine.construct", start=built,
                          duration=time.time() - built, kind="serve",
                          attrs=self._programs.holds())
        # rows whose recurrent state a prefill wrote into a slot; of
        # them, those that BEGAN from the state a page keeps (a prefix
        # hit over a plan whose pages keep it), and the pages whose end
        # a prefill wrote the state of
        self.state_installs = 0
        self.state_restores = 0
        self.state_snapshot_pages = 0
        # decode dispatches, and those whose program advances the state
        # in the state kernel, reads the rows its layers pick in the
        # latent kernel and scores their index keys in the index kernel
        # (``EnginePrograms.decode_kernels``)
        self.decode_dispatches = 0
        self.state_kernel_dispatches = 0
        self.latent_kernel_dispatches = 0
        self.index_kernel_dispatches = 0
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
        # deferred page frees: [syncs_remaining, slot_pages]
        # (``_retire_slot``)
        self._deferred_free: list[list] = []
        # prefill dispatches, and those whose program holds the prefill
        # attention kernel (in its full layers; in its sliding layers;
        # over latent rows, in either) and computes its routed experts in
        # the grouped kernel (``EnginePrograms.prefill_kernels``)
        self.prefill_dispatches = 0
        self.prefill_kernel_dispatches = 0
        self.window_kernel_dispatches = 0
        self.latent_prefill_kernel_dispatches = 0
        self.expert_kernel_dispatches = 0
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
        # prefix-cache digest publishing (serve/prefix_router.py,
        # ``_publish_digest``): handles route repeat-prefix traffic to
        # the replica already holding the pages
        self._digest_enabled = (self._prefix_enabled
                                and _cfg.serve_prefix_routing_enabled)
        self._digest_interval = float(_cfg.serve_digest_publish_interval_s)
        self._digest_t = 0.0

    # the two programs' bodies, as ``(cfg, params, *args, ...)`` in the
    # order ``engine_programs`` states: what lowers a program without an
    # engine (the compile tests) takes them from here
    _paged_decode_impl = staticmethod(_paged_decode_impl)
    _paged_prefill_impl = staticmethod(_paged_prefill_impl)

    def _window_pages(self, max_covered: int) -> int:
        """Power-of-two page count covering ``max_covered`` tokens,
        clamped to the table width."""
        need = max(1, -(-max_covered // self.page_size))
        return min(_bucket(need, minimum=1), self.max_pages_per_seq)

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
        if self._traced_programs:
            # a traced slice dispatched these: their instruction-to-scope
            # maps, while the backend still holds their executables
            _program_scopes.record_programs(self._traced_programs)
            self._traced_programs = set()

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
        this round (or, with ``req.error`` set, rejects it). Cached
        full-prefix pages are mapped read-only into the slot's table
        (never re-written: suffix writes start at the first non-reused
        page boundary and decode writes past the prompt) and only the
        remainder is allocated fresh."""
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
        # whether this program's full layers, and its sliding ones,
        # attend in the prefill kernel, and its routed experts run in the
        # grouped one
        kernels = self._programs.prefill_kernels(len(part), bucket, wp)
        token_rows, new_tokens = len(part) * bucket, int(slens_np.sum())
        self.prefill_dispatches += 1
        self.prefill_kernel_dispatches += kernels["attn_kernel"]
        self.window_kernel_dispatches += kernels["window_attn_kernel"]
        self.latent_prefill_kernel_dispatches += kernels["latent_attn_kernel"]
        self.expert_kernel_dispatches += kernels["expert_kernel"]
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
                   missed_pages=lookups - cached // page, **kernels,
                   page_rows=self._programs.page_rows)
        slots = None
        if self._programs.recurrent is not None:
            # every row's final state goes into its slot; the scan cuts
            # the padded bucket into chunks, padding included
            slots = jnp.asarray(np.array([it[1] for it in part], np.int32))
            self.state_installs += len(part)
            if ph:
                ph.set(state_installs=len(part),
                       scan_chunks=len(part) * -(
                           -bucket // self._programs.recurrent.chunk))
            if self._programs.kept:
                # the pages keep the state at their end: rows that start
                # behind reused pages begin from it, and every page a
                # suffix completes has its own written
                restores = int((starts_np > 0).sum())
                written = int((slens_np // self.page_size).sum())
                self.state_restores += restores
                self.state_snapshot_pages += written
                if ph:
                    ph.set(state_restores=restores,
                           state_snapshot_pages=written)
        slens = jnp.asarray(slens_np)
        rows = jnp.asarray(np.stack(
            [self._table[it[1]][:wp] for it in part]))
        temps = jnp.asarray(np.array(
            [it[0].temperature for it in part], np.float32))
        # called from THIS frame (``EnginePrograms.prefill``)
        program, arguments = self._programs.prefill(
            wp, table_rows=rows, tokens=tokens, slens=slens,
            starts=jnp.asarray(starts_np), temps=temps,
            key=self._next_key(), slots=slots)
        firsts = self._programs.prefilled(program(*arguments))
        if ph:
            self._traced_programs.add("jit_" + program.__name__)
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
            self._last_dev = self._programs.scatter_firsts(
                self._last_dev, slots, firsts)
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
            # itself block on the device, which serialized the whole
            # loop. Readiness is derived purely from device-stream
            # ordering via completed_seq.
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
        dispatched then queues behind only the ONE in-flight chunk —
        without the window, a request arriving just after an emit waits
        out the whole double-buffered pipeline (~2.5 chunks of
        queue_wait). The wait costs no device time: the in-flight chunk
        computes while this thread sleeps, and the remaining period
        fraction covers the next dispatch. Closed until the loop has a
        period estimate, when no slot is free, or under page
        backpressure (a request the pool can't place would spin).
        Returns the request that arrived, or None once the window is
        closed or has run out."""
        if (self._chunk_period is None or self._sync_t is None
                or self._stop.is_set()
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
            program, arguments = self._programs.decode(
                chunk, pb, table=dev[table], tokens=self._last_dev,
                lengths=dev["lens"], active=dev["active"],
                temps=dev["temps"], key=self._next_key())
            out = self._programs.decoded(program(*arguments))
            if ph:
                self._traced_programs.add("jit_" + program.__name__)
            toks = out["toks"]
            self._chunk_stats.append(out["stats"])
            kernels = self._programs.decode_kernels(pb)
            self.decode_dispatches += 1
            self.state_kernel_dispatches += kernels["state_kernel"]
            self.latent_kernel_dispatches += kernels["latent_kernel"]
            self.index_kernel_dispatches += kernels["index_kernel"]
            now = time.monotonic()
            stream_seq = next(self._stream_seq)
            if ph:
                # KV rows the chunk's first step reads in a layer, over
                # the live slots, from the host's own lengths: all of a
                # slot's rows in a full layer, its window's in a sliding
                rows = self._lengths[active_idx].astype(np.int64) + 1
                ph.set(seq=stream_seq, chunk=chunk, drain=drain,
                       live=len(active_idx), slots=self.max_batch,
                       kv_rows_full=int(rows.sum()),
                       attn_step_pages=kernels["attn_step_pages"])
                programs = self._programs
                if programs.window is not None:
                    # and the live slots whose context has passed it
                    ph.set(kv_rows_window=int(
                               np.minimum(rows, programs.window).sum()),
                           slots_past_window=int(
                               (rows > programs.window).sum()))
                if programs.selects is not None:
                    # a layer with an indexer scores every row's index
                    # key (gathered, or where it lies: the index kernel)
                    # and attends over the rows it picks: gathered, or
                    # read in place among the slot's (the latent kernel)
                    ph.set(index_rows=int(rows.sum()),
                           kv_rows_selected=int(
                               np.minimum(rows, programs.selects).sum()),
                           latent_kernel=kernels["latent_kernel"],
                           index_kernel=kernels["index_kernel"])
                if programs.recurrent is not None:
                    # the live slots' recurrent state, which one step
                    # reads once and writes once in every layer, and
                    # whether this program does so in the state kernel
                    ph.set(state_slots=len(active_idx),
                           state_bytes=2 * len(active_idx)
                           * programs.state_slot_bytes,
                           state_kernel=kernels["state_kernel"])
            self._last_dev = out["last"]
            dev["lens"] = out["lengths"]   # on device for the chained chunk
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
        before the chunk's tokens are emitted. What the double buffer
        costs an answer's end: module docstring, ``_release_foreseen``.

        Each pass is one ``engine.iteration`` span while spans are
        recorded (``tracing.phase``), its phases its children: what the
        children leave uncovered is host work no phase names."""
        pending = None   # the _Chunk in flight
        self._last_dev = jnp.asarray(np.zeros((self.max_batch,), np.int32))
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

    # ``stats()``'s always-on integers, each the attribute of its name,
    # counted where ``__init__`` says what it is
    _COUNTS = (
        "total_generated", "total_finished", "prefill_dispatches",
        "prefill_kernel_dispatches", "window_kernel_dispatches",
        "latent_prefill_kernel_dispatches", "expert_kernel_dispatches",
        "decode_dispatches", "state_kernel_dispatches",
        "latent_kernel_dispatches", "index_kernel_dispatches",
        "decode_slot_steps", "decode_delivered",
        "decode_overrun_tail", "decode_overrun_ahead", "decode_vacant",
        "retirements_foreseen", "slots_handed_over", "prefill_token_rows",
        "prefill_new_tokens", "state_installs", "state_restores",
        "state_snapshot_pages")

    def stats(self) -> dict:
        out = {
            "active_slots": sum(r is not None for r in self._active),
            "waiting": self._waiting.qsize(),
            **{name: getattr(self, name) for name in self._COUNTS},
            # the bytes the slots' recurrent state arrays hold (0 where
            # the plan has no recurrent run)
            "state_bytes_held": (self._programs.state_slot_bytes
                                 * self.max_batch),
            # the layers that keep pages (by format) and state, with the
            # bytes of a page and of a slot's state over them
            **self._programs.holds(),
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
        out["kv_pages_bytes"] = self._programs.pages_bytes()
        out["cache_bytes_per_token"] = (
            out["kv_pages_bytes"] // (self.num_pages * self.page_size))
        # what max_batch contiguous bf16 rows of max_len would take
        out["kv_dense_equiv_bytes"] = (
            self.max_batch * self.max_len * self._programs.bf16_row_bytes)
        return out
