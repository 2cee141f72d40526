"""Continuous-batching LLM serving engine on TPU.

The reference serves models via user code inside Serve replicas
(`python/ray/serve/_private/replica.py`, SURVEY.md P15) — it has no model
engine. This module is the TPU-native engine a Serve deployment wraps:

- **Continuous batching**: a fixed-shape decode program runs every step over
  all `max_batch` cache slots; which slots are live is a mask, so admitting
  or retiring a request never recompiles. New requests are prefilled into a
  free slot (prompt padded to a power-of-two bucket — a handful of compiled
  prefill variants total) while decode keeps streaming for everyone else.
- **Static shapes everywhere**: the only compiled programs are
  one decode step + one prefill per bucket size.
- Tokens stream back to callers through per-request queues; TTFT and
  throughput are measured at the engine so Serve autoscaling can act on
  queue depth and latency.

Threading: one engine thread owns the device loop (prefill/decode); callers
enqueue requests and read token queues — no JAX calls on caller threads.
"""

from __future__ import annotations

import itertools
import queue
import threading
import time
import uuid
from collections import deque
from dataclasses import dataclass, field
from functools import partial
from typing import Iterator

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models import decoding
from ray_tpu.models.decoding import (KVCache, SamplingParams, lax_slice_row,
                                     lax_update_row)
from ray_tpu.util import metrics as _metrics
from ray_tpu.util import tracing as _tracing

# Per-request TTFT decomposition (metrics plane): every request's time to
# first token splits into queue_wait (submit -> prefill dispatch),
# device_wait (dispatch -> the device starts the prefill: the wait on its
# queue behind the decode chunk in flight), prefill (the program's own
# run; start and end stamped by the watcher thread), pipeline_stall
# (device completion -> the loop coming for the firsts) and ship (the
# host copy of the first-token batch). The five stages sum to the
# observed TTFT exactly (see Request.breakdown). Series carry the
# hosting deployment + replica tags (from the serve replica context) so
# the controller's autoscaler and the dashboard can split per
# deployment/replica; engines outside serve tag deployment="-".
_STAGES = ("queue_wait", "device_wait", "prefill", "pipeline_stall", "ship")
_serve_hist = _metrics.histogram(
    "ray_tpu_serve_stage_s", "per-request serve TTFT stage latency",
    tag_keys=("stage", "deployment", "replica"))


def _named_jit(name: str, fn, **jit_kwargs):
    """``jax.jit(fn)`` as the program ``jit_<name>``: a jitted
    ``functools.partial`` or lambda is ``jit__unknown`` / ``jit__lambda_``
    in a device trace, a compile log and the backend's list of live
    executables. The name carries the program's static facts (chunk,
    pages), so a reader tells the programs apart without looking inside
    them; it is also part of the compile-cache key."""
    def program(*args, **kwargs):
        return fn(*args, **kwargs)
    program.__name__ = program.__qualname__ = name
    return jax.jit(program, **jit_kwargs)


def _wall(mono: float) -> float:
    """A ``time.monotonic()`` stamp on the wall clock spans are kept on."""
    return time.time() - (time.monotonic() - mono)


def _bucket(n: int, minimum: int = 16) -> int:
    b = minimum
    while b < n:
        b *= 2
    return b


@dataclass
class Request:
    request_id: int
    prompt: np.ndarray                    # [P] int32
    max_new_tokens: int = 128
    temperature: float = 0.0
    eos_id: int | None = None
    # filled by the engine:
    out: "queue.Queue[int | None]" = field(default_factory=queue.Queue)
    submit_t: float = field(default_factory=time.monotonic)
    first_token_t: float | None = None
    # TTFT decomposition stamps (see Request.breakdown): prefill batch
    # dispatched / the device started it / its results ready (both from
    # the watcher thread) / the loop came to read the first-token batch
    dispatch_t: float | None = None
    start_t: float | None = None
    ready_t: float | None = None
    drain_t: float | None = None
    generated: int = 0
    slot: int = -1
    # set before the None sentinel when the request itself failed
    # (e.g. prompt longer than the cache) — distinguishes rejection from
    # a legitimate empty/EOS completion
    error: BaseException | None = None
    # tracing: the ambient span context at submit() (the replica's run
    # span when the request came through serve) plus a wall-clock submit
    # stamp — the engine emits its TTFT stage spans against these after
    # the first token drains
    trace_ctx: object | None = None
    submit_wall: float | None = None

    @property
    def ttft(self) -> float | None:
        if self.first_token_t is None:
            return None
        return self.first_token_t - self.submit_t

    @property
    def breakdown(self) -> dict | None:
        """Measured TTFT decomposition. ``start_t`` and ``ready_t``
        (stamped by the watcher thread off the device stream) are
        clamped into dispatch_t <= start <= ready <= first_token_t, so
        the five stages ALWAYS sum to the observed TTFT exactly. The
        loop usually comes for the firsts (``drain_t``) before they are
        ready and blocks: that wait is the device's (device_wait,
        prefill), not a stall of the pipeline, and ship starts when both
        the results and the loop are there."""
        if (self.first_token_t is None or self.dispatch_t is None
                or self.drain_t is None):
            return None
        ready = self.ready_t if self.ready_t is not None else self.drain_t
        ready = min(max(ready, self.dispatch_t), self.first_token_t)
        start = self.start_t if self.start_t is not None \
            else self.dispatch_t
        start = min(max(start, self.dispatch_t), ready)
        taken = min(max(self.drain_t, ready), self.first_token_t)
        return {
            "queue_wait_s": self.dispatch_t - self.submit_t,
            "device_wait_s": start - self.dispatch_t,
            "prefill_s": ready - start,
            "pipeline_stall_s": taken - ready,
            "ship_s": self.first_token_t - taken,
        }

    engine: "LLMEngine | None" = None

    def tokens(self) -> Iterator[int]:
        """Blocking stream of generated token ids (ends on None sentinel).
        Raises the engine's error if its device loop died."""
        while True:
            tok = self.out.get()
            if tok is None:
                if self.error is not None:
                    raise self.error
                if self.engine is not None and self.engine.error is not None:
                    raise RuntimeError(
                        "LLM engine loop failed"
                    ) from self.engine.error
                return
            yield tok


class LLMEngine:
    """Slot-based continuous batching over `ray_tpu.models.decoding`."""

    def __init__(self, cfg, params, *, max_batch: int = 8,
                 max_len: int = 2048, prefill_chunk: int = 1024,
                 decode_chunk: int | None = None,
                 drain_chunk: int | None = None):
        from ray_tpu.utils.config import get_config

        _cfg = get_config()
        self.cfg = cfg
        self.params = params
        self.max_batch = max_batch
        self.max_len = max_len
        self.prefill_chunk = prefill_chunk
        # tokens generated per device round trip: one host sync per CHUNK
        # of decode steps (lax.scan), not per token — every sync has a
        # fixed host cost, so fewer dispatches per token. Admission of
        # waiting requests happens between chunks (adds <= chunk *
        # step_time to queueing latency). Default: flag serve_decode_chunk.
        if decode_chunk is None:
            decode_chunk = _cfg.serve_decode_chunk
        self.decode_chunk = max(1, decode_chunk)
        self._drain_chunk_flag = (drain_chunk if drain_chunk is not None
                                  else _cfg.serve_drain_chunk)
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
        # host-side slot state (mirrors cache.lengths but trusted copy)
        self._lengths = np.zeros((max_batch,), np.int32)
        self._last_tok = np.zeros((max_batch,), np.int32)
        # bumped per admission into a slot: lets the pipelined loop tell
        # "same slot, same request" from "same slot, NEW request" when
        # deciding whether an in-flight chunk's tokens are still valid
        self._slot_gen = np.zeros((max_batch,), np.int64)
        self._active: list[Request | None] = [None] * max_batch
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
        # set when an admission failed on resources (not slots) this
        # round — gates the free-slot drain clause
        self._admission_blocked = False
        # drain-mode decode: a SHORT chunk used when a slot is about to
        # retire while requests wait, so admission happens within a few
        # steps instead of a full chunk (TTFT <- admission latency);
        # flag serve_drain_chunk
        self._drain_chunk = max(1, min(self._drain_chunk_flag,
                                       self.decode_chunk))
        self._setup_device_state()

    def _setup_device_state(self):
        """Build the KV cache + compiled programs (dense layout; the
        paged engine overrides this — serve/paged_llm.py)."""
        cfg = self.cfg
        from ray_tpu.models import olmoe

        if isinstance(cfg, olmoe.OlmoeConfig):
            raise TypeError(
                "the dense-KV engine (serve/llm.py, models/decoding.py) is "
                "not taught the OLMoE block (QK-norm, routed experts): "
                "serve an OlmoeConfig through PagedLLMEngine / "
                "kv_layout='paged'")
        self._cache = decoding.init_cache(cfg, self.max_batch,
                                          self.max_len)
        self._decode_fn = _named_jit(
            f"dense_decode_c{self.decode_chunk}",
            partial(self._decode_impl, cfg, chunk=self.decode_chunk),
            donate_argnums=(1,)
        )
        self._decode_fn_drain = (
            self._decode_fn if self._drain_chunk == self.decode_chunk
            else _named_jit(
                f"dense_decode_c{self._drain_chunk}",
                partial(self._decode_impl, cfg, chunk=self._drain_chunk),
                donate_argnums=(1,)))
        self._prefill_fn = _named_jit(
            "dense_prefill", partial(self._prefill_impl, cfg),
            static_argnames=("bucket",), donate_argnums=(1,),
        )
        # batched prefill: N prompts of one bucket in ONE dispatch —
        # each dispatch has a fixed sync cost, so a 16-request burst
        # admitted one-by-one pays 16 of them serially in TTFT before
        # any compute. Specializes per (n, bucket) shape;
        # admission splits bursts into power-of-two groups so the
        # variant count stays logarithmic.
        self._prefill_batch_fn = _named_jit(
            "dense_prefill_batch", partial(self._prefill_batch_impl, cfg),
            donate_argnums=(1,))

    # -- jitted programs ---------------------------------------------------

    @staticmethod
    def _decode_impl(cfg, params, cache: KVCache, tokens, lengths, active,
                     temps, key, *, chunk):
        """``chunk`` decode steps over every slot in one compiled program
        (scan); returns the [chunk, max_batch] token matrix plus the
        advanced lengths (kept ON DEVICE so chained chunks never need a
        host upload). Inactive slots are computed but masked (position 0
        write is harmless: a later prefill overwrites). Slots finishing
        mid-chunk keep decoding; the host drops their surplus tokens."""
        def step(carry, _):
            cache, toks, lens, key = carry
            key, sub = jax.random.split(key)
            start = jnp.where(active, lens, 0)
            logits, cache = decoding.cached_forward(
                cfg, params, toks[:, None], cache, start=start,
                logits_mode="last",
            )
            nxt = decoding.select_tokens(logits, temps, sub)
            lens = jnp.where(active, lens + 1, lens)
            return (cache, nxt, lens, key), nxt

        (cache, _, lens, _), toks = jax.lax.scan(
            step, (cache, tokens, lengths, key), None, length=chunk)
        # merged last-token vector: chunk-active slots advance to their
        # newest token, others keep their prior value — the loop chains
        # every next dispatch off this DEVICE array, so admissions /
        # retirements never force a host round trip to rebuild last_tok
        new_last = jnp.where(active, toks[-1], tokens)
        return cache, toks, lens, new_last

    @staticmethod
    def _prefill_impl(cfg, params, cache: KVCache, tokens, plen, slot, *,
                      bucket):
        """Prefill one prompt (padded to `bucket`) into cache row `slot`.
        Operates on a sliced single-row cache so cost is independent of
        max_batch."""
        row_k = lax_slice_row(cache.k, slot)
        row_v = lax_slice_row(cache.v, slot)
        row = KVCache(k=row_k, v=row_v,
                      lengths=jnp.zeros((1,), jnp.int32))
        logits, row = decoding.cached_forward(
            cfg, params, tokens[None, :], row,
            start=jnp.zeros((1,), jnp.int32),
            logits_mode="index", logits_idx=plen[None] - 1,
        )
        k = lax_update_row(cache.k, row.k, slot)
        v = lax_update_row(cache.v, row.v, slot)
        return KVCache(k=k, v=v, lengths=cache.lengths), logits[0]

    @staticmethod
    def _prefill_batch_impl(cfg, params, cache: KVCache, tokens, plens,
                            slots, temps, key):
        """Prefill ``n`` prompts (one bucket, padded) into cache rows
        ``slots`` in a single program, and sample each row's first
        token. Rows are gathered, run as one batch-n forward, and
        scattered back — cost scales with n, dispatch overhead doesn't."""
        n = tokens.shape[0]
        rows = KVCache(
            k=jnp.take(cache.k, slots, axis=1),
            v=jnp.take(cache.v, slots, axis=1),
            lengths=jnp.zeros((n,), jnp.int32))
        logits, rows = decoding.cached_forward(
            cfg, params, tokens, rows,
            start=jnp.zeros((n,), jnp.int32),
            logits_mode="index", logits_idx=plens - 1,
        )
        k = cache.k.at[:, slots].set(rows.k.astype(cache.k.dtype))
        v = cache.v.at[:, slots].set(rows.v.astype(cache.v.dtype))
        first = decoding.select_tokens(logits, temps, key)
        return KVCache(k=k, v=v, lengths=cache.lengths), first

    def warmup(self, prompt_len: int):
        """Deterministically compile every program a burst at this
        prompt bucket can hit: the batched prefill at each power-of-two
        group size up to max_batch, and both decode programs. Call
        BEFORE start() (request-driven warmup races the admit loop, so
        which (n, bucket) prefill variants compile is scheduling-
        dependent — a missed one lands seconds of JIT inside a measured
        or user-facing TTFT)."""
        bucket = min(_bucket(prompt_len), self.max_len)
        tokens = jnp.zeros((1, bucket), jnp.int32)
        if self._last_dev is None:
            self._last_dev = jnp.asarray(self._last_tok)
        n = 1
        while n <= self.max_batch:
            toks = jnp.broadcast_to(tokens, (n, bucket))
            self._cache, firsts = self._prefill_batch_fn(
                self.params, self._cache, toks,
                jnp.ones((n,), jnp.int32),
                jnp.arange(n, dtype=jnp.int32),
                jnp.zeros((n,), jnp.float32), self._next_key())
            # warm the firsts scatter at this group size too: it
            # specializes per slots-shape, and a compile inside _admit
            # stalls the loop ~0.5s per NEW burst size (measured)
            self._last_dev = self._scatter_fn(
                self._last_dev, jnp.arange(n, dtype=jnp.int32), firsts)
            np.asarray(firsts)
            n *= 2
        self._last_dev = jnp.asarray(self._last_tok)
        active = jnp.zeros((self.max_batch,), bool)
        for fn in {id(self._decode_fn): self._decode_fn,
                   id(self._decode_fn_drain):
                       self._decode_fn_drain}.values():
            self._cache, toks, _, _ = fn(
                self.params, self._cache,
                jnp.zeros((self.max_batch,), jnp.int32),
                jnp.zeros((self.max_batch,), jnp.int32), active,
                jnp.zeros((self.max_batch,), jnp.float32),
                self._next_key())
            np.asarray(toks)
        # warmup wrote garbage prefills into cache rows; lengths stayed
        # 0 and no slot is active, so real admissions overwrite cleanly
        self._lengths[:] = 0
        self._last_tok[:] = 0

    # -- engine loop -------------------------------------------------------

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

    def _free_slots(self) -> list[int]:
        return [i for i, r in enumerate(self._active) if r is None]

    def _on_slot_retired(self, slot: int):
        """Hook: a request finished and its slot was released (paged
        engine reclaims KV pages here)."""
        self._dev_dirty = True

    def _on_idle(self):
        """Hook: the loop has no active slots and nothing in flight
        (paged engine finishes deferred page frees here — with the
        pipeline drained they cannot race an in-flight chunk)."""

    def _reserve_slot_resources(self, req: "Request", slot: int) -> bool:
        """Hook: claim per-slot resources for an admission (paged engine
        reserves KV pages). False = backpressure — the caller requeues
        the request and stops admitting this round."""
        return True

    def _pack_admit(self, req: "Request", slot: int, plen: int) -> tuple:
        """Hook: build one admit item (req, slot, plen, padded) — the
        tokens the prefill program must actually process, padded to a
        power-of-two bucket (the paged engine packs only the
        non-prefix-cached SUFFIX here)."""
        bucket = min(_bucket(plen), self.max_len)
        padded = np.zeros((bucket,), np.int32)
        padded[:plen] = req.prompt
        return (req, slot, plen, padded)

    def _dispatch_prefill(self, part: list, bucket: int, ph):
        """Hook: dispatch one prefill sub-batch (``part`` is a list of
        (req, slot, plen, padded)); returns the device first-tokens.
        ``ph`` is the dispatch's span (``tracing.phase``): the paged
        engine adds its window and prefix-cache counts."""
        tokens = jnp.asarray(np.stack([it[3] for it in part]))
        plens = jnp.asarray(np.array([it[2] for it in part], np.int32))
        slots = jnp.asarray(np.array([it[1] for it in part], np.int32))
        temps = jnp.asarray(np.array(
            [it[0].temperature for it in part], np.float32))
        if ph:
            ph.set(new_tokens=sum(it[2] for it in part), cached_tokens=0)
        self._cache, firsts = self._prefill_batch_fn(
            self.params, self._cache, tokens, plens, slots, temps,
            self._next_key(),
        )
        return firsts

    def _admit(self, first: "Request | None" = None):
        with _tracing.phase("engine.admit", kind="serve") as ph:
            admitted, dispatches = self._admit_round(first)
            if ph:
                ph.set(admitted=admitted, dispatches=dispatches,
                       blocked=self._admission_blocked)

    def _admit_round(self, first: "Request | None") -> tuple:
        """Prefill waiting requests into free slots. All prefills of the
        round are DISPATCHED first and their first tokens extracted in
        one host pass — each sync has a fixed cost, so a burst of
        admissions pays ~one, not one per request. ``first``: a request
        already pulled off the queue (the admission window's timed get)
        — admitted ahead of the queue, requeued on backpressure like any
        other. Returns (requests admitted, prefill dispatches)."""
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
            if not self._reserve_slot_resources(req, slot):
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
            return 0, 0
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
                req.slot = slot
                self._active[slot] = req
                # admission GENERATION: an in-flight decode chunk
                # dispatched for this slot's PREVIOUS occupant must
                # neither have its tokens emitted to the new request
                # nor be chained from
                self._slot_gen[slot] += 1
                self._lengths[slot] = plen
            # any chunk dispatched from here on (seq >= _dispatch_seq)
            # executes after this prefill on the device stream
            self._pending_firsts.append(
                (self._dispatch_seq, part, firsts))
        self._admitting = []
        self._dev_dirty = True   # active set / lengths changed
        return len(admits), len(batches)

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

    def _admission_window(self) -> bool:
        """Continuous admission: between the previous chunk's sync and
        the NEXT chunk's dispatch, block on the waiting queue for up to
        a fraction of the EMA chunk period and prefill arrivals
        immediately. A prefill dispatched here queues behind only the
        ONE in-flight chunk — without the window, a request arriving
        just after an emit waits out the whole double-buffered pipeline
        (~2.5 chunks of queue_wait, the dominant TTFT term in
        BENCH_r07). The wait costs no device time: the in-flight chunk
        computes while this thread sleeps, and the remaining period
        fraction covers the next dispatch. Skipped until the loop has a
        period estimate, when no slot is free, or under page
        backpressure (a request the pool can't place would spin)."""
        if (not self._continuous_admission or self._chunk_period is None
                or self._sync_t is None):
            return False
        deadline = self._sync_t + self._window_frac * self._chunk_period
        admitted = False
        while not self._stop.is_set():
            if self._admission_blocked or \
                    not any(r is None for r in self._active):
                break
            timeout = deadline - time.monotonic()
            if timeout <= 0:
                break
            with _tracing.phase("engine.wait_arrivals", kind="serve",
                                attrs={"what": "window"}) as ph:
                try:
                    req = self._waiting.get(timeout=timeout)
                except queue.Empty:
                    req = None
                if ph:
                    ph.set(arrivals=int(req is not None))
            if req is None:
                break
            self._admit(first=req)
            admitted = True
        return admitted

    def _next_key(self):
        self._key, sub = jax.random.split(self._key)
        return sub

    def _emit(self, req: Request, tok: int):
        req.generated += 1
        self.total_generated += 1
        self._last_tok[req.slot] = tok
        # the cache-capacity cutoff counts prompt + emitted tokens — the
        # _lengths mirror is chunk-granular (pre-advanced at dispatch)
        # and would trip this up to two chunks early
        done = (req.eos_id is not None and tok == req.eos_id) or \
            req.generated >= req.max_new_tokens or \
            len(req.prompt) + req.generated >= self.max_len
        req.out.put(tok)
        if done:
            req.out.put(None)
            self._active[req.slot] = None
            self.total_finished += 1
            self._on_slot_retired(req.slot)
        else:
            # the emitted token occupies position lengths[slot] next step
            pass

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
        lag the device by up to two in-flight chunks."""
        if self._waiting.empty():
            return False
        if any(r is None for r in self._active) \
                and not self._admission_blocked:
            # a free slot AND admission actually possible (a page-starved
            # paged engine must not drain forever against a free slot it
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

    def _decode_call(self, chunk: int, last_tok, dev, ph):
        """Hook: run the compiled decode program for one chunk and
        return (token_matrix, advanced_lens, merged_last_tok) — the
        ONLY piece the paged engine overrides; the pipeline tail below
        stays shared. ``ph`` is the dispatch's span (the paged engine
        adds its pages bucket)."""
        decode = (self._decode_fn_drain if chunk == self._drain_chunk
                  and self._decode_fn_drain is not self._decode_fn
                  else self._decode_fn)
        self._cache, toks, lens, new_last = decode(
            self.params, self._cache, last_tok,
            dev["lens"], dev["active"], dev["temps"], self._next_key(),
        )
        return toks, lens, new_last

    def _chunk_facts(self, recording: bool) -> dict:
        """Hook: counts of the chunk being emitted that the decode
        program itself took, for its ``engine.emit`` span (the paged
        engine: the feed-forward's statistics of a routed block)."""
        return {}

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
            reupload = self._dev_inputs is None or self._dev_dirty
            dev = self._device_inputs(active_idx)
            toks, lens, new_last = self._decode_call(
                chunk, self._last_dev, dev, ph)
            now = time.monotonic()
            stream_seq = next(self._stream_seq)
            if ph:
                ph.set(seq=stream_seq, chunk=chunk, live=len(active_idx),
                       slots=self.max_batch, drain=drain,
                       reupload=reupload)
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
            gens = [int(self._slot_gen[i]) for i in active_idx]
            seq = self._dispatch_seq
            self._dispatch_seq += 1
        self._ready_q.put(("decode", stream_seq, toks, now, (), ph or None))
        return toks, active_idx, gens, chunk, seq

    def _emit_chunk(self, toks_np, active_idx, gens):
        for i, gen in zip(active_idx, gens):
            if self._slot_gen[i] != gen:
                continue   # slot re-admitted since dispatch: the chunk's
                # tokens belong to the RETIRED occupant, not this request
            for t in range(toks_np.shape[0]):
                req = self._active[i]
                if req is None:
                    break   # finished mid-chunk; drop surplus tokens
                self._emit(req, int(toks_np[t, i]))

    def _sync_chunk(self, toks, active_idx, gens, seq: int | None):
        """Chunk N's host sync, then its tokens to their streams. Firsts
        of prefills dispatched before the chunk (``seq``: before chunk
        ``seq``; None: drained by the caller already) go out ahead of
        it, so emission order per request is preserved."""
        with _tracing.phase("engine.wait_device", kind="serve",
                            attrs={"what": "chunk"}):
            toks_np = np.asarray(toks)
        now = time.monotonic()
        if seq is not None:
            self._drain_firsts(completed_seq=seq)
        with _tracing.phase("engine.emit", kind="serve") as ph:
            generated, finished = self.total_generated, self.total_finished
            self._emit_chunk(toks_np, active_idx, gens)
            facts = self._chunk_facts(bool(ph))
            if ph:
                ph.set(what="chunk",
                       tokens=self.total_generated - generated,
                       finished=self.total_finished - finished, **facts)
        return now

    def _wait_idle(self):
        """No live slot and nothing in flight: poll for arrivals every
        millisecond, as ONE span however long the wait (an idle engine
        must not fill the span ring)."""
        with _tracing.phase("engine.wait_arrivals", kind="serve",
                            attrs={"what": "idle"}) as ph:
            while True:
                self._on_idle()
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
        before the chunk's tokens are emitted.

        Each pass is one ``engine.iteration`` span while spans are
        recorded (``tracing.phase``), its phases its children: what the
        children leave uncovered is host work no phase names."""
        pending = None   # (device_toks, active_idx, gens, chunk, seq)
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
        """One pass of the loop; returns the chunk left in flight."""
        self._admit()
        active_idx = [i for i, r in enumerate(self._active)
                      if r is not None]
        if not active_idx:
            self._sync_t = None   # pipeline drains: period resets
            if pending is not None:
                toks, idxs, gens, _, seq = pending
                self._sync_chunk(toks, idxs, gens, seq)
            elif self._pending_firsts:
                # every active request is brand-new and nothing is
                # in flight (e.g. max_new_tokens=1 bursts): block
                # for the outstanding firsts
                self._drain_firsts(completed_seq=self._dispatch_seq)
            else:
                self._wait_idle()
            return None
        if pending is None:
            return self._dispatch_decode(active_idx)
        # continuous admission: requests arriving while `pending`
        # computes are prefilled NOW, before the next chunk is
        # dispatched behind them
        if self._admission_window():
            active_idx = [i for i, r in enumerate(self._active)
                          if r is not None]
        nxt = self._dispatch_decode(active_idx)
        toks_prev, idx_prev, gens_prev, _, _ = pending
        # EVERY pending prefill was dispatched before nxt: block for
        # their firsts now (bounded by chunk N + prefill compute —
        # chunk N+1 is already queued behind them, so this wait
        # steals no device time) and emit them FIRST. Waiting for
        # the next chunk's sync instead cost a whole extra chunk of
        # first-token latency.
        self._drain_firsts(completed_seq=self._dispatch_seq)
        sync_t = self._sync_t
        now = self._sync_chunk(toks_prev, idx_prev, gens_prev, None)
        if sync_t is not None:
            period = now - sync_t
            self._chunk_period = (
                period if self._chunk_period is None
                else 0.5 * self._chunk_period + 0.5 * period)
        self._sync_t = now
        return nxt

    # -- metrics -----------------------------------------------------------

    def stats(self) -> dict:
        live = sum(r is not None for r in self._active)
        out = {
            "active_slots": live,
            "waiting": self._waiting.qsize(),
            "total_generated": self.total_generated,
            "total_finished": self.total_finished,
            "mean_ttft_s": float(np.mean(self.ttfts)) if self.ttfts else None,
        }
        return out


class LLMDeployment:
    """Serve deployment body hosting an LLMEngine in the replica process.

    Use with ``@serve.deployment``/`serve.run`; each replica owns its own
    engine (and TPU chip(s)). `model_builder` is a picklable zero-arg
    callable returning (cfg, params) — keeps weights out of the deploy RPC.

        dep = serve.deployment(LLMDeployment).bind(model_builder=build)
        handle = serve.run(dep)
        tokens = handle.remote([1, 2, 3], max_new_tokens=16).result()
    """

    def __init__(self, model_builder, *, max_batch: int = 8,
                 max_len: int = 2048, kv_layout: str = "paged",
                 **engine_kwargs):
        cfg, params = model_builder()
        if kv_layout == "paged":
            from ray_tpu.serve.paged_llm import PagedLLMEngine

            self._engine = PagedLLMEngine(
                cfg, params, max_batch=max_batch, max_len=max_len,
                **engine_kwargs)
        elif kv_layout == "dense":
            self._engine = LLMEngine(cfg, params, max_batch=max_batch,
                                     max_len=max_len, **engine_kwargs)
        else:
            raise ValueError(f"unknown kv_layout {kv_layout!r}")
        self._engine.start()

    def __call__(self, prompt, max_new_tokens: int = 128,
                 temperature: float = 0.0, eos_id: int | None = None):
        req = self._engine.submit(
            prompt, max_new_tokens=max_new_tokens,
            temperature=temperature, eos_id=eos_id)
        return list(req.tokens())

    def stats(self) -> dict:
        return self._engine.stats()


