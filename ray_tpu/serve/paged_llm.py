"""Paged-KV continuous-batching LLM engine.

Reference: ABSENT from the reference repo (it serves models via user
code in replicas — SURVEY.md P15). This engine wires the vLLM-style
paged KV allocator (``ray_tpu/ops/paged_attention.py``) into the
continuous-batching loop of ``serve/llm.py``:

- The KV cache is a POOL of fixed-size pages [L, P, page, nkv, hd];
  each slot owns a page list. HBM scales with TOKENS IN FLIGHT
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
  (gather the window, ``_cached_attention``), chosen where the program
  is lowered; nothing sets it.
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
  and scans over (layer weights, layer index); a layer scatters its new
  rows at [layer, page, offset] (``_write_kv``) and reads its pages at
  [layer, table]: decode in the kernel, prefill by gathering its window
  (``gather_kv_window``). Scanning OVER the pools
  instead hands each layer a slice: XLA then copies every layer's K
  and V pool out and back, every layer of every step, and the prefill
  program holds a second pool (measured on a v5e at 12 layers x 544
  pages: 43% of the device's time, 5.9 GB of HBM).

- What is the MODEL's comes from the model's module, resolved from the
  config's class (``_model_module``): the attention projections
  (``attention_projections``: norm, q/k/v, whatever the block does to
  them, rotary), the feed-forward (``feed_forward``: a dense SwiGLU, or
  routed experts) and the output head (``lm_head_weights``). What is the
  ENGINE's stays here, once for every model: the page write, decode's
  attention over the pages (the kernel), prefill's gather and
  ``_cached_attention``, the layer scan, sampling, the chunk loop. A
  feed-forward may hand back statistics of its call (scalars; a dense
  one has none): the decode program averages them over the chunk's
  layer-steps, and they go on the chunk's ``engine.emit`` span.

Engine mechanics (queues, continuous batching, chunked + pipelined
decode, metrics) are inherited from ``LLMEngine``.
"""

from __future__ import annotations

from collections import deque
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models import llama
from ray_tpu.models.decoding import (_cached_attention,
                                     select_tokens)
from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.paged_attention import (PageAllocator, PrefixCache,
                                         page_hashes, quantize_kv)
from ray_tpu.ops.paged_decode_attention import (gather_kv_window,
                                                paged_decode_attention)
from ray_tpu.ops.rope import rope_sin_cos
from ray_tpu.serve.llm import LLMEngine, _bucket, _named_jit


def _model_module(cfg):
    """The module that states ``cfg``'s block, by the config's class (as
    ``JaxTrainer._resolve_family`` finds a trainer's)."""
    if isinstance(cfg, llama.LlamaConfig):
        return llama
    from ray_tpu.models import olmoe

    if isinstance(cfg, olmoe.OlmoeConfig):
        return olmoe
    raise TypeError(
        f"unsupported model config {type(cfg).__name__}; the paged engine "
        "serves LlamaConfig and OlmoeConfig")


def _write_kv(kp, vp, ks, vs, layer, k_new, v_new, pidx, ip, quantized):
    """THE KV write, shared by decode and prefill (shape-generic: decode
    writes one token per slot with [B] indices, prefill a padded suffix
    with [n, T] indices), on the STACKED pools [L, P, page, nkv, hd]
    (+ scale pools in int8 mode) at layer ``layer``: k/v land at
    (layer, pidx, ip), out-of-bounds indices dropping.

    The pools come in whole and go out whole: all that is written is
    the new rows (a scatter, in place on the buffer the layer loop
    carries). Write before any read of the layer's pages, so the reader
    sees the rows just written."""
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


class PagedLLMEngine(LLMEngine):
    """LLMEngine with a paged KV cache (see module docstring).

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
        if prefix_cache is None:
            prefix_cache = _cfg.serve_prefix_cache_enabled   # flag
        if kv_dtype not in ("bf16", "int8"):
            raise ValueError(f"kv_dtype must be 'bf16' or 'int8', "
                             f"got {kv_dtype!r}")
        self.kv_dtype = kv_dtype
        self.page_size = page_size
        self.max_pages_per_seq = -(-max_len // page_size)
        # default pool: half the dense equivalent — the paged layout's
        # raison d'être is NOT reserving worst-case length per slot —
        # floored so every slot can hold a minimal reservation (prompt
        # page + 1 overshoot page); without the floor, short-sequence
        # configs (max_pages_per_seq == 2) starve half of max_batch and
        # admission waits a full generation for pages, not slots
        if num_pages is not None:
            self.num_pages = num_pages
        else:
            half_dense = max_batch * self.max_pages_per_seq // 2
            floor = max_batch * min(2, self.max_pages_per_seq)
            self.num_pages = max(half_dense, floor)
        self._prefix_enabled = prefix_cache
        super().__init__(cfg, params, max_batch=max_batch,
                         max_len=max_len, decode_chunk=decode_chunk)
        # prefix-cache digest publishing (serve/prefix_router.py): the
        # engine periodically drops a compact digest — chained full-page
        # hashes + pool occupancy — into the process annex registry;
        # the metrics pusher piggybacks it to the GCS and handles route
        # repeat-prefix traffic to the replica already holding the pages
        self._digest_enabled = (self._prefix_enabled
                                and _cfg.serve_prefix_routing_enabled)
        self._digest_interval = float(_cfg.serve_digest_publish_interval_s)
        self._digest_t = 0.0

    # -- device state ------------------------------------------------------

    def _setup_device_state(self):
        cfg = self.cfg
        nkv = getattr(cfg, "n_kv_heads", None) or cfg.n_heads
        shape = (cfg.n_layers, self.num_pages, self.page_size, nkv,
                 cfg.head_dim)
        page_dtype = jnp.int8 if self.kv_dtype == "int8" else jnp.bfloat16
        self._k_pages = jnp.zeros(shape, page_dtype)
        self._v_pages = jnp.zeros(shape, page_dtype)
        # per-token-per-head dequant scales (int8 mode; tiny dummies in
        # bf16 mode so every program shares one signature/donation set)
        scale_shape = (shape[:-1] if self.kv_dtype == "int8"
                       else (cfg.n_layers, 1, 1, 1))
        self._k_scale = jnp.ones(scale_shape, jnp.float32)
        self._v_scale = jnp.ones(scale_shape, jnp.float32)
        self._table = np.full((self.max_batch, self.max_pages_per_seq),
                              -1, np.int32)
        self._alloc = PageAllocator(self.num_pages)
        # deferred page frees: (slot_pages, syncs_remaining) — a chunk
        # dispatched before the retirement was observed may still write
        # into the retired slot's own pages; they return to the free
        # list only after two chunk syncs have drained the pipeline
        self._deferred_free: list[list[int]] = []
        self._decode_cache: dict[tuple[int, int], object] = {}
        self._prefill_cache: dict[int, object] = {}
        # per dispatched decode chunk, the feed-forward's statistics on
        # the device until the chunk is emitted (_chunk_facts)
        self._chunk_stats: deque = deque()
        # prefix cache state: shared (read-only, refcounted) pages per
        # slot, the slot's cached-prefix token count, and the full-page
        # hash chain awaiting registration after its prefill dispatch
        self._prefix = PrefixCache()
        self._shared: dict[int, list[int]] = {}
        self._prefix_len = np.zeros((self.max_batch,), np.int32)
        self._pending_hashes: dict[int, list[bytes]] = {}

    def _decode_paged(self, chunk: int, pages_bucket: int):
        key = (chunk, pages_bucket)
        fn = self._decode_cache.get(key)
        if fn is None:
            fn = _named_jit(
                f"paged_decode_c{chunk}_w{pages_bucket}",
                partial(self._paged_decode_impl, self.cfg, chunk=chunk,
                        page_size=self.page_size,
                        quantized=self.kv_dtype == "int8"),
                donate_argnums=(1, 2, 3, 4))
            self._decode_cache[key] = fn
        return fn

    def _prefill_paged(self, window_pages: int):
        """Prefill program gathering a ``window_pages``-page KV window —
        bucketed like decode so a short-prompt batch reads a fraction of
        the full window's KV bytes (the window must cover every row's
        start + suffix)."""
        fn = self._prefill_cache.get(window_pages)
        if fn is None:
            fn = _named_jit(
                f"paged_prefill_w{window_pages}",
                partial(self._paged_prefill_impl, self.cfg,
                        page_size=self.page_size,
                        quantized=self.kv_dtype == "int8"),
                donate_argnums=(1, 2, 3, 4))
            self._prefill_cache[window_pages] = fn
        return fn

    def _window_pages(self, max_covered: int) -> int:
        """Power-of-two page count covering ``max_covered`` tokens,
        clamped to the table width."""
        need = max(1, -(-max_covered // self.page_size))
        return min(_bucket(need, minimum=1), self.max_pages_per_seq)

    # -- jitted programs ---------------------------------------------------

    @staticmethod
    def _paged_decode_impl(cfg, params, k_pages, v_pages, k_scale,
                           v_scale, table, tokens, lengths, active,
                           temps, key, *, chunk, page_size, quantized):
        """``chunk`` decode steps over every slot; KV rows written, then
        attended over where they lie, through the (bucketed) page table
        [B, PB]. In int8 mode (``quantized``) writes quantize per
        token+head and the kernel dequantizes against the scale pages —
        half the KV bytes per step. Two nested scans: over steps,
        carrying the pools, last tokens, lengths and key; inside it
        over layers, carrying the activations and the same stacked
        pools (module docstring: in place), scanning over the layers'
        weights and indices."""
        model = _model_module(cfg)
        num_pages = k_pages.shape[1]
        b = table.shape[0]
        layers = jnp.arange(k_pages.shape[0])

        def one_step(carry, _):
            k_pages, v_pages, k_scale, v_scale, toks, lens, key = carry
            key, sub = jax.random.split(key)
            pos = jnp.where(active, lens, 0)                    # [B]
            x = params["embedding"][toks[:, None]]              # [B,1,d]
            sin, cos = rope_sin_cos(pos[:, None], cfg.head_dim,
                                    theta=cfg.rope_theta)
            # per-slot write target for this token
            pidx = jnp.take_along_axis(
                table, (pos // page_size)[:, None], axis=1)[:, 0]
            # holes (beyond reserved pages) drop; inactive slots drop too
            pidx = jnp.where((pidx >= 0) & active, pidx, num_pages)
            ip = pos % page_size

            def block(carry, xs):
                x, kp, vp, ks, vs = carry
                p, layer = xs
                q, k, v = model.attention_projections(cfg, p, x, sin, cos)
                kp, vp, ks, vs = _write_kv(
                    kp, vp, ks, vs, layer, k[:, 0], v[:, 0], pidx, ip,
                    quantized)
                # each live slot's pages up to its length, read where
                # they lie; the row just written is among them
                attn = paged_decode_attention(
                    q[:, 0], kp, vp, ks, vs, layer, table, pos, active)
                x = x + attn.reshape(b, 1, -1) @ p["wo"]
                x, stats = model.feed_forward(cfg, p, x,
                                              valid=active[:, None])
                return (x, kp, vp, ks, vs), stats

            (x, k_pages, v_pages, k_scale, v_scale), stats = jax.lax.scan(
                block, (x, k_pages, v_pages, k_scale, v_scale),
                (params["blocks"], layers))
            x = rms_norm(x, params["final_norm"], eps=cfg.rms_eps)[:, 0]
            head = model.lm_head_weights(cfg, params)
            logits = jnp.einsum("bd,dv->bv", x, head,
                                preferred_element_type=jnp.float32)
            nxt = select_tokens(logits, temps, sub)
            lens = jnp.where(active, lens + 1, lens)
            return (k_pages, v_pages, k_scale, v_scale, nxt, lens,
                    key), (nxt, stats)

        (k_pages, v_pages, k_scale, v_scale, _, lens, _), (toks, stats) = \
            jax.lax.scan(
                one_step,
                (k_pages, v_pages, k_scale, v_scale, tokens, lengths,
                 key), None, length=chunk)
        # merged device-resident last-token vector (see llm._decode_impl)
        new_last = jnp.where(active, toks[-1], tokens)
        # the feed-forward's statistics [chunk, layers], as the chunk's
        # means (nothing, for a block that hands back none)
        stats = jax.tree.map(jnp.mean, stats)
        return (k_pages, v_pages, k_scale, v_scale, toks, lens, new_last,
                stats)

    @staticmethod
    def _paged_prefill_impl(cfg, params, k_pages, v_pages, k_scale,
                            v_scale, table_rows, tokens, slens, starts,
                            temps, key, *, page_size, quantized):
        """Prefill ``n`` prompt SUFFIXES (one padded bucket) into pages
        and sample each row's first token. ``tokens`` holds only the
        tokens past each row's cached prefix (``starts`` absolute
        offsets; 0 = no prefix reuse, the plain prefill). Suffix KV is
        written into the pages first, then attention runs over the
        row's whole gathered page window, so suffix queries see the
        reused prefix KV exactly as the original prompt computed it.
        table_rows: [n, max_pages_per_seq]. The layer scan carries the
        activations and the stacked pools, as decode's does: the
        program holds one pool, the donated one."""
        model = _model_module(cfg)
        num_pages = k_pages.shape[1]
        n, t = tokens.shape
        mp = table_rows.shape[1]
        s = mp * page_size
        scale = cfg.head_dim ** -0.5
        x = params["embedding"][tokens]
        rel = jnp.arange(t, dtype=jnp.int32)
        positions = starts[:, None] + rel[None, :]            # [n, T]
        sin, cos = rope_sin_cos(positions, cfg.head_dim,
                                theta=cfg.rope_theta)
        pidx_all = jnp.take_along_axis(
            table_rows, positions // page_size, axis=1)       # [n, T]
        valid = rel[None, :] < slens[:, None]                 # [n, T]
        pidx_all = jnp.where((pidx_all >= 0) & valid, pidx_all,
                             num_pages)
        ip_all = positions % page_size

        def block(carry, xs):
            x, kp, vp, ks, vs = carry
            p, layer = xs
            q, k, v = model.attention_projections(cfg, p, x, sin, cos)
            kp, vp, ks, vs = _write_kv(
                kp, vp, ks, vs, layer, k, v, pidx_all, ip_all, quantized)
            kg, vg = gather_kv_window(kp, vp, ks, vs, layer, table_rows)
            # gather the whole window AFTER the suffix writes: queries
            # attend over cached prefix + their own fresh KV; positions
            # beyond start+i are masked causally, stale page contents
            # beyond the prompt never influence the result
            kg = kg.reshape(n, s, cfg.n_kv_heads, cfg.head_dim)
            vg = vg.reshape(n, s, cfg.n_kv_heads, cfg.head_dim)
            attn = _cached_attention(q, kg, vg, starts, scale=scale)
            x = x + attn.reshape(n, t, -1) @ p["wo"]
            x, _ = model.feed_forward(cfg, p, x, valid=valid)
            return (x, kp, vp, ks, vs), None

        (x, k_pages, v_pages, k_scale, v_scale), _ = jax.lax.scan(
            block, (x, k_pages, v_pages, k_scale, v_scale),
            (params["blocks"], jnp.arange(k_pages.shape[0])))
        x = rms_norm(x, params["final_norm"], eps=cfg.rms_eps)
        x = jnp.take_along_axis(
            x, (slens - 1)[:, None, None], axis=1).squeeze(1)
        head = model.lm_head_weights(cfg, params)
        logits = jnp.einsum("bd,dv->bv", x, head,
                            preferred_element_type=jnp.float32)
        first = select_tokens(logits, temps, key)
        return k_pages, v_pages, k_scale, v_scale, first

    # -- engine integration ------------------------------------------------

    def _pages_bucket(self) -> int:
        """Power-of-two page count covering every live slot's RESERVED
        pages — exclusive AND shared-prefix (chained chunks may run
        ahead of the host's view of lengths, but never past the
        reservation)."""
        owned = [len(self._alloc.owned.get(i, ()))
                 + len(self._shared.get(i, ()))
                 for i, r in enumerate(self._active) if r is not None]
        need = max(owned) if owned else 1
        pb = 1
        while pb < need:
            pb *= 2
        return min(pb, self.max_pages_per_seq)

    def _decode_call(self, chunk: int, last_tok, dev, ph):
        pb = self._pages_bucket()
        ph.set(pages=pb)
        fn = self._decode_paged(chunk, pb)
        key = ("table", pb)
        if key not in dev:
            # sliced page table uploads only on admission/retirement
            # (the _device_inputs rebuild drops stale entries). The
            # explicit host COPY matters: jnp.asarray may transfer
            # asynchronously from the numpy buffer, and a retirement
            # writing table[slot] = -1 mid-transfer would hand the
            # in-flight chunk a torn table
            dev[key] = jnp.asarray(self._table[:, :pb].copy())
        (self._k_pages, self._v_pages, self._k_scale, self._v_scale,
         toks, lens, new_last, stats) = fn(
            self.params, self._k_pages, self._v_pages, self._k_scale,
            self._v_scale, dev[key], last_tok, dev["lens"],
            dev["active"], dev["temps"], self._next_key(),
        )
        self._chunk_stats.append(stats)
        return toks, lens, new_last

    def _chunk_facts(self, recording: bool) -> dict:
        """The feed-forward's statistics of the chunk being emitted
        (chunks are emitted in the order they were dispatched). They came
        out of the program whose tokens the loop has just read, so reading
        them waits for nothing."""
        stats = self._chunk_stats.popleft() if self._chunk_stats else {}
        return ({name: float(v) for name, v in stats.items()}
                if recording else {})

    def _reserve_slot_resources(self, req, slot: int) -> bool:
        """Reserve-on-admit: pages for prompt + token budget + one page
        of chained-dispatch overshoot; exhaustion = backpressure (the
        base _admit requeues the request until pages free up).

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
            # can NEVER fit, even with the pool empty: reject now (the
            # base _admit turns req.error into a terminated stream)
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

    def _pack_admit(self, req, slot: int, plen: int) -> tuple:
        """Pack only the SUFFIX past the slot's cached prefix — a
        shared-prefix request prefills (and buckets) just its tail."""
        start = int(self._prefix_len[slot])
        suffix = np.asarray(req.prompt, np.int32)[start:]
        bucket = min(_bucket(len(suffix)), self.max_len)
        padded = np.zeros((bucket,), np.int32)
        padded[:len(suffix)] = suffix
        return (req, slot, plen, padded)

    def _dispatch_prefill(self, part: list, bucket: int, ph):
        tokens = jnp.asarray(np.stack([it[3] for it in part]))
        starts_np = np.array([self._prefix_len[it[1]] for it in part],
                             np.int32)
        slens_np = np.array([it[2] for it in part], np.int32) - starts_np
        wp = self._window_pages(int((starts_np + slens_np).max()))
        if ph:
            # what the prefix cache gave this dispatch, counted as its
            # lookups were (PrefixCache.acquire): the full pages before
            # a prompt's last token, those reused and those missed
            page, cached = self.page_size, int(starts_np.sum())
            lookups = (sum((it[2] - 1) // page for it in part)
                       if self._prefix_enabled else 0)
            ph.set(window_pages=wp, new_tokens=int(slens_np.sum()),
                   cached_tokens=cached,
                   missed_pages=lookups - cached // page)
        prefill = self._prefill_paged(wp)
        slens = jnp.asarray(slens_np)
        rows = jnp.asarray(np.stack(
            [self._table[it[1]][:wp] for it in part]))
        temps = jnp.asarray(np.array(
            [it[0].temperature for it in part], np.float32))
        (self._k_pages, self._v_pages, self._k_scale, self._v_scale,
         firsts) = prefill(
            self.params, self._k_pages, self._v_pages, self._k_scale,
            self._v_scale, rows, tokens, slens, jnp.asarray(starts_np),
            temps, self._next_key())
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

    def _publish_digest(self, force: bool = False):
        """Drop this replica's prefix-cache digest into the process
        annex registry (throttled; the pusher ships it). Engine-thread
        only — ``_by_hash`` has a single mutator."""
        if not self._digest_enabled:
            return
        import time as _time
        now = _time.monotonic()
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

    def _on_slot_retired(self, slot: int):
        super()._on_slot_retired(slot)   # marks device inputs dirty
        # a chunk dispatched before this retirement was observed may
        # still write into the slot's own (reserved) pages: defer the
        # free by two chunk syncs. Shared prefix pages are released
        # immediately — nothing ever WRITES them (suffix and decode
        # positions lie past the prefix), and a stale in-flight read of
        # a page later evicted + rewritten only feeds tokens the
        # retired slot already discards.
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

    def _emit_chunk(self, toks_np, active_idx, gens):
        super()._emit_chunk(toks_np, active_idx, gens)
        # one chunk sync elapsed: age the deferred frees
        self._age_deferred_frees()
        self._publish_digest()

    def _on_idle(self):
        # no active slots and nothing in flight: every dispatched chunk
        # has synced, so deferred frees cannot race anything — release
        # them all (otherwise pages retired on the last emit before an
        # idle period would strand and deadlock page backpressure)
        if self._deferred_free:
            self._age_deferred_frees(drain_all=True)

    def warmup_prefix(self, prefix_len: int, tail_len: int,
                      max_n: int | None = None):
        """Compile the SUFFIX prefill variants that prefix-cache hits
        dispatch (tail bucket + the window covering prefix+tail), so a
        deployment with a known system-prompt shape doesn't pay XLA
        compilation inside the first shared-prefix request's TTFT.
        ``warmup`` alone only covers the cold (starts=0) path."""
        bucket = min(_bucket(tail_len), self.max_len)
        wp = self._window_pages(prefix_len + bucket)
        prefill = self._prefill_paged(wp)
        n = 1
        top = max_n if max_n is not None else self.max_batch
        while n <= top:
            rows = jnp.full((n, wp), -1, jnp.int32)
            (self._k_pages, self._v_pages, self._k_scale,
             self._v_scale, firsts) = prefill(
                self.params, self._k_pages, self._v_pages,
                self._k_scale, self._v_scale, rows,
                jnp.zeros((n, bucket), jnp.int32),
                jnp.ones((n,), jnp.int32),
                jnp.full((n,), prefix_len, jnp.int32),
                jnp.zeros((n,), jnp.float32), self._next_key())
            np.asarray(firsts)
            n *= 2

    def warmup(self, prompt_len: int):
        """Compile the prefill program (each power-of-two group size at
        this bucket) and the decode programs at every pages-bucket a
        run can touch. For shared-prefix workloads also call
        ``warmup_prefix`` with the expected (prefix, tail) shape."""
        bucket = min(_bucket(prompt_len), self.max_len)
        wp = self._window_pages(bucket)
        prefill = self._prefill_paged(wp)
        if self._last_dev is None:
            self._last_dev = jnp.asarray(self._last_tok)
        n = 1
        while n <= self.max_batch:
            rows = jnp.full((n, wp), -1, jnp.int32)
            (self._k_pages, self._v_pages, self._k_scale,
             self._v_scale, firsts) = prefill(
                self.params, self._k_pages, self._v_pages,
                self._k_scale, self._v_scale, rows,
                jnp.zeros((n, bucket), jnp.int32),
                jnp.ones((n,), jnp.int32),
                jnp.zeros((n,), jnp.int32),
                jnp.zeros((n,), jnp.float32), self._next_key())
            # warm the firsts scatter at this group size (it
            # specializes per slots-shape; compiling inside _admit
            # stalls the loop ~0.5s — measured)
            self._last_dev = self._scatter_fn(
                self._last_dev, jnp.arange(n, dtype=jnp.int32), firsts)
            np.asarray(firsts)
            n *= 2
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
                (self._k_pages, self._v_pages, self._k_scale,
                 self._v_scale, toks, _, _, _) = fn(
                    self.params, self._k_pages, self._v_pages,
                    self._k_scale, self._v_scale,
                    jnp.full((self.max_batch, pb), -1, jnp.int32),
                    jnp.zeros((self.max_batch,), jnp.int32),
                    jnp.zeros((self.max_batch,), jnp.int32), active,
                    jnp.zeros((self.max_batch,), jnp.float32),
                    self._next_key())
                np.asarray(toks)
        self._lengths[:] = 0
        self._last_tok[:] = 0

    def stats(self) -> dict:
        out = super().stats()
        out["kv_pages_total"] = self.num_pages
        out["kv_pages_free"] = len(self._alloc.free)
        # feed the metrics plane: pool occupancy + prefix-cache hit
        # counters ride the process's next pushed delta frame
        from ray_tpu.util import metrics as _m
        if _m.enabled():
            g = _m.gauge("ray_tpu_serve_kv_pages",
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
        scale_bytes = (self._k_scale.size * 4 * 2
                       if self.kv_dtype == "int8" else 0)
        out["kv_pages_bytes"] = int(
            self._k_pages.size * self._k_pages.dtype.itemsize * 2
            + scale_bytes)   # K+V pages (+ dequant scales in int8 mode)
        dense = (self.cfg.n_layers * self.max_batch * self.max_len
                 * self._k_pages.shape[3] * self._k_pages.shape[4]
                 * 2 * 2)
        out["kv_dense_equiv_bytes"] = int(dense)
        return out
