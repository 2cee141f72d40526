"""What the two serving runners share: set-up of the engine (weights,
the cell's programs warmed, the reference check) and the client side of a
run (one thread that submits what is due and reads every token as it
arrives, stamping it on the benchmark's clock)."""

from __future__ import annotations

import gc
import hashlib
import json
import queue
import threading
import time
import weakref

import numpy as np

from benchmark import reference, systems
from benchmark.harness import say, span

# The engine's greedy tokens, teacher-forced through the float32 reference:
# no token's reference logit may fall short of the reference's best by more
# than this. The engine hands out tokens, not logits, so this is the
# comparison a run can make through its public path. With unit-variance
# logits over a 32k vocabulary the best two lie about 0.2 apart, and the
# bf16 engine itself now and then picks the second: over 64 checked tokens
# (two prompts, 32 each) it missed by 0.0289 in one of two chip runs and
# by nothing in the other (PERF.md, PR 23). The tolerance is 3.5 times
# that. It catches a wrong page, position, mask or prefix reuse, whose
# tokens miss by far more; it does NOT tell a lower precision apart (an
# int8 KV cache read 0.0190, three tokens of 64 not the reference's):
# that needs logits out of the engine (PERF.md, open questions).
TOKEN_GAP_TOL = 0.1
POLL_S = 0.002


def _ceil_pow2(n: int, minimum: int = 1) -> int:
    b = minimum
    while b < n:
        b *= 2
    return b


def warm_cells(prompts_and_budgets, system: dict, limits: dict):
    """The cells of the benchmark's warm-up grid that a schedule can
    reach, from its prompts alone: (prefill cells, decode cells).

    The engine compiles one program per shape of work, by rules of its
    own that the benchmark does not read. The grid is the benchmark's:
    a prefill is placed by its group size n (a power of two up to
    ``limits['max_group']``), its new tokens T (the suffix past the cached
    prefix, rounded up to a power of two from 16) and its context in pages
    wp (rounded up to a power of two), and kept while n x T x wp x page <=
    ``limits['max_score_elements']`` (past that the engine's program does
    not fit the chip: PERF.md, PR 23); a decode by the pages the longest
    live request has reserved, rounded up to a power of two. One request
    at the top of every cell is run during set-up (``warm_programs``), so
    an engine whose programs are cut as coarsely as this grid or more so
    compiles nothing in the window; one that cuts them finer shows in
    ``compiles_in_window``.

    The cached prefix of a prompt is whatever leading full pages another
    prompt of the schedule shares with it (the prefix cache is addressed
    by the content of full pages, chained): none; all of them; or up to a
    point where the sharing drops (an earlier turn's end, a system
    prompt's end), since what was registered together is evicted
    together."""
    page, max_len = system["page_size"], system["max_len"]
    max_pages = -(-max_len // page)
    seen: dict = {}
    chains = []
    for prompt, _ in prompts_and_budgets:
        h, chain = b"", []
        for i in range(len(prompt) // page):
            h = hashlib.blake2b(
                h + np.asarray(prompt[i * page:(i + 1) * page],
                               np.int32).tobytes(), digest_size=8).digest()
            chain.append(h)
            seen[h] = seen.get(h, 0) + 1
        chains.append(chain)
    prefill, decode = set(), set()
    for (prompt, budget), chain in zip(prompts_and_budgets, chains):
        plen = len(prompt)
        chain = chain[:(plen - 1) // page]      # one new token at least
        shared = 0
        while shared < len(chain) and seen[chain[shared]] >= 2:
            shared += 1
        hits = {0, shared} | {i for i in range(1, shared)
                              if seen[chain[i]] < seen[chain[i - 1]]}
        wp = min(_ceil_pow2(-(-plen // page)), max_pages)
        for h in hits:
            t = min(_ceil_pow2(plen - h * page, 16), max_len)
            n = 1
            while (n <= limits["max_group"] and n * t * wp * page
                   <= limits["max_score_elements"]):
                prefill.add((n, t, wp))
                n *= 2
        pages = -(-min(plen + budget, max_len) // page) + 1
        decode.add(min(_ceil_pow2(pages), max_pages))
    return prefill, decode


def _cell_top(t: int, wp: int, page: int, max_len: int) -> tuple:
    """(cached pages, new tokens) of the largest request in a prefill
    cell: T new tokens (one fewer where the prompt would reach max_len,
    which the engine refuses) behind as many cached pages as the context
    of wp pages leaves."""
    cached = wp - max(1, -(-t // page))
    new = t
    if cached * page + new >= max_len:
        new = max_len - 1 - cached * page
    return cached, new


def warm_programs(eng, prefill_cells, decode_cells, system: dict,
                  vocab: int, seed: int) -> dict:
    """Run the largest request of every cell once through the engine's
    public path (``submit``), so that every program the window can need
    is compiled, or read from the compile cache, during set-up.

    Decode cells first, one at a time and to the end, smallest first (the
    decode program follows the longest reservation alive): a fresh prompt
    whose reservation is the cell's top, and one chunk's worth of tokens
    and one more. Then a base prompt of max_len - 1 tokens, whose pages
    the prefix cache keeps. Then the prefill cells: the base's first
    pages, as many as the cell's top has cached, and fresh tokens behind
    them, one token generated. A cell of group size n is n such prompts
    handed over together; the engine dispatches what waits together as
    one group, and ``dispatch_t`` tells whether it did (if not, again)."""
    page, max_len = system["page_size"], system["max_len"]
    rng = np.random.default_rng([int(seed), 11])

    def fresh(n):
        return rng.integers(1, vocab, n, dtype=np.int32)

    hits0 = systems.engine_counters(eng)["prefix_hit_pages"]
    for pb in sorted(decode_cells):
        new = 17
        plen = max(1, min((pb - 1) * page, max_len) - new)
        collect(eng, eng.submit(fresh(plen), max_new_tokens=new))
    base = fresh(max_len - 1)
    collect(eng, eng.submit(base, max_new_tokens=1))
    handles, want_hits, regrouped, split = [], 0, 0, 0
    for n, t, wp in sorted(prefill_cells):
        cached, new = _cell_top(t, wp, page, max_len)
        if cached < 0 or new < 1:
            continue                    # no request can lie in this cell
        for attempt in range(6):
            prompts = [np.concatenate([base[:cached * page], fresh(new)])
                       for _ in range(n)]      # made first: the hand-over
            group = [eng.submit(p, max_new_tokens=1)    # is then quick
                     for p in prompts]
            handles.extend(group)
            want_hits += n * cached
            _wait_dispatched(eng, group)
            if len({h.dispatch_t for h in group}) == 1:
                break
            regrouped += 1
        else:
            split += 1                  # never went out as one group
    for h in handles:
        collect(eng, h)
    hits = systems.engine_counters(eng)["prefix_hit_pages"] - hits0
    return {"requests": len(handles) + len(decode_cells) + 1,
            "regrouped": regrouped, "never_grouped": split,
            "cached_pages_hit": hits,
            "cached_pages_wanted": want_hits}


def _wait_dispatched(eng, handles, timeout_s: float = 600.0):
    deadline = time.perf_counter() + timeout_s
    while any(h.dispatch_t is None and h.error is None for h in handles):
        if eng.error is not None:
            raise RuntimeError("engine loop failed") from eng.error
        if time.perf_counter() > deadline:
            raise TimeoutError("a warm-up request was never dispatched")
        time.sleep(POLL_S)


def prepare_engine(ctx, prompts_and_budgets, limits: dict):
    """Engine with weights from the seed, its loop started, every program
    the schedule can reach run once through the public path, and the
    reference check made the same way. Returns (engine, facts).

    Where the warm-up COMPILED a program (the first run in a checkout),
    the engine is thrown away and a second one warmed, which only reads
    the compile cache: a compilation stalls the engine's loop for seconds,
    the loop's running estimate of its chunk period takes that for a long
    chunk, and its admission window, three quarters of the estimate, then
    holds every dispatch back for about a minute more (the estimate halves
    its error per chunk, and a chunk lasts as long as the window). A first
    run would otherwise measure a slower engine than every later run."""
    config, system = ctx.config, ctx.config["system"]
    import ray_tpu.serve.paged_llm  # noqa: F401 - timed apart from its use
    ctx.phases.mark("program import")
    params = systems.make_params(config, ctx.args.seed)
    ctx.phases.mark("weights")

    rng = np.random.default_rng([int(ctx.args.seed), 9])
    vocab = config["vocab_size"]
    check = system["reference_check"]   # sized to the engine, so data
    n, shared, new = (check["prompt_tokens"], check["shared_tokens"],
                      check["new_tokens"])
    first = rng.integers(1, vocab, n, dtype=np.int32)
    second = np.concatenate([first[:shared], rng.integers(
        1, vocab, n - shared, dtype=np.int32)])
    samples = [(first, new), (second, new)]
    prefill, decode = warm_cells(
        list(prompts_and_budgets) + samples, system, limits)
    for again in (False, True):
        eng = start_engine(config, params)
        missed = ctx.compiles.cache_misses
        warmed = warm_programs(eng, prefill, decode, system, vocab,
                               ctx.args.seed)
        missed = ctx.compiles.cache_misses - missed
        ctx.phases.mark("programs warm" + (" again" if again else ""))
        say("programs", prefill_cells=len(prefill),
            decode_cells=sorted(decode), compiled=missed, **warmed)
        if not missed or again:
            break
        # its page pool has to go before another is made: two pools do
        # not fit beside the weights
        error = stop_engine(eng)
        if error is not None:
            raise RuntimeError("engine loop failed") from error
        gone, eng = weakref.ref(eng), None
        say("engine discarded", gone=wait_gone(gone),
            live_bytes=systems.live_bytes())

    logits = systems.family(config).logits      # the family's reference
    gaps, flips = [], 0
    for prompt, new in samples:     # the second reuses the first's pages
        req = eng.submit(prompt, max_new_tokens=new)
        tokens = collect(eng, req)
        ok = len(tokens) == new and all(0 <= t < vocab for t in tokens)
        gap, missed = (reference.token_gap(logits, config, eng.params,
                                           prompt, tokens)
                       if ok else (float("inf"), new))
        gaps.append(gap)
        flips += missed
    hits = systems.engine_counters(eng)["prefix_hit_pages"]
    ctx.phases.mark("reference check")

    # what the compiler says the largest program compiled so far needs
    # beside nothing else (the largest prefill program holds the page
    # pool once: the donated argument, which its result aliases), against
    # the live arrays
    temp, top = systems.largest_program()
    live = systems.live_bytes()
    facts = {"token_gap": max(gaps), "tol": TOKEN_GAP_TOL,
             "tokens_checked": sum(n for _, n in samples),
             "tokens_not_the_references": flips,
             "reference_prefix_hit_pages": hits,
             "live_bytes": live, "temp_bytes": temp, "top_program": top,
             "peak_bytes": max(live, temp)}
    say("reference", **facts)
    return eng, facts


class Client:
    """The client side: submits requests and reads their tokens, all on
    the calling thread. ``now`` is the benchmark's clock."""

    def __init__(self, eng, vocab: int):
        self.eng, self.vocab = eng, vocab
        self.live: list = []
        self.all: list = []
        self.occupancy: list = []      # (time, active slots, live kv tokens)

    def submit(self, req, now: float):
        with span("submit"):
            req.submit_t = now
            req.handle = self.eng.submit(req.prompt,
                                         max_new_tokens=req.max_new_tokens)
        self.live.append(req)
        self.all.append(req)

    def read(self) -> bool:
        """Take every token that has arrived; True if any did."""
        got, still = False, []
        now = time.perf_counter()
        for req in self.live:
            out = req.handle.out
            while True:
                try:
                    tok = out.get_nowait()
                except queue.Empty:
                    break
                got = True
                if tok is None:
                    req.done = True
                    req.failed = (req.handle.error is not None
                                  or self.eng.error is not None
                                  or len(req.tokens) != req.max_new_tokens)
                    break
                req.tokens.append(tok)
                req.token_times.append(now)
            if not req.done:
                still.append(req)
        self.live = still
        if got:
            c = systems.engine_counters(self.eng)
            kv = sum(len(r.prompt) + len(r.tokens) for r in self.live
                     if r.tokens)
            self.occupancy.append((now, c["active_slots"], kv))
        return got

    def valid_tokens(self, reqs) -> bool:
        return all(0 <= t < self.vocab for r in reqs for t in r.tokens)


def settle_collector():
    """What a serving front end does once it has warmed up: collect what
    the set-up left behind, then move everything that is still alive out
    of the collector's sight (``gc.freeze``), so that no full collection
    inside the window walks the set-up's objects (the traced programs, the
    schedule, the engine's tables) while every other thread waits for the
    lock: one took 0.11 s on the engine's loop (PERF.md, PR 49). Only
    once the engine the window uses is the only one: a discarded engine's
    cycles have to be collected for its page pool to go."""
    gc.collect()
    gc.freeze()


class StallWatch:
    """What the run's own process can say of a stall in its window, on
    the clock ``benchmark/tools/stall_sampler.py`` samples on: where the
    runner's loop took ``LEAST_S`` longer over its work or its sleep than
    it meant to, and every collection of the cyclic collector that took
    ``GC_LEAST_S`` or more, with its generation and the thread it ran on.
    Printed as one ``bench stalls`` line; no metric reads it."""

    LEAST_S, GC_LEAST_S = 0.1, 0.01

    def __init__(self):
        self.holdups: list = []         # [time, seconds, "work" | "sleep"]
        self.collections: list = []     # [time, seconds, generation, tid]
        self.gc0 = [g["collections"] for g in gc.get_stats()]
        self._t = None
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase, info):
        now = time.perf_counter()
        if phase == "start":
            self._t = now
        elif self._t is not None and now - self._t >= self.GC_LEAST_S:
            self.collections.append(
                [self._t, now - self._t, info["generation"],
                 threading.get_native_id()])

    def took(self, start: float, meant: float, what: str) -> float:
        """Note a step of the loop that began at ``start`` and was meant
        to take ``meant`` seconds; returns the time now."""
        now = time.perf_counter()
        if now - start - meant >= self.LEAST_S:
            self.holdups.append([start, now - start - meant, what])
        return now

    def report(self, t0: float, t1: float):
        gc.callbacks.remove(self._on_gc)
        counts = [g["collections"] - a
                  for g, a in zip(gc.get_stats(), self.gc0)]
        print("bench stalls: " + json.dumps({
            "window": [t0, t1], "holdups": self.holdups[:50],
            "collections": self.collections[:50], "gc_counts": counts,
            "threads": {t.native_id: t.name
                        for t in threading.enumerate()}}), flush=True)


def collect(eng, handle, timeout_s: float = 120.0) -> list:
    """Every token of one request, waiting with a deadline and an eye on
    the engine: a request its loop had taken when a prefill failed gets no
    end-of-stream, so a plain blocking read would hang for good."""
    tokens, deadline = [], time.perf_counter() + timeout_s
    while True:
        try:
            tok = handle.out.get(timeout=0.05)
        except queue.Empty:
            if eng.error is not None:
                raise RuntimeError("engine loop failed") from eng.error
            if time.perf_counter() > deadline:
                raise TimeoutError("no token from the engine in "
                                   f"{timeout_s:.0f} s")
            continue
        if tok is None:
            if handle.error is not None:
                raise handle.error
            return tokens
        tokens.append(tok)


def fill_prefix_cache(eng, prompts, max_waiting: int):
    """Put prompts into the prefix cache before the schedule starts (one
    generated token each), never more than ``max_waiting`` handed over
    and not yet dispatched: the engine takes everything waiting into one
    prefill program, and a program for a dozen prompts at once is one the
    window never runs (and may not fit the chip)."""
    pending, handles, todo = [], [], list(prompts)
    while todo or pending:
        if eng.error is not None:
            raise RuntimeError("engine loop failed") from eng.error
        pending = [h for h in pending if h.dispatch_t is None]
        while todo and len(pending) < max_waiting:
            h = eng.submit(todo.pop(0), max_new_tokens=1)
            pending.append(h)
            handles.append(h)
        time.sleep(POLL_S)
    for h in handles:
        collect(eng, h)


def start_engine(config: dict, params):
    eng = systems.make_engine(config, params)
    eng.start()
    return eng


def wait_gone(gone) -> bool:
    """Wait until a stopped engine, of which the caller now holds only the
    weak reference ``gone``, is really gone, and its page pool with it:
    its helper threads let go of it a moment after they are told to stop,
    and two pools do not fit beside the weights."""
    until = time.perf_counter() + 30.0
    while gone() is not None and time.perf_counter() < until:
        gc.collect()
        time.sleep(0.05)
    return gone() is None


def stop_engine(eng):
    eng.stop()
    if eng._thread is not None:
        eng._thread.join()
    return eng.error


def window_counters(client, c0: dict, c1: dict, t0: float, t1: float,
                    system: dict) -> dict:
    occ = [(a, kv) for t, a, kv in client.occupancy if t0 <= t < t1]
    page, max_batch = system["page_size"], system["max_batch"]
    return {
        "prefix_hit_pages": c1["prefix_hit_pages"] - c0["prefix_hit_pages"],
        # the full prompt pages the window's requests could have reused
        "prefix_lookup_pages": sum(
            (len(r.prompt) - 1) // page for r in client.all
            if t0 <= r.submit_t < t1),
        "occupancy_samples": [a for a, _ in occ],
        "live_kv_tokens_mean": (sum(kv for _, kv in occ) / len(occ)
                                if occ else 0.0),
        "max_batch": max_batch,
    }
