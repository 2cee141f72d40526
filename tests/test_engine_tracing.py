"""The serving engine measured from inside (PR 24): names on its device
programs and the flash kernels, the loop's phases as spans while a
``jax.profiler`` session is live (``util/tracing.phase``), the device's
timeline from the watcher thread, and the five TTFT stages. All on the
CPU with the tiny llama config."""

import dataclasses
import re
import sys
import threading
import time
from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.models import llama
from ray_tpu.serve.llm import _STAGES
from ray_tpu.serve.paged_llm import PagedLLMEngine
from ray_tpu.util import tracing

PHASES = {"engine.admit", "engine.dispatch_prefill", "engine.wait_arrivals",
          "engine.dispatch_decode", "engine.wait_device", "engine.emit"}
PAGE = 16
# stats()' account of what the two programs computed (always on)
DECODE_ACCOUNT = ("decode_slot_steps", "decode_delivered",
                  "decode_overrun_tail", "decode_overrun_ahead",
                  "decode_vacant")
PREFILL_ACCOUNT = ("prefill_token_rows", "prefill_new_tokens")
# and of the ends it foresaw and the slots handed on ahead of a read-back
HANDOVER_ACCOUNT = ("retirements_foreseen", "slots_handed_over")


@pytest.fixture(scope="module")
def tiny():
    cfg = llama.llama_tiny()
    return cfg, llama.init_params(cfg, jax.random.key(0))


def make_engine(tiny, **kwargs):
    cfg, params = tiny
    kwargs.setdefault("max_batch", 4)
    return PagedLLMEngine(cfg, params, max_len=128, page_size=PAGE,
                          num_pages=40, **kwargs)


def clear_ring():
    tracing.drain_spans(1 << 20)
    _, flight = tracing._rings()
    flight.clear()


def wait_idle(eng, timeout=60.0):
    """Until the loop thread stands in its idle poll: then every chunk it
    dispatched has been read back, and the counters and the ring rest."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        frame = sys._current_frames().get(eng._thread.ident)
        while frame is not None:
            if frame.f_code.co_name == "_wait_idle":
                return
            frame = frame.f_back
        time.sleep(0.005)
    raise AssertionError("the engine loop did not go idle")


def account(eng) -> dict:
    stats = eng.stats()
    return {k: stats[k] for k in (DECODE_ACCOUNT + PREFILL_ACCOUNT
                                  + HANDOVER_ACCOUNT)}


def handed_over(spans) -> int:
    return sum(s["attrs"]["handed_over"] for s in spans
               if s["name"] == "engine.admit")


def chunk_emits(spans) -> list:
    return [s["attrs"] for s in spans if s["name"] == "engine.emit"
            and s["attrs"]["what"] == "chunk"]


@pytest.fixture(scope="module")
def profiled(tiny, tmp_path_factory):
    """A toy paged engine that served two waves of requests while a
    profiler session was live: (spans, requests, what ``stats()``'
    prefix-cache counts and dispatch accounts rose by meanwhile)."""
    eng = make_engine(tiny)
    eng.start()
    rng = np.random.default_rng(0)
    shared = rng.integers(1, 500, 3 * PAGE)

    def prompt(n):       # three shared full pages, then n tokens of its own
        return np.concatenate([shared, rng.integers(1, 500, n)])

    list(eng.submit(prompt(9), max_new_tokens=20).tokens())    # compiles
    wait_idle(eng)      # the chunk in flight at its end has been read
    clear_ring()
    stats0 = dict(eng.stats()["prefix_cache"], **account(eng))
    jax.profiler.start_trace(str(tmp_path_factory.mktemp("profile")))
    try:
        reqs = []
        for wave in range(2):
            batch = [eng.submit(prompt(5 + 20 * i), max_new_tokens=20)
                     for i in range(3)]
            for r in batch:
                assert len(list(r.tokens())) == 20
            reqs += batch
        # let the engine go idle, then end the idle wait with one more
        # request (however loaded the machine, an idle span of half the
        # pause is there in the end: each further request ends one more)
        for _ in range(50):
            time.sleep(0.1)
            reqs.append(eng.submit(prompt(7), max_new_tokens=4))
            assert len(list(reqs[-1].tokens())) == 4
            if any(s["attrs"].get("what") == "idle" and s["duration"] > 0.05
                   for s in tracing.recorded_spans("engine.wait_arrivals")):
                break
        eng.stop()
    finally:
        jax.profiler.stop_trace()
    stats1 = dict(eng.stats()["prefix_cache"], **account(eng))
    spans = tracing.recorded_spans()
    clear_ring()
    rose = {k: stats1[k] - stats0[k] for k in (
        "hit_pages", "miss_pages") + DECODE_ACCOUNT + PREFILL_ACCOUNT
        + HANDOVER_ACCOUNT}
    return spans, reqs, rose


def test_loop_phases_are_children_of_their_iteration(profiled):
    spans, reqs, _ = profiled
    by_id = {s["span_id"]: s for s in spans}
    iterations = [s for s in spans if s["name"] == "engine.iteration"]
    assert len(iterations) >= 5
    assert len({s["trace_id"] for s in iterations}) == 1    # one an engine
    assert all(s["parent_id"] is None for s in iterations)
    seen = {s["name"] for s in spans}
    assert PHASES | {"engine.iteration", "device.run"} <= seen
    # each phase lies inside its parent, and siblings do not overlap
    children: dict = {}
    for s in spans:
        if s["name"] in PHASES and s["parent_id"] in by_id:
            parent = by_id[s["parent_id"]]
            want = ("engine.admit" if s["name"] == "engine.dispatch_prefill"
                    else "engine.iteration")
            assert parent["name"] == want, (s["name"], parent["name"])
            assert s["start"] >= parent["start"] - 1e-4
            assert (s["start"] + s["duration"]
                    <= parent["start"] + parent["duration"] + 1e-4)
            children.setdefault(parent["span_id"], []).append(s)
    assert children
    for sibs in children.values():
        sibs.sort(key=lambda s: s["start"])
        for a, b in zip(sibs, sibs[1:]):
            assert a["start"] + a["duration"] <= b["start"] + 1e-4
    # an idle engine is ONE wait span, however long it idles
    idle = [s for s in spans if s["name"] == "engine.wait_arrivals"
            and s["attrs"]["what"] == "idle"]
    pauses = len(reqs) - 6          # the requests after the two waves
    assert 1 <= len(idle) <= max(4, pauses)
    assert max(s["duration"] for s in idle) > 0.05
    assert [s["attrs"]["seq"] for s in iterations] == sorted(
        s["attrs"]["seq"] for s in iterations)


def test_every_dispatch_has_counts_and_one_device_run(profiled):
    spans, reqs, _ = profiled
    prefills = [s for s in spans if s["name"] == "engine.dispatch_prefill"]
    decodes = [s for s in spans if s["name"] == "engine.dispatch_decode"]
    runs = [s for s in spans if s["name"] == "device.run"]
    assert prefills and decodes
    assert sum(s["attrs"]["group"] for s in prefills) == len(reqs)
    for s in prefills:
        assert {"seq", "group", "bucket", "token_rows", "new_tokens",
                "cached_tokens", "missed_pages", "attn_kernel",
                "window_attn_kernel", "latent_attn_kernel",
                "expert_kernel"} <= set(s["attrs"])
        assert s["attrs"]["attn_kernel"] == 0       # lowered for the CPU
        assert s["attrs"]["window_attn_kernel"] == 0
        assert s["attrs"]["latent_attn_kernel"] == 0
        assert s["attrs"]["expert_kernel"] == 0
    for s in decodes:
        a = s["attrs"]
        assert {"seq", "chunk", "live", "slots", "drain"} <= set(a)
        assert 1 <= a["live"] <= a["slots"] == 4
    # stream order: sequence numbers rise with the dispatch time
    dispatches = sorted(prefills + decodes, key=lambda s: s["start"])
    seqs = [s["attrs"]["seq"] for s in dispatches]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
    # one device.run a dispatch, parented to it, never before it
    by_parent = {}
    for r in runs:
        by_parent.setdefault(r["parent_id"], []).append(r)
    for d in dispatches:
        (run,) = by_parent[d["span_id"]]
        assert run["attrs"]["seq"] == d["attrs"]["seq"]
        assert run["attrs"]["kind"] == d["name"].rsplit("_", 1)[1]
        assert run["attrs"]["wait_s"] >= 0.0 and run["duration"] >= 0.0
    # the device runs one program at a time, in stream order
    runs.sort(key=lambda r: r["attrs"]["seq"])
    for a, b in zip(runs, runs[1:]):
        assert a["start"] + a["duration"] <= b["start"] + 1e-3


def test_five_stages_sum_to_the_time_to_first_token(profiled):
    spans, reqs, _ = profiled
    for r in reqs:
        bd = r.breakdown
        assert set(bd) == {f"{s}_s" for s in _STAGES} and len(bd) == 5
        assert all(v >= 0.0 for v in bd.values())
        assert sum(bd.values()) == pytest.approx(r.ttft, abs=1e-9)
    # a request with no ambient span is a trace of its own: one
    # engine.request root and its five stage children, tiling it
    roots = [s for s in spans if s["name"] == "engine.request"]
    assert len(roots) == len(reqs)
    assert len({s["trace_id"] for s in roots}) == len(reqs)
    for root in roots:
        kids = [s for s in spans if s["parent_id"] == root["span_id"]]
        assert [k["name"] for k in kids] == [f"engine.{s}" for s in _STAGES]
        assert sum(k["duration"] for k in kids) == pytest.approx(
            root["duration"], abs=1e-6)
        assert root["parent_id"] is None


def test_prefix_miss_pages_are_the_pages_past_the_first_miss(profiled):
    """hit / (hit + miss) in stats() is the share the dispatch spans
    give: every request shares three full pages with the one before."""
    spans, reqs, hits = profiled
    prefills = [s["attrs"] for s in spans
                if s["name"] == "engine.dispatch_prefill"]
    cached = sum(a["cached_tokens"] for a in prefills) // PAGE
    missed = sum(a["missed_pages"] for a in prefills)
    assert (hits["hit_pages"], hits["miss_pages"]) == (cached, missed)
    lookups = sum((len(r.prompt) - 1) // PAGE for r in reqs)
    assert cached + missed == lookups and cached >= 3 * len(reqs)
    assert missed > 0


def test_every_chunks_slot_steps_are_accounted_for(profiled):
    """Each ``engine.emit`` span of a chunk classifies all ``chunk x
    max_batch`` slot-steps of its dispatch, exactly, and says which
    dispatch that was: ``seq``, ``chunk`` and ``drain`` are its
    ``engine.dispatch_decode`` span's, and ``vacant`` the slots that span
    did not count live."""
    spans, _, rose = profiled
    emits = chunk_emits(spans)
    assert len(emits) >= 5
    decodes = {s["attrs"]["seq"]: s["attrs"] for s in spans
               if s["name"] == "engine.dispatch_decode"}
    for a in emits:
        assert a["slot_steps"] == a["chunk"] * 4
        assert (a["tokens"] + a["overrun_tail"] + a["overrun_ahead"]
                + a["vacant"]) == a["slot_steps"]
        assert min(a["tokens"], a["overrun_tail"], a["overrun_ahead"],
                   a["vacant"]) >= 0
        d = decodes[a["seq"]]
        assert (a["chunk"], a["drain"]) == (d["chunk"], d["drain"])
        assert a["vacant"] == (d["slots"] - d["live"]) * d["chunk"]
    # two waves of 20-token answers end inside their chunks, and the
    # loop foresaw every end: no chunk was dispatched behind one
    assert sum(a["overrun_tail"] for a in emits) > 0
    assert sum(a["overrun_ahead"] for a in emits) == 0
    assert (rose["decode_delivered"] + rose["decode_overrun_tail"]
            + rose["decode_overrun_ahead"] + rose["decode_vacant"]
            ) == rose["decode_slot_steps"] > 0


def test_dispatch_accounts_on_the_spans_equal_stats(profiled):
    """Over the profiled run the spans' sums are what ``stats()``' seven
    integers rose by (they count where the spans are set, spans or no)."""
    spans, reqs, rose = profiled
    emits = chunk_emits(spans)
    for key, attr in zip(DECODE_ACCOUNT, ("slot_steps", "tokens",
                                          "overrun_tail", "overrun_ahead",
                                          "vacant")):
        assert sum(a[attr] for a in emits) == rose[key], key
    prefills = [s["attrs"] for s in spans
                if s["name"] == "engine.dispatch_prefill"]
    for a in prefills:
        assert a["token_rows"] == a["group"] * a["bucket"] >= a["new_tokens"]
    assert sum(a["token_rows"] for a in prefills) == \
        rose["prefill_token_rows"]
    assert sum(a["new_tokens"] for a in prefills) == \
        rose["prefill_new_tokens"]
    # every token but a request's first comes out of a decode chunk
    assert rose["decode_delivered"] == sum(r.generated - 1 for r in reqs)
    # every answer ended on its budget, so every end was foreseen; and no
    # slot was handed over: never more than three of the four were held
    assert rose["retirements_foreseen"] == len(reqs)
    assert rose["slots_handed_over"] == handed_over(spans) == 0


def _served(tiny, submit, trace=True, **engine):
    """Requests handed over BEFORE the loop starts (so what it admits
    when does not hang on the clock), served to their ends, with tracing
    on unless ``trace`` is false: (the chunks' emit spans in order, the
    engine's account, the hand-overs its ``engine.admit`` spans show)."""
    eng = make_engine(tiny, **engine)
    clear_ring()
    if trace:
        tracing.enable_tracing()
    try:
        reqs = submit(eng)
        eng.start()
        for r, n in reqs:
            assert len(list(r.tokens())) == n
        wait_idle(eng)
        eng.stop()
        spans = tracing.recorded_spans("engine.")
    finally:
        tracing.disable_tracing()
        clear_ring()
    return chunk_emits(spans), account(eng), handed_over(spans)


@pytest.mark.parametrize("chunk,tokens,tail", [
    (4, 7, 2),      # first token, a chunk of 4, then 2 of the next 4
    (4, 9, 0),      # ends with the last step of its second chunk
    (16, 20, 13),   # the default chunk: 16, then 3 of 16
    (1, 3, 0)], ids=["mid-chunk", "chunk-end", "default-chunk", "chunk-1"])
def test_a_foreseen_end_costs_its_chunks_tail_and_no_chunk_behind_it(
        tiny, chunk, tokens, tail):
    """One request alone in an engine of two slots: its answer's tokens
    after the first come out of whole chunks; the steps after its end in
    its last chunk are ``overrun_tail``; the loop knows at that chunk's
    dispatch that the answer ends inside it, so NO chunk is dispatched
    behind it and nothing is ``overrun_ahead``; the other slot is
    ``vacant`` throughout."""
    def submit(eng):
        return [(eng.submit(np.arange(1, 20), max_new_tokens=tokens),
                 tokens)]

    emits, total, handed = _served(tiny, submit, decode_chunk=chunk,
                                   max_batch=2)
    live = -(-(tokens - 1) // chunk)         # chunks that deliver
    want = [(chunk, 0, 0)] * (live - 1) + [(chunk - tail, tail, 0)]
    assert [(a["tokens"], a["overrun_tail"], a["overrun_ahead"])
            for a in emits] == want
    assert all(a["vacant"] == chunk and a["slot_steps"] == 2 * chunk
               and a["chunk"] == chunk for a in emits)
    assert [total[k] for k in DECODE_ACCOUNT] == [
        2 * chunk * live, tokens - 1, tail, 0, chunk * live]
    # foreseen, and nobody waited for the slot
    assert [total[k] for k in HANDOVER_ACCOUNT] == [1, 0] and handed == 0


def test_a_one_token_request_is_live_in_the_chunk_behind_its_prefill(tiny):
    """Ends are foreseen where decode is dispatched: a request whose first
    token is its last is live in the one chunk that follows its prefill
    (all of it ``overrun_ahead``, as before) and released at that
    dispatch. The benchmark's set-up leans on it: its one-token requests
    are how the decode programs of every kind are met before a window."""
    def submit(eng):
        return [(eng.submit(np.arange(1, 20), max_new_tokens=1), 1)]

    emits, total, handed = _served(tiny, submit, decode_chunk=4, max_batch=2)
    assert [(a["tokens"], a["overrun_tail"], a["overrun_ahead"], a["vacant"])
            for a in emits] == [(0, 0, 4, 4)]
    assert [total[k] for k in HANDOVER_ACCOUNT] == [1, 0] and handed == 0


def test_a_handed_over_slot_loses_no_chunk_in_flight(tiny):
    """One slot, two requests: A (7 tokens) ends two steps into its
    second chunk, and the loop knows so where it dispatches that chunk:
    B takes the slot at the top of the next pass, its prefill behind A's
    last chunk on the device stream, and the chunk after that one is
    B's; A's last two tokens still reach A when its chunk is read, after
    B holds the slot. B (5 tokens) ends with its chunk's last step and no
    chunk follows it."""
    def submit(eng):
        return [(eng.submit(np.arange(1, 20), max_new_tokens=7), 7),
                (eng.submit(np.arange(30, 45), max_new_tokens=5), 5)]

    emits, total, handed = _served(tiny, submit, decode_chunk=4,
                                   max_batch=1)
    assert [(a["tokens"], a["overrun_tail"], a["overrun_ahead"], a["vacant"])
            for a in emits] == [(4, 0, 0, 0), (2, 2, 0, 0), (4, 0, 0, 0)]
    assert [total[k] for k in DECODE_ACCOUNT] == [12, 10, 2, 0, 0]
    # the seqs are the stream's: B's prefill lies between A's last chunk
    # and its own first
    assert [a["seq"] for a in emits] == [1, 2, 4]
    # both ends foreseen; B took A's slot ahead of A's read-back
    assert [total[k] for k in HANDOVER_ACCOUNT] == [2, 1] and handed == 1


@pytest.fixture(scope="module")
def greedy7(tiny):
    """The tiny model's first seven greedy tokens after ``arange(1, 20)``."""
    eng = make_engine(tiny, max_batch=2)
    eng.start()
    try:
        return list(eng.submit(np.arange(1, 20), max_new_tokens=7).tokens())
    finally:
        eng.stop()


@pytest.mark.parametrize("early", [True, False], ids=["early", "at-bound"])
def test_an_end_on_eos_pays_the_chunk_in_flight_and_one_on_the_bound_none(
        tiny, greedy7, early):
    """An ``eos_id`` may end an answer sooner than its budget, which the
    host cannot foresee: the end is seen where its chunk is read, and the
    chunk dispatched before that is ``overrun_ahead``, all of it. The
    same request run to its budget (its ``eos_id`` never sampled) is
    foreseen like any other."""
    assert greedy7[6] not in greedy7[:6]
    # seven tokens either way: the first, a chunk of 4, 2 of the next 4
    eos, budget = (greedy7[6], 40) if early else (max(greedy7) + 1, 7)

    def submit(eng):
        return [(eng.submit(np.arange(1, 20), max_new_tokens=budget,
                            eos_id=eos), 7)]

    emits, total, handed = _served(tiny, submit, decode_chunk=4, max_batch=2)
    ahead = 4 if early else 0
    want = [(4, 0, 0), (2, 2, 0)] + [(0, 0, 4)] * early
    assert [(a["tokens"], a["overrun_tail"], a["overrun_ahead"])
            for a in emits] == want
    assert total["decode_overrun_ahead"] == ahead
    assert [total[k] for k in HANDOVER_ACCOUNT] == [int(not early), 0]
    assert handed == 0


def test_the_hand_over_counts_equal_the_spans_and_the_identity_holds(tiny):
    """Two slots and six waiting requests of different lengths: each
    freed slot has a taker, so every hand-over the ``engine.admit`` spans
    show is one ``stats()`` counted, every chunk's slot-steps sum, and
    with tracing off the integers come out the same."""
    budgets = (7, 5, 12, 3, 9, 6)

    def submit(eng):
        return [(eng.submit(np.arange(1 + i, 20 + 2 * i), max_new_tokens=n),
                 n) for i, n in enumerate(budgets)]

    emits, total, handed = _served(tiny, submit, decode_chunk=4, max_batch=2)
    for a in emits:
        assert (a["tokens"] + a["overrun_tail"] + a["overrun_ahead"]
                + a["vacant"]) == a["slot_steps"] == 8
    assert total["retirements_foreseen"] == len(budgets)
    assert total["slots_handed_over"] == handed >= 3
    assert total["decode_overrun_ahead"] == 0
    assert total["decode_delivered"] == sum(budgets) - len(budgets)
    assert (total["decode_delivered"] + total["decode_overrun_tail"]
            + total["decode_vacant"]) == total["decode_slot_steps"]
    none, untraced, _ = _served(tiny, submit, trace=False, decode_chunk=4,
                                max_batch=2)
    assert none == [] and untraced == total


def test_prefill_rows_are_group_times_bucket_and_new_tokens_the_suffixes(
        tiny):
    """``prefill_token_rows`` / ``prefill_new_tokens`` against the prompts
    submitted: a prompt is padded to its power-of-two bucket, a prefix
    hit leaves only the suffix past the cached pages to compute, and
    prompts of one bucket admitted together are one dispatch of ``group
    x bucket`` rows."""
    rng = np.random.default_rng(4)
    shared = rng.integers(1, 500, 3 * PAGE)
    eng = make_engine(tiny)
    eng.start()
    for own, rows, new in ((9, 64, 57), (5, 16, 5)):
        before = account(eng)
        prompt = np.concatenate([shared, rng.integers(1, 500, own)])
        assert len(list(eng.submit(prompt, max_new_tokens=2).tokens())) == 2
        after = account(eng)
        assert after["prefill_token_rows"] - before["prefill_token_rows"] \
            == rows
        assert after["prefill_new_tokens"] - before["prefill_new_tokens"] \
            == new
    eng.stop()

    def submit(eng):        # 20 and 30 tokens: one dispatch of 2 x 32
        return [(eng.submit(rng.integers(1, 500, n), max_new_tokens=2), 2)
                for n in (20, 30)]

    _, total, _ = _served(tiny, submit)
    assert (total["prefill_token_rows"], total["prefill_new_tokens"]) == (
        64, 50)


def test_prefill_dispatches_say_whether_their_program_holds_the_kernel(
        tiny, monkeypatch):
    """``attn_kernel`` on ``engine.dispatch_prefill`` is the rule the
    program was traced by (``ops/paged_prefill_attention.py``:
    ``kernel_engages``) applied to the host's own shapes, on a TPU
    backend alone; ``stats()`` counts the dispatches and those with it.
    Off a TPU every dispatch reads 0 (above). Here the engine is told it
    is on one, and a rule that the tiny shapes reach stands in for the
    256 MiB of scores: buckets of 64 tokens engage, shorter ones do not."""
    from ray_tpu.serve import engine_programs

    eng = make_engine(tiny)
    assert not eng._programs._kernel_backend        # the CPU's
    eng._programs._kernel_backend = True
    seen = []

    def rule(q_shape, pools, table_width, window):
        seen.append((q_shape, pools.shape, table_width, window))
        return q_shape[1] >= 64

    monkeypatch.setattr(engine_programs, "kernel_engages", rule)
    clear_ring()
    tracing.enable_tracing()
    try:
        eng.start()
        rng = np.random.default_rng(2)
        for n in (50, 9, 40, 70):       # distinct prompts: no page reused
            assert len(list(eng.submit(rng.integers(1, 500, n),
                                       max_new_tokens=4).tokens())) == 4
        eng.stop()
        spans = tracing.recorded_spans("engine.dispatch_prefill")
    finally:
        tracing.disable_tracing()
        clear_ring()
    got = [(s["attrs"]["bucket"], s["attrs"]["attn_kernel"]) for s in spans]
    assert got == [(64, 1), (16, 0), (64, 1), (128, 1)]
    stats = eng.stats()
    assert stats["prefill_dispatches"] == 4
    assert stats["prefill_kernel_dispatches"] == 3
    # the rule saw the dispatch's own shapes: rows, bucket, the model's
    # full-layer heads and head size; the pool; the window's pages
    cfg = tiny[0]
    assert seen[0] == ((1, 64, cfg.n_heads, cfg.head_dim),
                       eng._programs.pools[0].shape, 4, None)
    # a plan without a sliding run asks the rule nothing about a window
    assert not eng._programs._window_kernel_backend
    assert {call[3] for call in seen} == {None}
    assert [s["attrs"]["window_attn_kernel"] for s in spans] == [0] * 4
    assert stats["window_kernel_dispatches"] == 0


@pytest.mark.parametrize("sliding_heads", [None, 18],
                         ids=["smallthinker", "laguna-sliding-heads"])
def test_prefill_dispatches_say_whether_their_sliding_layers_hold_the_kernel(
        monkeypatch, sliding_heads):
    """``window_attn_kernel`` beside ``attn_kernel``: the same rule asked
    once more, with the plan's window and the sliding layers' own head
    count where the family states one (Laguna's ``n_heads_sliding``), on
    a TPU backend alone; ``stats()`` counts the dispatches with it as
    ``window_kernel_dispatches``. The stand-in rule engages a full layer
    from 64 tokens and a sliding one from 128: the two counters part."""
    from ray_tpu.models import laguna, smallthinker
    from ray_tpu.serve import engine_programs

    model, cfg = ((smallthinker, smallthinker.smallthinker_tiny())
                  if sliding_heads is None
                  else (laguna, laguna.laguna_tiny()))
    eng = make_engine((cfg, model.init_params(cfg, jax.random.key(0))))
    programs = eng._programs
    assert not programs._kernel_backend             # the CPU's
    assert not programs._window_kernel_backend
    assert eng.stats()["window_kernel_dispatches"] == 0
    programs._kernel_backend = programs._window_kernel_backend = True
    seen = []

    def rule(q_shape, pools, table_width, window):
        seen.append((q_shape[2], window))
        return q_shape[1] >= (64 if window is None else 128)

    monkeypatch.setattr(engine_programs, "kernel_engages", rule)
    clear_ring()
    tracing.enable_tracing()
    try:
        eng.start()
        rng = np.random.default_rng(2)
        for n in (50, 9, 70):
            assert len(list(eng.submit(rng.integers(1, cfg.vocab_size, n),
                                       max_new_tokens=4).tokens())) == 4
        eng.stop()
        spans = tracing.recorded_spans("engine.dispatch_prefill")
    finally:
        tracing.disable_tracing()
        clear_ring()
    got = [(s["attrs"]["bucket"], s["attrs"]["attn_kernel"],
            s["attrs"]["window_attn_kernel"]) for s in spans]
    assert got == [(64, 1, 0), (16, 0, 0), (128, 1, 1)]
    stats = eng.stats()
    assert stats["prefill_kernel_dispatches"] == 2
    assert stats["window_kernel_dispatches"] == 1
    assert seen[:2] == [(cfg.n_heads, None),
                        (sliding_heads or cfg.n_heads, cfg.window)]


_STATE_KERNEL_CASES = [
    # the backend the engine finds, the mixer's state size, state_kernel
    ("cpu", 16, 0), ("cpu", 128, 0), ("tpu", 16, 0), ("tpu", 128, 1)]


@pytest.mark.parametrize(
    "backend,state_size,engaged", _STATE_KERNEL_CASES,
    ids=[f"{b}-state{n}" for b, n, _ in _STATE_KERNEL_CASES])
def test_decode_dispatches_say_whether_their_program_holds_the_state_kernel(
        monkeypatch, backend, state_size, engaged):
    """``state_kernel`` on ``engine.dispatch_decode`` is the rule the
    program's state update was traced by (``ops/ssm.py``:
    ``state_kernel_engages``) applied to the engine's own state arrays,
    on a TPU backend alone; ``stats()`` counts the decode dispatches and
    those with it. On the CPU it is 0 of n whatever the shapes; an engine
    that FINDS a TPU backend (it is told so here, as it is built; its
    programs still lower for the CPU) says 1 where the state is whole
    lanes of float32 and 0 where it is not. A plan of pages alone
    carries no such count (the test below)."""
    from ray_tpu.models import falcon_h1
    from ray_tpu.serve import engine_programs

    cfg = falcon_h1.falcon_h1_tiny(ssm_state=state_size)
    monkeypatch.setattr(engine_programs.jax, "default_backend",
                        lambda: backend)
    eng = PagedLLMEngine(cfg, falcon_h1.init_params(cfg, jax.random.key(0)),
                         max_batch=2, max_len=64, page_size=PAGE,
                         num_pages=12)
    monkeypatch.undo()
    clear_ring()
    tracing.enable_tracing()
    try:
        eng.start()
        rng = np.random.default_rng(3)
        for n in (20, 7, 33):
            assert len(list(eng.submit(rng.integers(1, 100, n),
                                       max_new_tokens=9).tokens())) == 9
        eng.stop()
        decodes = tracing.recorded_spans("engine.dispatch_decode")
    finally:
        tracing.disable_tracing()
        clear_ring()
    stats = eng.stats()
    assert decodes and stats["decode_dispatches"] == len(decodes)
    assert [s["attrs"]["state_kernel"] for s in decodes] == \
        [engaged] * len(decodes)
    assert stats["state_kernel_dispatches"] == engaged * len(decodes)
    assert sum(s["attrs"]["state_kernel"] for s in decodes) == \
        stats["state_kernel_dispatches"]


def test_recurrent_state_counts_on_the_dispatch_spans_equal_stats(tiny):
    """A plan with a recurrent run (the tiny Falcon-H1): each prefill
    dispatch says how many rows' state it installs in a slot
    (``state_installs``: its group) and how many chunks its scan cuts
    the padded bucket into (``scan_chunks``); each decode dispatch how
    many live slots' state the chunk advances (``state_slots``) and the
    bytes one step reads and writes of them (``state_bytes``), all from
    the host's own counts. Their sums are ``stats()``'s. A plan of pages
    alone carries none of the four, nor ``state_kernel``, and its
    ``stats()`` count the decode dispatches with no state kernel among
    them."""
    from ray_tpu.models import falcon_h1

    cfg = falcon_h1.falcon_h1_tiny()
    eng = PagedLLMEngine(cfg, falcon_h1.init_params(cfg, jax.random.key(0)),
                         max_batch=3, max_len=128, page_size=PAGE,
                         num_pages=30)
    state_keys = {"state_installs", "scan_chunks", "state_slots",
                  "state_bytes", "state_kernel"}
    clear_ring()
    tracing.enable_tracing()
    try:
        eng.start()
        rng = np.random.default_rng(5)
        reqs = [eng.submit(rng.integers(1, 100, n), max_new_tokens=6)
                for n in (50, 9, 40, 70, 12)]
        for r in reqs:
            assert len(list(r.tokens())) == 6
        eng.stop()
        prefills = tracing.recorded_spans("engine.dispatch_prefill")
        decodes = tracing.recorded_spans("engine.dispatch_decode")
        plain = make_engine(tiny)
        clear_ring()
        plain.start()
        assert len(list(plain.submit(rng.integers(1, 500, 20),
                                     max_new_tokens=4).tokens())) == 4
        plain.stop()
        others = tracing.recorded_spans("engine.dispatch_")
    finally:
        tracing.disable_tracing()
        clear_ring()
    stats = eng.stats()
    slot_bytes = cfg.n_layers * 4 * (6 * 8 * 16 + 3 * cfg.conv_dim)
    assert stats["state_bytes_held"] == 3 * slot_bytes
    assert prefills and decodes
    for s in prefills:
        a = s["attrs"]
        assert a["state_installs"] == a["group"]
        assert a["scan_chunks"] == a["group"] * -(-a["bucket"]
                                                  // cfg.ssm_chunk)
    assert sum(s["attrs"]["state_installs"] for s in prefills) == \
        stats["state_installs"] == len(reqs)
    for s in decodes:
        a = s["attrs"]
        assert a["state_slots"] == a["live"]
        assert a["state_bytes"] == 2 * a["live"] * slot_bytes
    assert others and not any(state_keys & set(s["attrs"]) for s in others)
    assert plain.stats()["state_installs"] == 0
    assert plain.stats()["state_bytes_held"] == 0
    assert plain.stats()["decode_dispatches"] >= 1
    assert plain.stats()["state_kernel_dispatches"] == 0


def _pool_stats(model, cfg, **engine):
    eng = PagedLLMEngine(cfg, model.init_params(cfg, jax.random.key(0)),
                         max_batch=3, max_len=128, page_size=PAGE,
                         num_pages=30, **engine)
    return eng, eng.stats()


@pytest.mark.parametrize("family", ["llama-bf16", "llama-int8", "laguna",
                                    "dots3_note"])
def test_stats_count_the_pools_own_bytes_and_what_a_token_keeps(family):
    """``stats()`` counts the cache from the pools the plan states, not
    from an assumed K/V twin: ``kv_pages_bytes`` is every pool that holds
    a row a token (K and V pages, with their scales under int8; a latent
    plan's rows, in whole lanes), ``cache_bytes_per_token`` that over the
    pool's tokens, ``kv_dense_equiv_bytes`` what ``max_batch`` contiguous
    bf16 rows of ``max_len`` would take. A prefill dispatch's span names
    the rows' formats (``page_rows``)."""
    from ray_tpu.models import dots3_note, laguna

    tokens, slots_len = 30 * PAGE, 3 * 128
    if family.startswith("llama"):
        cfg = llama.llama_tiny()
        int8 = family.endswith("int8")
        eng, stats = _pool_stats(llama, cfg,
                                 kv_dtype="int8" if int8 else "bf16")
        row = cfg.n_layers * 2 * cfg.n_kv_heads * cfg.head_dim
        want = row + cfg.n_layers * 2 * cfg.n_kv_heads * 4 if int8 \
            else 2 * row
        rows, dense = "k+v", 2 * row
    elif family == "laguna":
        cfg = laguna.laguna_tiny()
        eng, stats = _pool_stats(laguna, cfg)
        want = dense = cfg.n_layers * 2 * cfg.n_kv_heads * cfg.head_dim * 2
        rows = "k+v"
    else:
        cfg = dots3_note.dots3_note_tiny()
        eng, stats = _pool_stats(dots3_note, cfg)
        # float32 rows of 24 | 16 (two full layers) and 40 (three sliding
        # ones), each in one lane group of 128
        want, dense = 7 * 128 * 4, 7 * 128 * 2
        rows = "latent:24,index_key:16;latent:40"
    assert stats["cache_bytes_per_token"] == want
    assert stats["kv_pages_bytes"] == want * tokens == sum(
        a.size * a.dtype.itemsize for a in eng._programs.pools
        if a.shape[1] == eng.num_pages)
    assert stats["kv_dense_equiv_bytes"] == dense * slots_len
    clear_ring()
    tracing.enable_tracing()
    try:
        eng.start()
        assert len(list(eng.submit(np.arange(1, 40) % cfg.vocab_size,
                                   max_new_tokens=3).tokens())) == 3
        eng.stop()
        prefills = tracing.recorded_spans("engine.dispatch_prefill")
        decodes = tracing.recorded_spans("engine.dispatch_decode")
    finally:
        tracing.disable_tracing()
        clear_ring()
    assert prefills and all(s["attrs"]["page_rows"] == rows
                            for s in prefills)
    selected = [s["attrs"].get("kv_rows_selected") for s in decodes]
    if family == "dots3_note":
        assert decodes and all(
            s["attrs"]["kv_rows_selected"] == min(
                s["attrs"]["kv_rows_full"], cfg.index_topk * s["attrs"]["live"])
            and s["attrs"]["index_rows"] == s["attrs"]["kv_rows_full"]
            for s in decodes)
    else:
        assert decodes and selected == [None] * len(decodes)


def _decode_spans_of(eng, prompts, new_tokens=9):
    """The ``engine.dispatch_decode`` spans of ``prompts`` served one
    after another, and the engine's ``stats()`` after them."""
    clear_ring()
    tracing.enable_tracing()
    try:
        eng.start()
        for prompt in prompts:
            assert len(list(eng.submit(
                prompt, max_new_tokens=new_tokens).tokens())) == new_tokens
        eng.stop()
        decodes = tracing.recorded_spans("engine.dispatch_decode")
    finally:
        tracing.disable_tracing()
        clear_ring()
    assert eng.error is None
    return decodes, eng.stats()


def _note_engine_on(monkeypatch, backend, **changes):
    """An engine over the tiny latent plan (``changes`` to its
    configuration; pages of 128, a table of two) that FINDS ``backend``
    as it is built; its programs still lower for the CPU."""
    from ray_tpu.models import dots3_note
    from ray_tpu.serve import engine_programs

    cfg = dots3_note.dots3_note_tiny(**changes)
    monkeypatch.setattr(engine_programs.jax, "default_backend",
                        lambda: backend)
    eng = PagedLLMEngine(cfg, dots3_note.init_params(cfg, jax.random.key(0)),
                         max_batch=2, max_len=256, page_size=128,
                         num_pages=8)
    monkeypatch.undo()
    return eng


_LATENT_KERNEL_CASES = [
    # the backend the engine finds, the keys its full layers keep (of a
    # table of 256), latent_kernel
    ("cpu", 64, 0), ("tpu", 64, 1), ("tpu", 12, 0), ("tpu", 256, 0)]


@pytest.mark.parametrize(
    "backend,topk,engaged", _LATENT_KERNEL_CASES,
    ids=[f"{b}-top{n}" for b, n, _ in _LATENT_KERNEL_CASES])
def test_decode_dispatches_say_whether_their_program_holds_the_latent_kernel(
        monkeypatch, backend, topk, engaged):
    """``latent_kernel`` on ``engine.dispatch_decode`` is the rule the
    program's full layers were traced by (``ops/latent_attention.py``:
    ``latent_kernel_engages``) applied to the dispatched program's own
    table (two pages of 128 here), on a TPU backend alone; ``stats()``
    counts the decode dispatches that took it. On the CPU it is 0 of n
    whatever the shapes; an engine that FINDS a TPU backend (it is told
    so here, as it is built; its programs still lower for the CPU) says 1
    where the table holds more than ``topk`` keys and no more than eight
    times as many, 0 where it holds twenty times as many and 0 where
    nothing is selected."""
    eng = _note_engine_on(monkeypatch, backend, index_topk=topk)
    rng = np.random.default_rng(3)
    decodes, stats = _decode_spans_of(
        eng, [rng.integers(1, 100, n) for n in (70, 7, 90)])
    assert decodes and stats["decode_dispatches"] == len(decodes)
    assert [s["attrs"]["latent_kernel"] for s in decodes] == \
        [engaged] * len(decodes)
    assert stats["latent_kernel_dispatches"] == engaged * len(decodes)


_LATENT_PREFILL_CASES = [
    # the backend the engine finds, the plan, latent_attn_kernel of the
    # prefills of 70, 7 and 90 tokens (buckets of 128, 16 and 128)
    ("cpu", "latent", [0, 0, 0]), ("tpu", "latent", [1, 0, 1]),
    ("tpu", "twins", [0, 0, 0])]


@pytest.mark.parametrize("traced", [True, False], ids=["traced", "untraced"])
@pytest.mark.parametrize(
    "backend,plan,engaged", _LATENT_PREFILL_CASES,
    ids=[f"{b}-{p}" for b, p, _ in _LATENT_PREFILL_CASES])
def test_prefill_dispatches_say_whether_their_latent_layers_hold_the_kernel(
        tiny, monkeypatch, backend, plan, engaged, traced):
    """``latent_attn_kernel`` on ``engine.dispatch_prefill`` is the rule a
    run of latent layers was traced by (``ops/latent_attention.py``:
    ``latent_prefill_kernel_engages``) applied to the host's own shapes,
    once a run, on a TPU backend alone; ``stats()`` counts the dispatches
    with it as ``latent_prefill_kernel_dispatches``, the spans' sum, and
    gives the same integers with tracing off. 0 on the CPU whatever the
    shapes, and 0 for a plan of K/V twins, whose runs the rule is never
    asked about. The stand-in rule engages a full layer from 64 tokens
    and a sliding one never: a program counts once if any run holds it."""
    from ray_tpu.serve import engine_programs

    seen = []

    def rule(q_shape, pool, table_pages, window, index_heads):
        seen.append((q_shape[:3], pool.shape[-1], table_pages, window,
                     index_heads))
        return window is None and q_shape[1] >= 64

    if plan == "latent":
        eng = _note_engine_on(monkeypatch, backend, index_topk=64)
    else:
        monkeypatch.setattr(engine_programs.jax, "default_backend",
                            lambda: backend)
        eng = make_engine(tiny)
        monkeypatch.undo()
    monkeypatch.setattr(engine_programs, "latent_prefill_kernel_engages",
                        rule)
    assert eng.stats()["latent_prefill_kernel_dispatches"] == 0
    clear_ring()
    if traced:
        tracing.enable_tracing()
    try:
        eng.start()
        rng = np.random.default_rng(3)
        for n in (70, 7, 90):
            assert len(list(eng.submit(rng.integers(1, 100, n),
                                       max_new_tokens=3).tokens())) == 3
        eng.stop()
        spans = tracing.recorded_spans("engine.dispatch_prefill")
    finally:
        tracing.disable_tracing()
        clear_ring()
    assert eng.error is None
    stats = eng.stats()
    assert stats["prefill_dispatches"] == 3
    assert stats["latent_prefill_kernel_dispatches"] == sum(engaged)
    if traced:
        assert [s["attrs"]["latent_attn_kernel"] for s in spans] == engaged
        assert [s["attrs"]["attn_kernel"] for s in spans] == [0] * 3
    else:
        assert not spans
    if backend == "tpu" and plan == "latent":
        # the rule saw the dispatch's own shapes: the full layers' heads
        # and the lanes of their rows' pool beside their indexer's heads
        # (a table of one page, 128 keys, is more than the 64 kept); a
        # program counts once, so no run is asked after one that holds
        # it; the 16-token dispatch asked every run, the sliding layers'
        # with their window and no indexer
        cfg = eng._programs.cfg
        assert seen[0] == ((1, 128, cfg.n_heads), 128, 1, None,
                           cfg.index_heads)
        assert [(call[0][1], call[3], call[4]) for call in seen[1:4]] == [
            (16, None, cfg.index_heads), (16, None, cfg.index_heads),
            (16, cfg.window, 0)]
        assert seen[3][0][2] == cfg.n_heads_sliding
    else:
        assert not seen


_INDEX_KERNEL_CASES = [
    # the backend the engine finds, the keys its full layers keep (of a
    # table of 256), an index key's width, latent_kernel, index_kernel
    ("cpu", 64, 128, 0, 0), ("tpu", 64, 128, 1, 1), ("tpu", 64, 16, 1, 1),
    ("tpu", 12, 128, 0, 1), ("tpu", 256, 128, 0, 0)]


@pytest.mark.parametrize(
    "backend,topk,width,latent,index", _INDEX_KERNEL_CASES,
    ids=[f"{b}-top{n}-key{w}" for b, n, w, _, _ in _INDEX_KERNEL_CASES])
def test_decode_dispatches_say_whether_their_program_holds_the_index_kernel(
        monkeypatch, backend, topk, width, latent, index):
    """``index_kernel`` on ``engine.dispatch_decode`` is what
    ``EnginePrograms.decode_kernels`` says of the dispatched program's
    own table (two pages of 128 here): the rule its full layers' scores
    were traced by (``ops/index_select.py``: ``index_kernel_engages``, on
    the index keys' pool, whose rows are whole lanes), on a TPU backend
    alone; ``stats()`` counts the decode dispatches that took it. 0 on
    the CPU whatever the shapes; on an engine that finds a TPU backend 1
    wherever the table holds more than ``topk`` keys (past eight times
    ``topk`` too, where the latent kernel does not engage; for keys of 16
    numbers too, since PR 60: the queries meet the row's spare lanes with
    zeros) and 0 where nothing is selected."""
    eng = _note_engine_on(monkeypatch, backend, index_topk=topk,
                          index_dim=width)
    said = eng._programs.decode_kernels(2)
    assert (said["latent_kernel"], said["index_kernel"]) == (latent, index)
    rng = np.random.default_rng(3)
    decodes, stats = _decode_spans_of(
        eng, [rng.integers(1, 100, n) for n in (70, 7, 90)])
    assert decodes and stats["decode_dispatches"] == len(decodes)
    assert [s["attrs"]["index_kernel"] for s in decodes] == \
        [index] * len(decodes)
    assert stats["index_kernel_dispatches"] == index * len(decodes)
    assert stats["latent_kernel_dispatches"] == latent * len(decodes)


_ATTN_STEP_CASES = [
    # the backend the engine finds, the family, its KV heads (query heads
    # a KV head as the tiny configuration has them), attn_step_pages
    ("tpu", "smallthinker", 4, 2), ("tpu", "nemotron_h", 2, 4),
    ("tpu", "llama", 8, 1), ("tpu", "dots3_note", None, 0),
    ("cpu", "smallthinker", 4, 0)]


@pytest.mark.parametrize(
    "backend,family,kv_heads,step", _ATTN_STEP_CASES,
    ids=[f"{b}-{f}-kv{n}" for b, f, n, _ in _ATTN_STEP_CASES])
def test_decode_dispatches_say_how_many_pages_a_step_of_the_kernel_takes(
        monkeypatch, backend, family, kv_heads, step):
    """``attn_step_pages`` on ``engine.dispatch_decode`` is the decode
    kernel's own rule (``ops/paged_decode_attention.py``: ``step_pages``)
    on the K/V pools the engine holds, pages of 128 tokens here: two
    pages a step at 4 KV heads, four at 2, one at 8; 0 for a plan none of
    whose layers attends over K/V twins (the latent family's rows) and 0
    on any backend but a TPU, where no kernel runs.
    ``EnginePrograms.decode_kernels`` is where the loop has it from."""
    from ray_tpu.models import dots3_note, nemotron_h, smallthinker
    from ray_tpu.serve import engine_programs

    model, cfg = {
        "smallthinker": lambda: (smallthinker, smallthinker.smallthinker_tiny(
            n_heads=7 * kv_heads, n_kv_heads=kv_heads)),
        "nemotron_h": lambda: (nemotron_h, nemotron_h.nemotron_h_tiny(
            n_kv_heads=kv_heads)),
        "llama": lambda: (llama, dataclasses.replace(
            llama.llama_tiny(), n_heads=kv_heads, n_kv_heads=kv_heads)),
        "dots3_note": lambda: (dots3_note, dots3_note.dots3_note_tiny()),
    }[family]()
    monkeypatch.setattr(engine_programs.jax, "default_backend",
                        lambda: backend)
    eng = PagedLLMEngine(cfg, model.init_params(cfg, jax.random.key(0)),
                         max_batch=2, max_len=256, page_size=128,
                         num_pages=8)
    monkeypatch.undo()
    assert eng._programs.decode_kernels(2)["attn_step_pages"] == step
    rng = np.random.default_rng(3)
    decodes, stats = _decode_spans_of(
        eng, [rng.integers(1, 100, n) for n in (70, 7)], new_tokens=5)
    assert decodes and stats["decode_dispatches"] == len(decodes)
    assert [s["attrs"]["attn_step_pages"] for s in decodes] == \
        [step] * len(decodes)


@pytest.mark.parametrize("family", ["llama", "olmoe", "laguna", "falcon_h1"])
def test_the_older_plans_carry_no_latent_kernel_count(monkeypatch, family):
    """A plan with no layer that picks its keys says nothing of the
    latent kernel on its decode dispatches and counts none, on an engine
    that finds a TPU backend too."""
    from ray_tpu.models import falcon_h1, laguna, olmoe
    from ray_tpu.serve import engine_programs

    model, cfg = {"llama": (llama, llama.llama_tiny),
                  "olmoe": (olmoe, olmoe.olmoe_tiny),
                  "laguna": (laguna, laguna.laguna_tiny),
                  "falcon_h1": (falcon_h1, falcon_h1.falcon_h1_tiny)}[family]
    monkeypatch.setattr(engine_programs.jax, "default_backend", lambda: "tpu")
    eng, _ = _pool_stats(model, cfg(), prefix_cache=False)
    monkeypatch.undo()
    decodes, stats = _decode_spans_of(eng, [np.arange(1, 40)], new_tokens=5)
    assert decodes and stats["decode_dispatches"] == len(decodes)
    assert not any("latent_kernel" in s["attrs"] for s in decodes)
    assert stats["latent_kernel_dispatches"] == 0
    # nor of the index kernel: a plan with no indexer
    assert not any("index_kernel" in s["attrs"] for s in decodes)
    assert stats["index_kernel_dispatches"] == 0
    assert eng._programs.decode_kernels(2)["index_kernel"] == 0


@pytest.mark.parametrize("family,backend,routes", [
    ("olmoe", "tpu", True), ("nemotron_h", "tpu", True),
    ("olmoe", "cpu", False), ("llama", "tpu", False)])
def test_prefill_dispatches_say_whether_their_experts_run_in_the_kernel(
        monkeypatch, family, backend, routes):
    """``expert_kernel`` on ``engine.dispatch_prefill`` is the rule the
    program's routed experts were traced by (``ops/moe.py``:
    ``expert_kernel_engages``, the dispatch's rows against the line)
    applied to the host's own count, ``group x bucket``, on a TPU backend
    alone and for a plan that routes; ``stats()`` counts the dispatches
    that took it beside ``prefill_dispatches``. The line is brought down
    to where toy prompts cross it: 16 rows stay under, 64 and 128 pass.
    An engine on the CPU, and a plan with no router on an engine that
    finds a TPU (it is told so as it is built; its programs still lower
    for the CPU), read 0 on every dispatch."""
    from ray_tpu.models import nemotron_h, olmoe
    from ray_tpu.ops import moe
    from ray_tpu.serve import engine_programs

    model, cfg = {"llama": (llama, llama.llama_tiny),
                  "olmoe": (olmoe, olmoe.olmoe_tiny),
                  "nemotron_h": (nemotron_h, nemotron_h.nemotron_h_tiny),
                  }[family]
    monkeypatch.setattr(engine_programs.jax, "default_backend",
                        lambda: backend)
    eng, _ = _pool_stats(model, cfg(), prefix_cache=False)
    monkeypatch.undo()
    monkeypatch.setattr(moe, "DENSE_MAX_TOKENS", 32)
    assert [moe.expert_kernel_engages(r) for r in (16, 32, 64)] == [
        False, False, True]
    clear_ring()
    tracing.enable_tracing()
    try:
        eng.start()
        rng = np.random.default_rng(4)
        for n in (9, 40, 70):
            assert len(list(eng.submit(rng.integers(1, 100, n),
                                       max_new_tokens=3).tokens())) == 3
        eng.stop()
        spans = tracing.recorded_spans("engine.dispatch_prefill")
    finally:
        tracing.disable_tracing()
        clear_ring()
    assert eng.error is None
    got = [(s["attrs"]["token_rows"], s["attrs"]["expert_kernel"])
           for s in spans]
    assert got == [(16, 0), (64, int(routes)), (128, int(routes))]
    stats = eng.stats()
    assert stats["prefill_dispatches"] == 3
    assert stats["expert_kernel_dispatches"] == 2 * routes


def test_ring_stays_empty_with_no_session_and_tracing_off(tiny):
    clear_ring()
    assert not tracing.recording()
    eng = make_engine(tiny)
    eng.start()
    req = eng.submit(np.arange(1, 30), max_new_tokens=12)
    assert len(list(req.tokens())) == 12
    eng.stop()
    assert tracing.recorded_spans() == []
    assert req.trace_ctx is None and req.breakdown is not None
    # the stamps behind the breakdown are always on
    assert req.start_t is not None and req.ready_t >= req.start_t
    assert req.breakdown.keys() == {f"{s}_s" for s in _STAGES}


def test_the_accounts_count_with_no_session_and_tracing_off(tiny):
    """The account's integers are the operator's: they count whether or not
    a span is recorded, and the ring stays empty."""
    clear_ring()
    assert not tracing.recording()
    eng = make_engine(tiny, decode_chunk=4)
    eng.start()
    assert len(list(eng.submit(np.arange(1, 30),
                               max_new_tokens=7).tokens())) == 7
    wait_idle(eng)
    eng.stop()
    assert tracing.recorded_spans() == []
    assert account(eng) == dict(
        decode_slot_steps=32, decode_delivered=6, decode_overrun_tail=2,
        decode_overrun_ahead=0, decode_vacant=24,
        prefill_token_rows=32, prefill_new_tokens=29,
        retirements_foreseen=1, slots_handed_over=0)


def test_phase_follows_enable_tracing_and_costs_nothing_off():
    clear_ring()
    off = tracing.phase("engine.iteration")
    assert not off and off is tracing.phase("engine.admit")
    with off as ph:
        ph.set(seq=1)
    assert tracing.recorded_spans() == []
    tracing.enable_tracing()
    try:
        with tracing.phase("engine.iteration", trace_id="t" * 16) as it:
            with tracing.phase("engine.admit") as ph:
                ph.set(admitted=2)
            assert tracing.current_context() is it
    finally:
        tracing.disable_tracing()
    admit, iteration = tracing.recorded_spans("engine.")
    assert iteration["trace_id"] == admit["trace_id"] == "t" * 16
    assert admit["parent_id"] == iteration["span_id"]
    assert admit["attrs"] == {"admitted": 2}
    assert iteration["parent_id"] is None
    clear_ring()


def test_stop_joins_the_watcher(tiny):
    eng = make_engine(tiny)
    eng.start()
    assert len(list(eng.submit(np.arange(1, 20),
                               max_new_tokens=4).tokens())) == 4
    watcher = eng._watcher
    assert watcher.is_alive()
    eng.stop()
    assert not watcher.is_alive() and not eng._thread.is_alive()
    assert not any(t.name == "llm-ready-watcher" and t is watcher
                   for t in threading.enumerate())


def test_failed_prefill_ends_the_requests_the_loop_had_taken(tiny):
    """A prefill dispatch that raises (on the chip: a program that does
    not fit) kills the loop; the requests it had taken off the queue are
    in neither ``_active`` nor ``_waiting``, and their streams end too."""
    eng = make_engine(tiny)

    def boom(part, bucket, ph):
        raise MemoryError("prefill program does not fit")

    eng._dispatch_prefill = boom
    reqs = [eng.submit(np.arange(1, 20 + i), max_new_tokens=4)
            for i in range(3)]
    eng.start()
    done = []

    def read(r):
        with pytest.raises(RuntimeError, match="engine loop failed"):
            list(r.tokens())
        done.append(r)

    threads = [threading.Thread(target=read, args=(r,), daemon=True)
               for r in reqs]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert len(done) == 3 and isinstance(eng.error, MemoryError)
    eng.stop()


def test_engine_programs_carry_their_static_facts_in_their_names(tiny):
    cfg, params = tiny
    eng = make_engine(tiny)
    programs = eng._programs
    decode = programs._decode_paged(8, 4)
    prefill = programs._prefill_paged(2)
    pools = programs.pools
    i32 = partial(jnp.zeros, dtype=jnp.int32)
    text = decode.lower(
        params, *pools, i32((4, 4)), i32((4,)), i32((4,)),
        jnp.zeros((4,), bool), jnp.zeros((4,), jnp.float32),
        jax.random.key(0)).as_text()
    assert re.search(r"module @jit_paged_decode_c\d+_w\d+\b", text)
    assert "@jit_paged_decode_c8_w4" in text
    text = prefill.lower(
        params, *pools, i32((2, 2)), i32((2, 16)), i32((2,)), i32((2,)),
        jnp.zeros((2,), jnp.float32), jax.random.key(0)).as_text()
    assert re.search(r"module @jit_paged_prefill_w\d+\b", text)
    assert "@jit_paged_prefill_w2" in text
    assert "@jit_scatter_firsts" in programs.scatter_firsts.lower(
        i32((4,)), i32((2,)), i32((2,))).as_text()


def test_the_prefill_kernel_is_named_in_a_program_lowered_for_the_tpu():
    """A prefill program over the rule (two rows of 2048 tokens over 16
    pages at 16 heads of 128: 512 MiB of scores) holds the kernel under its
    name where it is lowered for the TPU and nothing of it where it is
    lowered for the CPU; under the rule (64 tokens) neither does."""
    cfg = llama.LlamaConfig(vocab_size=64, d_model=128, n_layers=2,
                            n_heads=16, n_kv_heads=2, head_dim=128,
                            d_ff=256, remat="none")
    params = jax.eval_shape(partial(llama.init_params, cfg),
                            jax.random.key(0))
    pool = jax.ShapeDtypeStruct((2, 40, 128, 2, 128), jnp.bfloat16)
    scale = jax.ShapeDtypeStruct((2, 1, 1, 1), jnp.float32)
    fn = jax.jit(partial(PagedLLMEngine._paged_prefill_impl, cfg,
                         page_size=128, quantized=False))

    def kernels(tokens, platform):
        i32 = partial(jax.ShapeDtypeStruct, dtype=jnp.int32)
        text = fn.trace(
            params, pool, pool, scale, scale, i32((2, 16)),
            i32((2, tokens)), i32((2,)), i32((2,)),
            jax.ShapeDtypeStruct((2,), jnp.float32),
            jax.eval_shape(lambda: jax.random.key(0))).lower(
                lowering_platforms=(platform,)).as_text()
        return re.findall(r'kernel_name = "(\w+)"', text)

    assert kernels(2048, "tpu") == ["paged_prefill_attn"]
    assert kernels(2048, "cpu") == []
    assert kernels(64, "tpu") == []


def test_flash_kernels_are_named_in_the_lowered_program():
    """Lowered for the TPU (no chip needed to lower): the three kernels'
    names are what the HLO instructions, and so a device trace's
    operation events, are called."""
    from ray_tpu.ops.flash_attention import flash_attention

    q = jax.ShapeDtypeStruct((1, 256, 4, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 256, 2, 128), jnp.bfloat16)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True).astype(
            jnp.float32).sum()

    forward = jax.jit(partial(flash_attention, causal=True)).trace(
        q, kv, kv).lower(lowering_platforms=("tpu",)).as_text()
    assert re.findall(r'kernel_name = "(\w+)"', forward) == ["flash_fwd"]
    both = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(
        q, kv, kv).lower(lowering_platforms=("tpu",)).as_text()
    assert re.findall(r'kernel_name = "(\w+)"', both) == [
        "flash_fwd", "flash_dq", "flash_dkv"]
