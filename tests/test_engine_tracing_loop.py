"""The serving engine measured from inside (PR 24): the loop's phases as
spans while a ``jax.profiler`` session is live (``util/tracing.phase``),
the device's timeline from the watcher thread, the five TTFT stages, and
what the ring holds with tracing off. All on the CPU with the tiny llama
config. (The accounts: ``test_engine_tracing_accounts.py``; which kernels
a dispatch says its program holds: ``test_engine_tracing_kernels.py``.)"""

import threading
import time

import numpy as np
import pytest

import jax

from ray_tpu.serve.llm import _STAGES
from ray_tpu.util import tracing
from toy_engine import (DECODE_ACCOUNT, HANDOVER_ACCOUNT, PAGE,
                        PREFILL_ACCOUNT, account, chunk_emits, clear_ring,
                        handed_over, make_engine, tiny_llama, wait_idle)

PHASES = {"engine.admit", "engine.dispatch_prefill", "engine.wait_arrivals",
          "engine.dispatch_decode", "engine.wait_device", "engine.emit"}


@pytest.fixture(scope="module")
def tiny():
    return tiny_llama()


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
