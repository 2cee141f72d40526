"""The paged engine's accounts (PR 38): what ``stats()`` says its two
programs computed, slot-step by slot-step and row by row, against the
spans of the same dispatches; what it says of the stores its plan states
(the pools' own bytes, a slot's state); and those stores as
``serve/engine_programs.py`` states them, once, for the engine and for
whoever lowers its programs. All on the CPU with the tiny configs."""

import importlib
from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.models import llama
from ray_tpu.serve import engine_programs
from ray_tpu.serve.paged_llm import PagedLLMEngine
from ray_tpu.util import tracing
from toy_engine import (DECODE_ACCOUNT, HANDOVER_ACCOUNT, PAGE, account,
                        clear_ring, make_engine, pool_stats, served,
                        tiny_llama, wait_idle)


@pytest.fixture(scope="module")
def tiny():
    return tiny_llama()


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

    emits, total, handed = served(tiny, submit, decode_chunk=chunk,
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

    emits, total, handed = served(tiny, submit, decode_chunk=4, max_batch=2)
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

    emits, total, handed = served(tiny, submit, decode_chunk=4,
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

    emits, total, handed = served(tiny, submit, decode_chunk=4, max_batch=2)
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

    emits, total, handed = served(tiny, submit, decode_chunk=4, max_batch=2)
    for a in emits:
        assert (a["tokens"] + a["overrun_tail"] + a["overrun_ahead"]
                + a["vacant"]) == a["slot_steps"] == 8
    assert total["retirements_foreseen"] == len(budgets)
    assert total["slots_handed_over"] == handed >= 3
    assert total["decode_overrun_ahead"] == 0
    assert total["decode_delivered"] == sum(budgets) - len(budgets)
    assert (total["decode_delivered"] + total["decode_overrun_tail"]
            + total["decode_vacant"]) == total["decode_slot_steps"]
    none, untraced, _ = served(tiny, submit, trace=False, decode_chunk=4,
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

    _, total, _ = served(tiny, submit)
    assert (total["prefill_token_rows"], total["prefill_new_tokens"]) == (
        64, 50)


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
        eng, stats = pool_stats(llama, cfg,
                                 kv_dtype="int8" if int8 else "bf16")
        row = cfg.n_layers * 2 * cfg.n_kv_heads * cfg.head_dim
        want = row + cfg.n_layers * 2 * cfg.n_kv_heads * 4 if int8 \
            else 2 * row
        rows, dense = "k+v", 2 * row
    elif family == "laguna":
        cfg = laguna.laguna_tiny()
        eng, stats = pool_stats(laguna, cfg)
        want = dense = cfg.n_layers * 2 * cfg.n_kv_heads * cfg.head_dim * 2
        rows = "k+v"
    else:
        cfg = dots3_note.dots3_note_tiny()
        eng, stats = pool_stats(dots3_note, cfg)
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


# -- the stores and the programs' call, stated once --------------------------

_FAMILIES = ("llama", "olmoe", "laguna", "falcon_h1", "dots3_note",
             "nemotron_h", "smallthinker", "keye_vl", "granite_moe_hybrid",
             "lfm2_moe")
_SIZES = dict(max_batch=3, num_pages=10, page_size=PAGE)


def _tiny_of(family):
    """A family's module, its toy configuration and its weights' shapes."""
    model = importlib.import_module(f"ray_tpu.models.{family}")
    cfg = getattr(model, f"{family}_tiny")()
    return model, cfg, jax.eval_shape(partial(model.init_params, cfg),
                                      jax.random.key(0))


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("family", _FAMILIES)
def test_store_shapes_are_the_stores_the_engine_allocates(family, kv_dtype):
    """``store_shapes`` of each family's toy plan is, in order, the shapes
    and types of the pools and the state ``EnginePrograms`` holds, pages
    and state empty and scales one; over a plan that keeps rows (a
    latent's, an indexer's keys beside the twins) it refuses int8, which
    is where ``EnginePrograms`` meets the refusal."""
    model, cfg, params = _tiny_of(family)
    keeps_rows = any(run.attends and (run.rows or run.beside)
                     for run in model.layer_plan(cfg))
    if keeps_rows and kv_dtype == "int8":
        for build in (engine_programs.store_shapes,
                      partial(engine_programs.EnginePrograms, params=params)):
            with pytest.raises(ValueError, match="only K/V twins are stored"):
                build(cfg, kv_dtype=kv_dtype, **_SIZES)
        return
    pools, state, kept = engine_programs.store_shapes(cfg, kv_dtype=kv_dtype,
                                                      **_SIZES)
    programs = engine_programs.EnginePrograms(cfg, params, kv_dtype=kv_dtype,
                                              **_SIZES)

    def said(arrays):
        return [(a.shape, a.dtype) for a in arrays]

    assert said(pools) == said(programs.pools)
    assert said(state) == said(programs.state)
    assert said(kept) == said(programs.kept)
    assert bool(state) == (programs.recurrent is not None)
    held = programs.pools
    for at, a in enumerate(held):
        # a scale pool stands two behind its pages [L, P, page, nkv, hd]
        scale = a.ndim == 4 and at >= 2 and held[at - 2].ndim == 5
        assert float(a.min()) == float(a.max()) == float(scale)
    assert all(float(abs(a).max()) == 0.0 for a in programs.state)


@pytest.mark.parametrize("program,static", [
    ("decode", {"chunk": 4}), ("prefill", {})], ids=["decode", "prefill"])
@pytest.mark.parametrize("family", ["llama", "dots3_note", "falcon_h1",
                                    "nemotron_h", "keye_vl", "lfm2_moe"])
def test_a_bound_program_is_the_one_the_engine_jits(monkeypatch, family,
                                                    program, static):
    """``bound_program`` gives the body ``EnginePrograms._program`` jits,
    bound to the same facts, and the donated positions its order gives
    for the plan's stores (pages alone; rows; twins beside a state; a
    state and pages in different layers; keys beside the twins) are the
    ones the engine's jit is built with."""
    _, cfg, params = _tiny_of(family)
    programs = engine_programs.EnginePrograms(cfg, params, kv_dtype="bf16",
                                              **_SIZES)
    jitted = []
    monkeypatch.setattr(
        engine_programs, "_named_jit",
        lambda name, fn, donate_argnums: jitted.append(
            (name, fn, donate_argnums)))
    if program == "decode":
        programs._decode_paged(static["chunk"], 2)
    else:
        programs._prefill_paged(2)
    (name, fn, donated), = jitted
    assert name == {"decode": "paged_decode_c4_w2",
                    "prefill": "paged_prefill_w2"}[program]
    body, order = engine_programs.bound_program(
        cfg, program, page_size=PAGE, kv_dtype="bf16", **static)
    assert (fn.func, fn.args, fn.keywords) == (body.func, body.args,
                                               body.keywords)
    pools, state, kept = engine_programs.store_shapes(cfg, kv_dtype="bf16",
                                                      **_SIZES)
    state = order.carried(state, kept)
    assert donated == order.donated(len(pools), len(state))
    # the stores, and nothing else, among a call's arguments
    inputs = dict.fromkeys(order.inputs + order.beside_state, "input")
    args = order.arguments("weights", pools, inputs, state)
    assert [args[i] for i in donated] == [*pools, *state]
