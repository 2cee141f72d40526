"""Which kernels a dispatch says its program holds (the rules the
programs are traced by, on the host's own shapes: ``EnginePrograms.
prefill_kernels``, ``decode_kernels``) on the dispatch spans and in
``stats()``, and the names the device programs and their kernels carry in
lowered text. All on the CPU with the tiny configs; an engine is TOLD it
finds a TPU where a rule is asked, and its programs still lower for the
CPU."""

import dataclasses
import re
from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from engine_lowering import traced
from ray_tpu.models import llama
from ray_tpu.serve.engine_programs import _DECODE, _PREFILL
from ray_tpu.serve.paged_llm import PagedLLMEngine
from ray_tpu.util import tracing
from toy_engine import (PAGE, clear_ring, decode_spans_of, make_engine,
                        note_engine_on, pool_stats, tiny_llama)


@pytest.fixture(scope="module")
def tiny():
    return tiny_llama()


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
    eng = note_engine_on(monkeypatch, backend, index_topk=topk)
    rng = np.random.default_rng(3)
    decodes, stats = decode_spans_of(
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
        eng = note_engine_on(monkeypatch, backend, index_topk=64)
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
    eng = note_engine_on(monkeypatch, backend, index_topk=topk,
                          index_dim=width)
    said = eng._programs.decode_kernels(2)
    assert (said["latent_kernel"], said["index_kernel"]) == (latent, index)
    rng = np.random.default_rng(3)
    decodes, stats = decode_spans_of(
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
    decodes, stats = decode_spans_of(
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
    eng, _ = pool_stats(model, cfg(), prefix_cache=False)
    monkeypatch.undo()
    decodes, stats = decode_spans_of(eng, [np.arange(1, 40)], new_tokens=5)
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
    eng, _ = pool_stats(model, cfg(), prefix_cache=False)
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


def test_engine_programs_carry_their_static_facts_in_their_names(tiny):
    eng = make_engine(tiny)
    programs = eng._programs
    i32 = partial(jnp.zeros, dtype=jnp.int32)
    held = (programs.params, programs.pools)
    text = programs._decode_paged(8, 4).lower(*_DECODE.arguments(*held, dict(
        table=i32((4, 4)), tokens=i32((4,)), lengths=i32((4,)),
        active=jnp.zeros((4,), bool), temps=jnp.zeros((4,), jnp.float32),
        key=jax.random.key(0)), programs.state)).as_text()
    assert re.search(r"module @jit_paged_decode_c\d+_w\d+\b", text)
    assert "@jit_paged_decode_c8_w4" in text
    text = programs._prefill_paged(2).lower(*_PREFILL.arguments(*held, dict(
        table_rows=i32((2, 2)), tokens=i32((2, 16)), slens=i32((2,)),
        starts=i32((2,)), temps=jnp.zeros((2,), jnp.float32),
        key=jax.random.key(0)), programs.state)).as_text()
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

    def kernels(tokens, platform):
        text = traced(None, llama, cfg, "prefill", (2, tokens, 16),
                      num_pages=40, slots=2).lower(
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
