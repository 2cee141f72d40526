"""Keye-VL-2.0's block (``models/keye_vl.py``), what it forced in the ops
(a selection in the K/V page kernels and their plain formulations, the
indexer's pieces where a second model can import them, rotary tables from
three position axes) and in the engine (a run that keeps the K/V twins and
an index key beside them: ``LayerStack.beside``), and the model through the
paged engine's normal path, at a small size on the CPU in float32: three
layers, hidden 64, 8 query heads on 2 KV heads of 16, an indexer of 4 heads
of 8 keeping 8 keys of contexts of 40 and more, 8 experts of 32, 2 a token,
vocabulary 128. The plain reference is the benchmark's family file, the one
statement of it (``benchmark/families/keye_vl.py:logits``), which imports
nothing from the program."""

import re
from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import reference, serving
from benchmark.families import keye_vl as family
from ray_tpu.models import keye_vl, olmoe
from ray_tpu.models.llama import LayerStack
from ray_tpu.ops import index_select
from ray_tpu.ops import paged_decode_attention as pda
from ray_tpu.ops import paged_prefill_attention as ppa
from ray_tpu.ops.paged_attention import PageRow, write_kv
from ray_tpu.ops.rope import mrope_sin_cos, rope_sin_cos
from ray_tpu.serve import engine_programs
from ray_tpu.serve.paged_llm import PagedLLMEngine

# Float32 against float32: the program and the reference differ in the
# order of their sums, in rsqrt against 1/sqrt and in how the selection's
# set is made (``lax.top_k`` and a threshold against two stable sorts:
# the same set, ties and all); over three layers with logits of order 1
# that is 2e-6 (measured here). 1e-4 is fifty times that and a thousand
# times under what a wrong block shows (each departure of the reference
# moves the logits by 0.04 to 1.5). Through the engine the comparison is
# of tokens, as the benchmark's, and the engine keeps K and V in bf16
# pages whatever the model's type. At this size that rounding decides
# tokens: it moves the stream by a hundredth, the next layer's index
# scores with it, and ONE key tipped across the boundary of a selection
# of 8 is an eighth of a layer's attention (five seeds of the two
# prompts below through bf16 pages: 1-2 tokens of 12 not the reference's
# own, 0.06-0.53 short; at the published 2,048 keys a tipped key is a
# two-thousandth, and the chip's readings are in PERF.md). So the test
# hands the engine float32 K/V pools, which changes no program (a pool's
# type is its array's), and asks for what float32 then gives: EVERY token
# the reference's own greedy choice (five seeds: all 120 of 120).
TOL = 1e-4
GAP_TOL = 0.1
PAGE, TOPK = 8, 8
CONFIG = {
    "family": "keye_vl", "attention_bias": False, "decoder_sparse_step": 1,
    "head_dim": 16, "hidden_act": "silu", "hidden_size": 64,
    "intermediate_size": 192, "max_position_embeddings": 256,
    "mlp_only_layers": [], "model_type": "KeyeVL2",
    "moe_intermediate_size": 32, "norm_topk_prob": True,
    "num_attention_heads": 8, "num_experts": 8, "num_experts_per_tok": 2,
    "num_hidden_layers": 3, "num_key_value_heads": 2,
    "num_local_experts": 8, "rms_norm_eps": 1e-6,
    "rope_scaling": {"mrope_section": [2, 3, 3], "rope_type": "default",
                     "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 4,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": TOPK},
    "tie_word_embeddings": False, "vocab_size": 128,
    "torch_dtype": "float32", "system": {}}
DEPARTURES = {"qk_norm": {"qk_norm": "none"}, "indexer": {"indexer": "none"},
              "topk": {"topk": 4}, "index_norm": {"index_norm": "none"},
              "index_rope": {"index_rope": "none"},
              "norm_topk_prob": {"norm_topk_prob": False}}


def make_params(cfg, seed=0):
    """Seeded weights with norm vectors away from one, so that each norm
    is seen to be applied."""
    params = keye_vl.init_params(cfg, jax.random.key(seed))
    key = jax.random.key(seed + 1)
    for name in ("attn_norm", "mlp_norm", "k_norm", "index_norm"):
        key, sub = jax.random.split(key)
        stack = params["blocks"][name]
        params["blocks"][name] = stack * (
            1.0 + 0.3 * jax.random.normal(sub, stack.shape))
    return params


@pytest.fixture(scope="module")
def tiny():
    cfg = family.model_config(CONFIG)
    return cfg, make_params(cfg)


def tokens_of(seed, shape):
    return jnp.asarray(np.random.default_rng(seed).integers(
        1, 128, shape, dtype=np.int32))


# -- the module against the reference ----------------------------------------

def test_the_config_and_the_layer_plan(tiny):
    cfg, params = tiny
    assert cfg == keye_vl.keye_vl_tiny()
    (run,) = keye_vl.layer_plan(cfg)
    assert (run.key, run.kind, run.window, run.layers) == (
        None, "full", None, 3)
    assert run.rows is None and run.selects == TOPK
    assert run.beside == (PageRow("index_key", 8, "float32"),)
    # q | k | v | index queries | index key | index weights
    assert cfg.projected == (128, 160, 192, 224, 232, 236)
    assert params["blocks"]["w_in"].shape == (3, 64, 236)
    assert params["blocks"]["router"].dtype == jnp.float32
    published = keye_vl.keye_vl_2_30b_a3b()
    assert published.projected[-1] == 4096 + 512 + 512 + 1024 + 64 + 16
    assert keye_vl.layer_plan(published)[0].selects == 2048
    with pytest.raises(ValueError, match="mrope_sections"):
        keye_vl.keye_vl_tiny(mrope_sections=(2, 2, 2))
    # every other model's plan keeps nothing beside its twins
    assert LayerStack(None, "full", None, 1).beside is None


def test_forward_is_the_references(tiny):
    cfg, params = tiny
    tokens = tokens_of(1, (2, 40))      # 40 keys, 8 kept
    got = keye_vl.forward(cfg, params, tokens)
    want = family.logits(CONFIG, params, tokens)
    assert float(jnp.std(want)) > 0.5
    np.testing.assert_allclose(got, want, atol=TOL)


@pytest.mark.parametrize("name", list(DEPARTURES))
def test_each_departure_alone_moves_the_logits(tiny, name):
    cfg, params = tiny
    tokens = tokens_of(1, (2, 40))
    want = family.logits(CONFIG, params, tokens)
    other = family.logits(CONFIG, params, tokens, **DEPARTURES[name])
    assert float(jnp.max(jnp.abs(other - want))) > 100 * TOL, name


def test_the_reference_refuses_an_unknown_reading(tiny):
    _, params = tiny
    with pytest.raises(ValueError, match="chunks"):
        family.logits(CONFIG, params, tokens_of(1, (1, 8)), chunks="exact")
    # the chunk sizes tile the indexer's scores and change no number
    assert CONFIG["sa_config"]["q_chunk_size"] == 512


def test_mrope_with_three_unequal_axes(tiny):
    """The module's rotary tables from three position axes that differ
    (an image's tokens: one time, rows and columns) against the
    reference's, through the whole forward; equal axes are plain rotary,
    bit for bit; and the departure ``mrope="plain"`` (axis 0 alone) is
    told apart only where the axes differ."""
    cfg, params = tiny
    tokens = tokens_of(3, (2, 24))
    grid = np.arange(24)
    axes = jnp.asarray(np.broadcast_to(np.stack(
        [np.where(grid < 8, grid, 8 + (grid - 8) // 16),     # time
         np.where(grid < 8, grid, 8 + (grid - 8) // 4),      # height
         np.where(grid < 8, grid, 8 + (grid - 8) % 4)]       # width
    )[:, None], (3, 2, 24)).astype(np.int32))
    got = keye_vl.forward(cfg, params, tokens, axes=axes)
    want = family.logits(CONFIG, params, tokens, axes=axes)
    np.testing.assert_allclose(got, want, atol=TOL)
    text = family.logits(CONFIG, params, tokens)
    plain = family.logits(CONFIG, params, tokens, axes=axes, mrope="plain")
    assert float(jnp.max(jnp.abs(want - text))) > 100 * TOL
    assert float(jnp.max(jnp.abs(want - plain))) > 100 * TOL
    np.testing.assert_array_equal(
        text, family.logits(CONFIG, params, tokens, mrope="plain"))
    positions = jnp.arange(12)[None]
    for got, want in zip(
            mrope_sin_cos(jnp.broadcast_to(positions, (3, 1, 12)), 16,
                          (2, 3, 3), theta=1e7),
            rope_sin_cos(positions, 16, theta=1e7)):
        np.testing.assert_array_equal(got, want)
    with pytest.raises(ValueError, match="sections"):
        mrope_sin_cos(jnp.zeros((3, 1, 4), jnp.int32), 16, (2, 3, 4))


# -- the K/V kernels with a selection -----------------------------------------

def _pages_case(seed=0, slots=3, heads=8, kv_heads=2, hd=16, pages=5):
    """K/V twins of ``slots`` sequences of 31, 17 and 40 tokens in pages of
    8, layer 1 of 2, behind a shuffled page table."""
    rng = np.random.default_rng(seed)
    lengths = np.array([31, 17, 40][:slots])
    table = rng.permutation(slots * pages).reshape(slots, pages).astype(
        np.int32)
    pools = [jnp.zeros((2, slots * pages, PAGE, kv_heads, hd), jnp.bfloat16)
             for _ in range(2)] + [jnp.ones((2, 1, 1, 1), jnp.float32)] * 2
    t = pages * PAGE
    k, v = (jnp.asarray(rng.normal(size=(slots, t, kv_heads, hd)),
                        jnp.bfloat16) for _ in range(2))
    pos = np.broadcast_to(np.arange(t), (slots, t))
    pools = write_kv(*pools, 1, k, v, jnp.asarray(
        np.take_along_axis(table, pos // PAGE, 1)), jnp.asarray(pos % PAGE),
        False)
    return dict(pools=pools, table=jnp.asarray(table), lengths=lengths,
                rng=rng, heads=heads, hd=hd, keys=t)


def test_decode_attention_with_a_selection():
    """The plain formulation and the Pallas kernel (interpret) over a
    selection agree with each other and with plain softmax attention over
    the selected keys alone; a selection of every key a slot sees is no
    selection; a dead slot reads nothing."""
    case = _pages_case()
    rng, lengths, keys = case["rng"], case["lengths"], case["keys"]
    q = jnp.asarray(rng.normal(size=(3, case["heads"], case["hd"])),
                    jnp.bfloat16)
    pos = jnp.asarray(lengths - 1)
    active = jnp.asarray([True, True, True])
    seen = np.arange(keys)[None] < lengths[:, None]
    selected = seen & (rng.random((3, keys)) < 0.3)
    selected[:, 0] = True       # a slot's set is never empty
    args = (q, *case["pools"], 1, case["table"], pos, active)
    plain = pda.paged_decode_attention_reference(*args, jnp.asarray(selected))
    kernel = pda.paged_decode_attention_kernel(
        *args, jnp.asarray(selected), interpret=True)
    # bf16 outputs of unit size: one unit in the last place apart at most
    np.testing.assert_allclose(kernel.astype(np.float32),
                               plain.astype(np.float32), atol=2e-2)
    everything = pda.paged_decode_attention_reference(*args)
    assert float(jnp.max(jnp.abs(
        plain.astype(jnp.float32) - everything.astype(jnp.float32)))) > 0.1
    for fn in (pda.paged_decode_attention_reference,
               lambda *a: pda.paged_decode_attention_kernel(
                   *a, interpret=True)):
        np.testing.assert_allclose(
            fn(*args, jnp.asarray(seen)).astype(np.float32),
            fn(*args).astype(np.float32), atol=1e-6)
    # the entry is the plain formulation off the TPU
    np.testing.assert_array_equal(
        pda.paged_decode_attention(*args, selected=jnp.asarray(selected)),
        plain)
    dead = pda.paged_decode_attention_kernel(
        q, *case["pools"], 1, case["table"], pos,
        jnp.asarray([True, False, True]), jnp.asarray(selected),
        interpret=True)
    assert not np.asarray(dead[1]).any()
    np.testing.assert_array_equal(dead[0], kernel[0])


@pytest.mark.parametrize("t,starts", [(16, (24, 8)), (32, (0, 8))],
                         ids=["suffix", "cold"])
def test_prefill_attention_with_flags(t, starts, monkeypatch):
    """The plain formulation (whole and in blocks of queries) and the
    Pallas kernel (interpret) over a selection's flags agree; flags of
    every key are no flags."""
    case = _pages_case(seed=1, slots=2)
    rng, keys = case["rng"], case["keys"]
    q = jnp.asarray(rng.normal(size=(2, t, case["heads"], case["hd"])),
                    jnp.bfloat16)
    starts = jnp.asarray(starts, jnp.int32)
    slens = jnp.asarray([t, t - 3], jnp.int32)
    qpos = np.asarray(starts)[:, None] + np.arange(t)
    causal = np.arange(keys)[None, None] <= qpos[..., None]
    flags = (causal & (rng.random((2, t, keys)) < 0.4))
    flags[:, :, 0] = True
    flags = jnp.asarray(flags.astype(np.int8))
    args = (q, *case["pools"], 1, case["table"], starts, slens)
    plain = ppa.paged_prefill_attention_reference(*args, flags)
    kernel = ppa.paged_prefill_attention_kernel(*args, flags,
                                                interpret=True)
    valid = np.arange(t)[None] < np.asarray(slens)[:, None]
    np.testing.assert_allclose(
        np.asarray(kernel.astype(jnp.float32))[valid],
        np.asarray(plain.astype(jnp.float32))[valid], atol=2e-2)
    ones = jnp.ones_like(flags)
    for fn in (ppa.paged_prefill_attention_reference,
               lambda *a: ppa.paged_prefill_attention_kernel(
                   *a, interpret=True)):
        np.testing.assert_allclose(
            np.asarray(fn(*args, ones).astype(jnp.float32))[valid],
            np.asarray(fn(*args).astype(jnp.float32))[valid], atol=1e-6)
    assert float(jnp.max(jnp.abs(
        plain.astype(jnp.float32)
        - ppa.paged_prefill_attention_reference(*args).astype(
            jnp.float32)))) > 0.1
    # in blocks of queries: each block takes its own rows of the flags
    monkeypatch.setattr(ppa, "SCORES_MAX_BYTES", 4 * 2 * 8 * t * keys - 1)
    assert ppa.query_block(2, t, 8, keys, None) == (16 if t > 16 else t)
    blocked = ppa.paged_prefill_attention_reference(*args, flags)
    np.testing.assert_allclose(blocked.astype(np.float32),
                               plain.astype(np.float32), atol=1e-6)
    with pytest.raises(ValueError, match="sliding"):
        ppa.paged_prefill_attention_reference(*args, flags, window=4)


def test_a_narrow_index_key_is_scored_where_it_lies():
    """The index kernel (interpret) over keys narrower than their pool's
    lanes (8 of 128: Keye-VL's 64 of 128) against the gathered
    formulation: the queries meet the rows' spare lanes with zeros."""
    rng = np.random.default_rng(4)
    slots, pages, heads, width = 2, 4, 4, 8
    pool = jnp.zeros((2, slots * pages, 128, 128), jnp.bfloat16)
    pool = pool.at[..., :width].set(jnp.asarray(
        rng.normal(size=(2, slots * pages, 128, width)), jnp.bfloat16))
    table = jnp.asarray(rng.permutation(slots * pages).reshape(
        slots, pages).astype(np.int32))
    q = jnp.asarray(rng.normal(size=(slots, 1, heads, width)), jnp.bfloat16)
    w = jnp.asarray(rng.normal(size=(slots, 1, heads)), jnp.float32)
    count = jnp.asarray([300, 129], jnp.int32)
    want = index_select._scored_gathered(q, w, pool, 1, table, count)
    padded = jnp.pad(q[:, 0], ((0, 0), (0, 0), (0, 128 - width)))
    got = index_select.index_decode_scores_kernel(
        padded, w[:, 0], pool, 1, table, count, interpret=True)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # a pool's rows are whole lanes, so the rule holds wherever it selects
    assert index_select.index_kernel_engages(128, 64, 2048, pool.shape[-1])
    index = index_select.IndexInputs(q, w, None, 256)
    np.testing.assert_array_equal(
        index_select.decode_index_scores(index, pool, 1, table, count), want)
    # a table of no more keys than the layer keeps: nothing scored
    assert index_select.decode_selection(
        index._replace(topk=512), pool, 1, table, count) is None
    chosen = index_select.decode_selection(index, pool, 1, table, count)
    assert np.asarray(chosen).sum(-1).tolist() == [256, 129]


# -- the engine --------------------------------------------------------------

def test_the_engine_asks_the_module_for_what_its_plan_uses(tiny):
    cfg, _ = tiny
    assert engine_programs._model_module(cfg) is keye_vl
    # a plan that picks keys among K/V rows over a module with no indexer
    plan = keye_vl.layer_plan(cfg)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(olmoe, "layer_plan", lambda cfg: plan, raising=False)
        with pytest.raises(TypeError, match="index_projections"):
            engine_programs._model_module(olmoe.olmoe_tiny())


def test_a_plan_that_keeps_twins_and_an_index_row_sizes_its_pools(tiny):
    """The pools of a run that keeps K/V and an index key: the twins' four
    and the row's one behind them, each over the layers that keep it; a
    plan of two formats keeps each format's pools to its own layers."""
    cfg, params = tiny
    plan = keye_vl.layer_plan(cfg)
    (fmt,) = engine_programs._pool_slices(plan)[0]
    assert fmt == (None, PageRow("index_key", 8, "float32"))
    assert engine_programs._pool_slices(plan) == ({fmt: slice(0, 5)}, 5)
    assert engine_programs._pool_layers(plan, fmt) == 3
    assert engine_programs._pool_layers(plan, None) == 0
    eng = PagedLLMEngine(cfg=cfg, params=params, max_batch=2, max_len=64,
                         page_size=PAGE, num_pages=12)
    programs = eng._programs
    assert [p.shape for p in programs.pools] == [
        (3, 12, PAGE, 2, 16), (3, 12, PAGE, 2, 16), (3, 1, 1, 1),
        (3, 1, 1, 1), (3, 12, PAGE, 128)]
    assert programs.page_rows == "k+v,index_key:8"
    assert programs.selects == TOPK and programs.window is None
    assert programs.holds()["page_layers"] == "k+v,index_key:8=3"
    # K and V of 2 x 16 and the index key's whole lanes, bf16, 3 layers
    assert programs.bf16_row_bytes == 3 * 2 * (2 * 2 * 16 + 128)
    assert programs.pages_bytes() == sum(
        p.size * p.dtype.itemsize for p in programs.pools
        if p.shape[1] == 12)
    # two layers that select before two that do not: a format each
    mixed = (plan[0]._replace(layers=2),
             LayerStack("rest", "full", None, 2))
    where, n = engine_programs._pool_slices(mixed)
    assert (where, n) == ({fmt: slice(0, 5), None: slice(5, 9)}, 9)
    assert [engine_programs._pool_layers(mixed, f) for f in where] == [2, 2]
    assert engine_programs._places(mixed) == [(0, None), (0, None)]
    with pytest.raises(ValueError, match="int8"):
        PagedLLMEngine(cfg=cfg, params=params, max_batch=2, max_len=64,
                       page_size=PAGE, num_pages=12, kv_dtype="int8")


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_no_program_sorts_the_index_scores(tiny, program):
    """Lowered for the TPU, neither program of this plan sorts a row of
    index scores or takes a ``top_k`` of one (``kept`` searches for the
    ``topk``-th score: ``ops/index_select.py``); the one ``top_k`` left
    is the router's, over the eight experts."""
    cfg, params = tiny
    programs = PagedLLMEngine(cfg=cfg, params=params, max_batch=2,
                              max_len=64, page_size=PAGE,
                              num_pages=20)._programs
    pages = 4
    assert pages * PAGE > TOPK          # the layers select
    i32 = partial(jnp.zeros, dtype=jnp.int32)
    if program == "decode":
        fn = programs._decode_paged(8, pages)
        ins = (i32((2, pages)), i32((2,)), i32((2,)), jnp.zeros((2,), bool))
    else:
        fn = programs._prefill_paged(pages)
        ins = (i32((2, pages)), i32((2, 16)), i32((2,)), i32((2,)))
    text = fn.trace(params, *programs.pools, *ins,
                    jnp.zeros((2,), jnp.float32),
                    jax.random.key(0)).lower(
        lowering_platforms=("tpu",)).as_text()
    sorted_widths = [
        int(width) for line in text.splitlines()
        if "chlo.top_k" in line or "stablehlo.sort" in line
        for width in re.findall(r"tensor<(?:\d+x)*(\d+)xf32>", line)[:1]]
    assert sorted_widths == [cfg.n_experts]
    assert pages * PAGE not in sorted_widths


@pytest.fixture(scope="module")
def served(tiny):
    """Two prompts through the engine, as ``serving.prepare_engine`` serves
    its reference check: both past ``topk`` keys, the second reusing the
    first's pages, so that its queries score index keys out of shared
    pages."""
    cfg, params = tiny
    rng = np.random.default_rng(2)
    first = rng.integers(1, 128, 50, dtype=np.int32)
    second = np.concatenate([first[:32],
                             rng.integers(1, 128, 19, dtype=np.int32)])
    eng = PagedLLMEngine(cfg=cfg, params=params, max_batch=2, max_len=128,
                         page_size=PAGE, num_pages=40)
    pools = eng._programs.pools             # K and V pages in float32
    pools[:2] = [pool.astype(jnp.float32) for pool in pools[:2]]
    eng.start()
    out = [(p, serving.collect(eng, eng.submit(p, max_new_tokens=12)))
           for p in (first, second)]
    stats = eng.stats()
    eng.stop()
    return params, out, stats


def test_prefill_then_decode_through_the_cache_is_the_references(served):
    params, out, stats = served
    assert stats["prefix_cache"]["hit_pages"] == 4      # 32 tokens
    assert stats["page_layers"] == "k+v,index_key:8=3"
    for prompt, tokens in out:
        assert len(tokens) == 12
        assert reference.token_gap(family.logits, CONFIG, params, prompt,
                                   tokens) == (0.0, 0)


@pytest.mark.parametrize("name", list(DEPARTURES))
def test_each_departure_alone_reads_not_correct(served, name):
    params, out, _ = served

    def logits(*args):
        return family.logits(*args, **DEPARTURES[name])

    gap = max(reference.token_gap(logits, CONFIG, params, prompt, tokens)[0]
              for prompt, tokens in out)
    assert gap > 2 * GAP_TOL


def test_decode_dispatches_count_the_selection(tiny):
    """``engine.dispatch_decode`` counts the rows a layer would read and
    the rows it picks (``kv_selected_share``'s source) for this plan as
    for a latent one."""
    from ray_tpu.util import tracing

    cfg, params = tiny
    eng = PagedLLMEngine(cfg=cfg, params=params, max_batch=2, max_len=64,
                         page_size=PAGE, num_pages=20)
    tracing.drain_spans(1 << 20)
    tracing.enable_tracing()
    try:
        eng.start()
        serving.collect(eng, eng.submit(
            np.arange(1, 31, dtype=np.int32), max_new_tokens=4))
        eng.stop()
        # this engine's own: the flight ring is the process's, and keeps
        # what an earlier test file's engine recorded on this worker
        spans = [s for s in tracing.recorded_spans("engine.dispatch_decode")
                 if s["trace_id"] == eng._trace_id]
    finally:
        tracing.disable_tracing()
        tracing.drain_spans(1 << 20)     # leave no span in the ring
    assert spans
    for span in spans:
        attrs = span["attrs"]
        assert attrs["kv_rows_selected"] == min(attrs["kv_rows_full"], TOPK)
        assert attrs["index_rows"] == attrs["kv_rows_full"] > TOPK
        assert attrs["latent_kernel"] == attrs["index_kernel"] == 0
